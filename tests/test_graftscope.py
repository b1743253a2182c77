"""graftscope tracer + CLI: span nesting, thread tags, disabled-mode
zero-cost, Chrome-trace schema, epoch attribution, summarize/diff."""

import json
import threading
import tracemalloc

import pytest

from dynamic_load_balance_distributeddnn_tpu.obs.registry import MetricsRegistry
from dynamic_load_balance_distributeddnn_tpu.obs.trace import (
    EPOCH_CAT,
    Tracer,
    attribution,
    attribution_by_job,
    configure,
    get_tracer,
    load_trace,
)
from dynamic_load_balance_distributeddnn_tpu.obs.scope_cli import main as scope_main


def spans(tracer, name=None):
    out = [e for e in tracer.events() if e[2] == "X"]
    if name is not None:
        out = [e for e in out if e[0] == name]
    return out


# ------------------------------------------------------------------- recording


def test_span_nesting_records_contained_durations():
    tr = Tracer(mode="on")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    (outer,) = spans(tr, "outer")
    inner = spans(tr, "inner")
    assert len(inner) == 2
    o_ts, o_dur = outer[3], outer[4]
    for ev in inner:
        assert ev[3] >= o_ts
        assert ev[3] + ev[4] <= o_ts + o_dur + 1e-3  # us tolerance
    # spans record on exit: children land before their parent
    names = [e[0] for e in tr.events()]
    assert names == ["inner", "inner", "outer"]


def test_spans_carry_thread_ids_and_names():
    tr = Tracer(mode="on")

    def work():
        with tr.span("staged", cat="transfer"):
            pass

    t = threading.Thread(target=work, name="stage-thread-0")
    t.start()
    t.join()
    with tr.span("controller"):
        pass
    by_name = {e[0]: e for e in tr.events()}
    assert by_name["staged"][5] != by_name["controller"][5]  # distinct tids
    meta = [e for e in tr.chrome_events() if e["ph"] == "M"]
    assert {"stage-thread-0", threading.current_thread().name} <= {
        m["args"]["name"] for m in meta
    }


def test_disabled_mode_is_singleton_and_allocation_free():
    import dynamic_load_balance_distributeddnn_tpu.obs.trace as trace_mod

    tr = Tracer(mode="off")
    # singleton no-op: no per-call object
    assert tr.span("a") is tr.span("b")
    with tr.span("c"):
        pass  # warm any lazy state before measuring
    tracemalloc.start()
    try:
        # warm pass inside tracemalloc: one-time interpreter caching (method
        # descriptors etc.) lands here, not in the measured window
        for _ in range(100):
            with tr.span("hot"):
                pass
            with tr.span("device_wait", cat="wait"):
                pass
            tr.instant("beat")
            tr.span_ending_now("jax_trace", "compile", 0.001)
        snap1 = tracemalloc.take_snapshot()
        for _ in range(1000):
            with tr.span("hot"):
                pass
            with tr.span("device_wait", cat="wait"):
                pass
            tr.instant("beat")
            tr.span_ending_now("jax_trace", "compile", 0.001)
        snap2 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    # no PER-CALL allocations attributable to the tracer module: 1000 calls
    # allocating even one object each would be >= ~28 kB; a sub-kB residue
    # is one-off interpreter caching / GC timing, not a per-call cost
    tracer_bytes = sum(
        s.size_diff
        for s in snap2.compare_to(snap1, "filename")
        if s.size_diff > 0
        and s.traceback[0].filename == trace_mod.__file__
    )
    assert tracer_bytes < 1024, f"{tracer_bytes} bytes over 1000 disabled calls"
    assert tr.events() == []


def test_ring_mode_keeps_the_tail():
    tr = Tracer(mode="ring", ring_size=3)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    names = [e[0] for e in tr.events()]
    assert names == ["s7", "s8", "s9"]


def test_instant_and_span_ending_now():
    tr = Tracer(mode="on")
    tr.instant("heartbeat", cat="heartbeat", args={"n": 1})
    tr.span_ending_now("jax_lower", "compile", 0.25)
    (beat,) = [e for e in tr.events() if e[2] == "i"]
    assert beat[0] == "heartbeat" and beat[1] == "heartbeat" and beat[4] == 0.0
    assert beat[6] == {"n": 1}
    (lower,) = spans(tr, "jax_lower")
    # reported after the fact: it ends now, so it began a quarter second ago
    assert lower[4] == pytest.approx(0.25e6) and lower[3] < 0 < lower[3] + lower[4] + 1e5
    off = Tracer(mode="off")
    off.instant("heartbeat")
    off.span_ending_now("jax_lower", "compile", 0.25)
    assert off.events() == []


# ---------------------------------------------------------------- export/schema


def test_chrome_trace_json_schema(tmp_path):
    tr = Tracer(mode="on")
    tr.set_epoch(0)
    with tr.span("epoch", cat=EPOCH_CAT):
        with tr.span("train"):
            pass
    tr.instant("heartbeat", cat="heartbeat")
    path = tr.save(str(tmp_path / "t.trace.json"))
    with open(path) as f:
        payload = json.load(f)
    # extra top-level keys are legal Chrome-trace metadata: `graftscope`
    # carries the unix twin of the perf_counter base so cross-process
    # stitching (merge_trace_files) can realign compile-worker timelines
    assert set(payload) == {"traceEvents", "displayTimeUnit", "graftscope"}
    assert isinstance(payload["graftscope"]["base_unix"], float)
    events = payload["traceEvents"]
    assert events, "trace must not be empty"
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        assert ev["ph"] in ("M", "X", "i", "C")
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    xs = [e for e in events if e["ph"] == "X"]
    assert all(e["args"]["epoch"] == 0 for e in xs)  # epoch stamping
    assert load_trace(path) == events


def test_attribution_and_coverage(tmp_path):
    tr = Tracer(mode="on")
    for epoch in range(2):
        tr.set_epoch(epoch)
        with tr.span("epoch", cat=EPOCH_CAT):
            with tr.span("train"):
                with tr.span("probe", cat="probe"):  # nested non-phase: no double count
                    pass
            with tr.span("validate"):
                pass
    tr.set_epoch(None)
    att = attribution(tr.chrome_events())
    assert sorted(att["epochs"]) == [0, 1]
    for info in att["epochs"].values():
        assert set(info["phases"]) == {"train", "validate"}
        assert 0.0 < info["coverage"] <= 1.0 + 1e-6
        assert sum(info["phases"].values()) <= info["wall_s"] + 1e-6
    assert set(att["phase_totals_s"]) == {"train", "validate"}
    assert att["coverage_min"] is not None


def test_attribution_by_job_groups_tenant_spans():
    """Many-stream engine (ISSUE 18): epoch spans carrying the job tag set
    by ``Tracer.set_job`` on each tenant's driver thread group per tenant;
    untagged legacy spans degrade to the ``-`` pseudo-job."""
    tr = Tracer(mode="on")
    for job, n_epochs in (("alpha", 2), ("beta", 1)):
        tr.set_job(job)
        for epoch in range(n_epochs):
            tr.set_epoch(epoch)
            with tr.span("epoch", cat=EPOCH_CAT):
                with tr.span("train"):
                    pass
        tr.set_epoch(None)
    tr.set_job(None)
    tr.set_epoch(0)
    with tr.span("epoch", cat=EPOCH_CAT):  # untagged single-job shape
        pass
    tr.set_epoch(None)
    att = attribution_by_job(tr.chrome_events())
    assert set(att["jobs"]) == {"alpha", "beta", "-"}
    assert att["jobs"]["alpha"]["epochs"] == 2
    assert att["jobs"]["beta"]["epochs"] == 1
    assert "train" in att["jobs"]["alpha"]["phases"]
    assert (
        att["jobs"]["alpha"]["phases"]["train"]
        <= att["jobs"]["alpha"]["wall_s"] + 1e-6
    )


def test_job_tag_is_thread_local():
    """Concurrent tenants on their own threads must not cross-stamp."""
    tr = Tracer(mode="on")
    barrier = threading.Barrier(2)

    def tenant(job):
        tr.set_job(job)
        tr.set_epoch(0)
        barrier.wait()  # both threads tagged before either emits
        with tr.span("epoch", cat=EPOCH_CAT):
            pass
        tr.set_epoch(None)
        tr.set_job(None)

    threads = [
        threading.Thread(target=tenant, args=(j,)) for j in ("a", "b")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    att = attribution_by_job(tr.chrome_events())
    assert set(att["jobs"]) == {"a", "b"}
    assert all(info["epochs"] == 1 for info in att["jobs"].values())


# ----------------------------------------------------------------------- CLI


@pytest.fixture()
def saved_trace(tmp_path):
    tr = Tracer(mode="on")
    tr.set_epoch(0)
    with tr.span("epoch", cat=EPOCH_CAT):
        with tr.span("train"):
            pass
        with tr.span("validate"):
            pass
    return tr.save(str(tmp_path / "run.trace.json"))


def test_cli_summarize(saved_trace, capsys):
    assert scope_main(["summarize", saved_trace]) == 0
    out = capsys.readouterr().out
    assert "epoch 0" in out and "train" in out and "% wall" in out
    assert scope_main(["summarize", saved_trace, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert "epochs" in payload and payload["coverage_min"] is not None


def test_cli_summarize_epoch_filter_and_errors(saved_trace, capsys):
    assert scope_main(["summarize", saved_trace, "--epoch", "0"]) == 0
    capsys.readouterr()
    assert scope_main(["summarize", saved_trace, "--epoch", "7"]) == 2
    assert scope_main(["summarize", str(saved_trace) + ".missing"]) == 2


def test_cli_summarize_by_job(tmp_path, capsys):
    tr = Tracer(mode="on")
    tr.set_job("tenant0")
    tr.set_epoch(0)
    with tr.span("epoch", cat=EPOCH_CAT):
        with tr.span("train"):
            pass
    tr.set_epoch(None)
    tr.set_job(None)
    path = tr.save(str(tmp_path / "ms.trace.json"))
    assert scope_main(["summarize", path, "--by-job"]) == 0
    out = capsys.readouterr().out
    assert "tenant0" in out and "top phases" in out
    assert scope_main(["summarize", path, "--by-job", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["jobs"]["tenant0"]["epochs"] == 1
    assert "train" in payload["jobs"]["tenant0"]["phases"]
    # per-epoch filtering and per-tenant grouping are different reports
    assert scope_main(["summarize", path, "--by-job", "--epoch", "0"]) == 2


def test_cli_diff(saved_trace, tmp_path, capsys):
    tr = Tracer(mode="on")
    tr.set_epoch(0)
    with tr.span("epoch", cat=EPOCH_CAT):
        with tr.span("train"):
            pass
    other = tr.save(str(tmp_path / "other.trace.json"))
    assert scope_main(["diff", saved_trace, other, "--json"]) == 0
    deltas = json.loads(capsys.readouterr().out)
    assert "train" in deltas and "validate" in deltas
    assert deltas["validate"]["b_s"] == 0.0  # absent in B


# ------------------------------------------------------------------- registry


def test_registry_snapshot_unifies_surfaces():
    from dynamic_load_balance_distributeddnn_tpu.balance.timing import (
        HostOverheadMeter,
    )
    from dynamic_load_balance_distributeddnn_tpu.obs.recorder import MetricsRecorder

    rec = MetricsRecorder()
    rec.record_epoch(
        epoch=0, train_loss=1.0, train_time=0.5, sync_time=0.1, val_loss=1.1,
        accuracy=50.0, partition=[0.5, 0.5], node_time=[0.5, 0.4],
        wallclock_time=2.0, examples_per_s=100.0,
    )
    meter = HostOverheadMeter()
    meter.add_put_s(0.25)
    reg = MetricsRegistry(recorder=rec, tracer=Tracer(mode="off"))
    reg.attach(host_meter=meter)
    snap = reg.snapshot()
    assert snap["recorder"]["examples_per_s"] == 100.0
    assert snap["host"]["put_s"] == 0.25
    assert snap["trace"]["mode"] == "off"
    assert {"total", "foreground", "background"} <= set(snap["compiles"])
    # per-device peak-memory series (ISSUE 13): allocator stats where the
    # backend has them, host-RSS fallback on this CPU tier either way
    mem = snap["memory"]
    assert mem["source"] in ("memory_stats", "host_rss")
    if mem["source"] == "memory_stats":
        assert mem["per_device"] and all(
            m["peak_bytes"] == m["peak_bytes_in_use"] + m["peak_bytes_reserved"] >= 0
            for m in mem["per_device"]
        )
    else:
        assert mem["host_peak_rss_bytes"] > 0
    # the facade honors the None-for-absent contract and rejects typo'd slots
    assert reg.last("mfu_bf16_peak") is None
    assert reg.series("examples_per_s") == [100.0]
    with pytest.raises(ValueError):
        reg.attach(host_metre=meter)


def test_device_peak_memory_counts_what_programs_reserve(monkeypatch):
    """The TPU runtime counts programs' temporaries under "reserved": a chip
    half full read 0.53 GiB in use beside 7.05 reserved (PERF.md, PR 23)."""
    import jax

    from dynamic_load_balance_distributeddnn_tpu.obs.registry import device_peak_memory

    class Chip:
        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 7, "peak_bytes_reserved": 100}

    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()])
    (row,) = device_peak_memory()["per_device"]
    assert (row["peak_bytes_in_use"], row["peak_bytes_reserved"], row["peak_bytes"]) == (7, 100, 107)


# ------------------------------------------------ the spans of a traced run


def _tiny_run(tmp_path, trace, **kw):
    from dynamic_load_balance_distributeddnn_tpu.config import Config
    from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer

    cfg = Config(debug=True, world_size=4, batch_size=128, learning_rate=0.05, epoch_size=2,
                 dataset="mnist", model="mnistnet", dynamic_batch_size=True, seed=11, bucket=8,
                 device=0, trace=trace, trace_dir=str(tmp_path / "traces"),
                 stat_dir=str(tmp_path / "statis"), **kw)
    tr = Trainer(cfg, bundle=synthetic_dataset("mnist", n_train=512, n_test=128),
                 log_to_file=False)
    for epoch in range(2):
        tr.run_epoch(epoch)
    tr._aot.close(False)
    return tr


@pytest.mark.parametrize("path,kw", [("packed", {}), ("elastic:scan", {"packed": "off"})])
def test_a_traced_run_names_the_hosts_waits(tmp_path, path, kw):
    import gc

    import dynamic_load_balance_distributeddnn_tpu.obs.trace as trace_mod

    try:
        tr = _tiny_run(tmp_path, "on", **kw)
        assert trace_mod._gc_hook in gc.callbacks
        assert tr.recorder.meta["exec_path"] == [path, path]
        events = get_tracer().chrome_events()
    finally:
        configure("off")
    assert trace_mod._gc_hook not in gc.callbacks
    done = [e for e in events if e["ph"] == "X"]
    cats = {e["name"]: e["cat"] for e in done}
    for name, cat in [("device_wait", "wait"), ("input_wait", "transfer"), ("gc", "host"),
                      ("jax_trace", "compile"), ("jax_lower", "compile"),
                      ("backend_compile", "compile"), ("trainer_init", "setup"),
                      ("setup_model", "setup")]:
        assert cats.get(name) == cat, (name, sorted(cats))
    epochs = [e for e in done if e["cat"] == EPOCH_CAT]
    waits = [e for e in done if e["name"] == "device_wait"]
    assert len(epochs) == 2 and len(waits) >= 4  # train and validate, both epochs
    for w in waits:  # the controller thread waits inside an epoch, never between two
        assert any(e["tid"] == w["tid"] and e["ts"] <= w["ts"]
                   and w["ts"] + w["dur"] <= e["ts"] + e["dur"] + 1 for e in epochs), w
    # attribution still tiles an epoch by its phase spans alone: the new
    # categories nest inside phases and must not enter its sums
    whole = attribution(events)
    phases_only = attribution([e for e in events if e.get("cat") in (EPOCH_CAT, "phase")])
    assert whole == phases_only and whole["coverage_min"] >= 0.95


def test_with_the_tracer_off_nothing_is_hooked_parsed_or_written(tmp_path):
    import gc

    import dynamic_load_balance_distributeddnn_tpu.obs.trace as trace_mod
    from dynamic_load_balance_distributeddnn_tpu.obs import scopes

    class NeverRead:
        def as_text(self):
            raise AssertionError("the tracer is off: no text is parsed")

    tr = _tiny_run(tmp_path, "off")
    assert not tr._trace.enabled and tr._trace.trace_dir is None
    # (a pool thread of an earlier traced test may still close an `aot_lower`
    # span into this buffer: look for this run's own call sites)
    mine = {"device_wait", "input_wait", "gc", "jax_trace", "jax_lower", "backend_compile",
            "cache_read", "trainer_init", "setup_model", "epoch", "train"}
    assert not mine & {e[0] for e in tr._trace.events()}
    assert not tr._aot.has(("fused_eval_step", 0, 128, 28, 28, 1))
    assert trace_mod._gc_hook not in gc.callbacks
    scopes.record_program(("k",), NeverRead())
    assert not (tmp_path / "traces").exists()
