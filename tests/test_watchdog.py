"""Stall-watchdog unit tests (runtime/watchdog.py).

The watchdog turns a dead-runtime PJRT hang (0% CPU, uninterruptible in C++)
into a bounded subprocess failure. These tests pin its contract: heartbeat is
a no-op unless configured, arming creates missing parents, a fresh heartbeat
holds the process alive, and a stale one hard-exits with the chosen code —
including when the heartbeat file could not be created at all (fail-closed).
"""

import os
import subprocess
import sys

from dynamic_load_balance_distributeddnn_tpu.runtime import watchdog


def test_heartbeat_noop_without_env(tmp_path, monkeypatch):
    monkeypatch.delenv("DBS_HEARTBEAT_FILE", raising=False)
    watchdog.heartbeat()  # must not raise or create anything


def test_heartbeat_touches_configured_file(tmp_path, monkeypatch):
    hb = tmp_path / "hb"
    monkeypatch.setenv("DBS_HEARTBEAT_FILE", str(hb))
    watchdog.heartbeat()
    assert hb.exists()


def test_arm_creates_missing_parent(tmp_path, monkeypatch):
    monkeypatch.delenv("DBS_HEARTBEAT_FILE", raising=False)
    hb = tmp_path / "not" / "yet" / "there" / "hb"
    t = watchdog.arm_stall_watchdog(str(hb), stall_s=10_000, poll_s=10_000)
    assert t.daemon
    assert hb.exists()
    assert os.environ["DBS_HEARTBEAT_FILE"] == str(hb)


_CHILD = r"""
import sys, time
from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
    arm_stall_watchdog, heartbeat,
)
mode = sys.argv[1]
hb = sys.argv[2]
if mode == "grace":
    # tight stall but a long first-heartbeat grace: the silent cold-compile
    # window must survive, and the tight threshold must apply after the
    # first heartbeat lands
    arm_stall_watchdog(hb, stall_s=0.6, poll_s=0.1, exit_code=19,
                       first_grace_s=6.0)
    time.sleep(2.0)   # > stall_s, inside grace -> must survive
    heartbeat()       # device answered once: grace over
    time.sleep(30)    # > stall_s with no heartbeat -> must fire now
    sys.exit(0)
arm_stall_watchdog(hb, stall_s=1.0, poll_s=0.2, exit_code=19,
                   first_grace_s=1.0)
if mode == "alive":
    for _ in range(10):
        time.sleep(0.3)
        heartbeat()
    sys.exit(0)
time.sleep(30)  # "hang": no heartbeats -> watchdog must fire
sys.exit(0)
"""


def _run_child(mode: str, hb: str, timeout: float = 20):
    return subprocess.run(
        [sys.executable, "-c", _CHILD, mode, hb],
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.getcwd()},
    )


def test_stale_heartbeat_hard_exits(tmp_path):
    proc = _run_child("hang", str(tmp_path / "hb"))
    assert proc.returncode == 19


def test_fresh_heartbeat_keeps_process_alive(tmp_path):
    proc = _run_child("alive", str(tmp_path / "hb"))
    assert proc.returncode == 0


def test_first_grace_survives_cold_compile_then_tightens(tmp_path):
    # silent pre-first-heartbeat window longer than stall_s survives (cold
    # XLA compile); after the first heartbeat the tight
    # stall applies and a stale heartbeat fires. Timing discriminates the
    # regressions: tight firing lands at ~2.0+0.6s; a grace threshold that
    # never tightens would fire at 2.0+6.0=8s, past the 5.5s bound.
    import time

    t0 = time.time()
    proc = _run_child("grace", str(tmp_path / "hb"))
    elapsed = time.time() - t0
    assert proc.returncode == 19
    assert elapsed < 5.5, f"fired at {elapsed:.1f}s: grace never tightened"
    assert elapsed > 1.9, f"fired at {elapsed:.1f}s: grace did not hold"


def test_fails_closed_when_hb_uncreatable(tmp_path):
    # a path that cannot exist (parent is a FILE) -> watchdog must still fire
    blocker = tmp_path / "f"
    blocker.write_text("x")
    proc = _run_child("hang", str(blocker / "hb"))
    assert proc.returncode == 19
