"""Tier-1 gate: the shipped tree must lint clean — single-file AND flow.

Any future PR that reintroduces a G00x violation in the package fails the
default fast pytest run right here — the CI half of the ISSUE-1 contract
(`graftlint dynamic_load_balance_distributeddnn_tpu` exits 0). Since ISSUE 8 the gate also runs the whole-program rules with NO
baseline file (`--flow`: G011 donation lifetimes, G012 thread/lock
discipline, G013 stale-mesh placement, since ISSUE 10 the graftmesh
families — G014 collective/axis consistency, G015 sharding-spec flow, G016
non-uniform shard arithmetic — and since ISSUE 16 the graftrdzv families —
G017 protocol-file discipline, G018 recovery phase order, G019 quiesce
before reshard): every pre-existing finding was either fixed or carries an
inline `# graftlint: disable=G01x` with a justification comment, so new
interprocedural regressions fail here too. Since ISSUE 16 the gate also
executes `scripts/lint_sarif.sh` itself — the exact CI invocation, SARIF
output and all — so the wired script can never drift from the green tree.
"""

import json
import pathlib
import subprocess

from dynamic_load_balance_distributeddnn_tpu.analysis.cli import main as cli_main

REPO = pathlib.Path(__file__).resolve().parents[1]
TARGETS = [str(REPO / "dynamic_load_balance_distributeddnn_tpu")]


def test_shipped_tree_lints_clean(capsys):
    rc = cli_main(TARGETS)
    out = capsys.readouterr().out
    assert rc == 0, f"graftlint found violations in the shipped tree:\n{out}"


def test_shipped_tree_flow_lints_clean(capsys):
    rc = cli_main(["--flow", "--no-cache", *TARGETS])
    out = capsys.readouterr().out
    assert rc == 0, (
        "graftlint --flow found unsanctioned whole-program violations in "
        f"the shipped tree:\n{out}"
    )


def test_lint_sarif_script_gates_clean(tmp_path):
    """The wired CI step itself (ISSUE 16 satellite): run the actual
    `scripts/lint_sarif.sh` — no baseline, full flow pass, SARIF out — and
    hold it to exit 0 with zero results on the shipped tree. A second run
    against the same content-hash cache must agree, and the cache must
    have materialized (the warm-run budget CI relies on is real)."""
    script = REPO / "scripts" / "lint_sarif.sh"
    out_path = tmp_path / "lint.sarif"
    cache = tmp_path / "cache"
    env = {"GRAFTLINT_CACHE_DIR": str(cache), "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: os.environ[k] for k in ("PATH", "HOME") if k in os.environ})
    for attempt in ("cold", "warm"):
        proc = subprocess.run(
            ["bash", str(script), str(out_path)],
            cwd=str(REPO),
            env=env,
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert proc.returncode == 0, (
            f"{attempt} lint_sarif.sh exited {proc.returncode}:\n"
            f"{proc.stderr}"
        )
        sarif = json.loads(out_path.read_text())
        assert sarif["version"] == "2.1.0"
        results = [
            r for run in sarif.get("runs", []) for r in run.get("results", [])
        ]
        assert results == [], f"{attempt} run reported findings: {results}"
        assert "0 finding(s)" in proc.stderr
    # the content-hash cache actually materialized between the two runs
    assert any(cache.iterdir())
