"""``graftlint`` console entry point.

Usage::

    graftlint dynamic_load_balance_distributeddnn_tpu
    graftlint --flow dynamic_load_balance_distributeddnn_tpu
    graftlint --select G001,G003 train/engine.py
    graftlint --ignore G008 --format json pkg/ | jq .findings
    graftlint --flow --format sarif pkg/ > lint.sarif
    graftlint --flow --write-baseline .graftlint-baseline.json pkg/
    graftlint --flow --baseline .graftlint-baseline.json pkg/
    graftlint --list-rules

``--flow`` adds the whole-program rules (G011 donation lifetimes, G012
thread/lock discipline, G013 stale-mesh placement, and the graftmesh
families: G014 collective/axis consistency, G015 sharding-spec flow, G016
non-uniform shard arithmetic; and the graftrdzv families: G017
protocol-file discipline, G018 recovery phase order, G019 quiesce
discipline) on top of the single-file ones; selecting a
flow code implies it. ``--format json|sarif`` emits machine-readable
findings (SARIF for per-line CI annotation — ``scripts/lint_sarif.sh`` is
the wired CI invocation). Findings are cached by file content hash and the
per-file work runs on a process pool (``--jobs``).

Exit status: 0 when clean, 1 when findings, 2 on usage/parse errors.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Sequence

from dynamic_load_balance_distributeddnn_tpu.analysis.linter import (
    Finding,
    lint_paths,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.rules import RULES


def _flow_rules():
    from dynamic_load_balance_distributeddnn_tpu.analysis.flow.rules import (
        FLOW_RULES,
    )

    return FLOW_RULES


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="graftlint",
        description=(
            "TPU/JAX correctness linter for this repo: jit-in-hot-scope "
            "(G001), unsynced walls (G002), off-ladder batch shapes (G003), "
            "tracer coercion (G004), use-after-donation (G005), per-step "
            "puts (G006), execute-to-compile warms (G007), unattributable "
            "recorded walls (G008), registry bypass (G009), unguarded "
            "recovery blocking (G010); with --flow also the whole-program "
            "rules: donation lifetimes (G011), thread/lock discipline "
            "(G012), stale-mesh placement (G013), collective/axis "
            "consistency (G014), sharding-spec flow (G015), non-uniform "
            "shard arithmetic (G016), rendezvous protocol-file discipline "
            "(G017), recovery phase order (G018), quiesce-before-reshard "
            "(G019)."
        ),
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help="files and/or package directories to lint (recursive)",
    )
    parser.add_argument(
        "--select",
        default=None,
        metavar="CODES",
        help="comma-separated rule codes to run (default: all)",
    )
    parser.add_argument(
        "--ignore",
        default=None,
        metavar="CODES",
        help="comma-separated rule codes to skip",
    )
    parser.add_argument(
        "--flow",
        action="store_true",
        help="run the whole-program dataflow rules (G011-G019) too",
    )
    parser.add_argument(
        "--format",
        default="text",
        choices=("text", "json", "sarif"),
        help="output format (json/sarif for CI annotation)",
    )
    parser.add_argument(
        "--baseline",
        default=None,
        metavar="FILE",
        help="suppress findings recorded in this baseline file",
    )
    parser.add_argument(
        "--write-baseline",
        default=None,
        metavar="FILE",
        help="write current findings to FILE as the new baseline and exit 0",
    )
    parser.add_argument(
        "--jobs",
        type=int,
        default=0,
        metavar="N",
        help="process-pool width for per-file work (0 = auto, 1 = serial)",
    )
    parser.add_argument(
        "--cache-dir",
        default=None,
        metavar="DIR",
        help="content-hash cache directory (default: a per-user tmp dir; "
        "$GRAFTLINT_CACHE_DIR overrides)",
    )
    parser.add_argument(
        "--no-cache",
        action="store_true",
        help="disable the findings/summary cache",
    )
    parser.add_argument(
        "--list-rules",
        action="store_true",
        help="print the rule catalogue and exit",
    )
    parser.add_argument(
        "-q",
        "--quiet",
        action="store_true",
        help="suppress the per-finding fix hints",
    )
    return parser


def _all_rule_codes() -> dict:
    catalogue = dict(RULES)
    catalogue.update(_flow_rules())
    return catalogue


def _parse_codes(raw: Optional[str]) -> Optional[List[str]]:
    if not raw:
        return None
    return [c.strip() for c in raw.split(",") if c.strip()]


def _to_json(findings: Sequence[Finding]) -> str:
    return json.dumps(
        {
            "findings": [
                {
                    "code": f.code,
                    "path": f.path,
                    "line": f.line,
                    "col": f.col,
                    "message": f.message,
                    "fix_hint": f.fix_hint,
                    "symbol": f.symbol,
                }
                for f in findings
            ],
            "count": len(findings),
        },
        indent=2,
    )


def _to_sarif(findings: Sequence[Finding]) -> str:
    catalogue = _all_rule_codes()
    used = sorted({f.code for f in findings})
    sarif = {
        "$schema": (
            "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/master/"
            "Schemata/sarif-schema-2.1.0.json"
        ),
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "graftlint",
                        "informationUri": "README.md#static-analysis",
                        "rules": [
                            {
                                "id": code,
                                "shortDescription": {
                                    "text": getattr(
                                        catalogue.get(code), "summary", code
                                    )
                                },
                            }
                            for code in used
                        ],
                    }
                },
                "results": [
                    {
                        "ruleId": f.code,
                        "level": "error",
                        "message": {"text": f"{f.message} — fix: {f.fix_hint}"},
                        "locations": [
                            {
                                "physicalLocation": {
                                    "artifactLocation": {"uri": f.path},
                                    "region": {
                                        "startLine": max(f.line, 1),
                                        "startColumn": f.col + 1,
                                    },
                                }
                            }
                        ],
                    }
                    for f in findings
                ],
            }
        ],
    }
    return json.dumps(sarif, indent=2)


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    catalogue = _all_rule_codes()
    if args.list_rules:
        for code, rule in sorted(catalogue.items()):
            flow_tag = " [flow]" if code in _flow_rules() else ""
            print(f"{code}{flow_tag}  {rule.summary}")
        return 0
    if not args.paths:
        print("graftlint: no paths given (try --help)", file=sys.stderr)
        return 2

    select = _parse_codes(args.select)
    ignore = set(_parse_codes(args.ignore) or ())
    unknown = sorted((set(select or ()) | ignore) - set(catalogue))
    if unknown:
        print(f"graftlint: unknown rule codes {unknown}", file=sys.stderr)
        return 2

    flow_codes = set(_flow_rules())
    wanted = set(select) if select is not None else set(catalogue)
    wanted -= ignore
    sf_select: Optional[Sequence[str]] = sorted(wanted & set(RULES))
    flow_select: Optional[Sequence[str]] = sorted(wanted & flow_codes)
    # selecting a flow code implies flow mode; plain runs stay single-file
    flow = args.flow or (select is not None and bool(flow_select))
    if select is None and not ignore:
        sf_select = None  # "all" cache key — the common gate invocation
    if not flow:
        flow_select = None

    cache_dir: Optional[str]
    if args.no_cache:
        cache_dir = None
    elif args.cache_dir:
        cache_dir = args.cache_dir
    else:
        from dynamic_load_balance_distributeddnn_tpu.analysis.flow.project import (
            default_cache_dir,
        )

        cache_dir = default_cache_dir()

    try:
        findings = lint_paths(
            args.paths,
            select=sf_select,
            jobs=args.jobs,
            cache_dir=cache_dir,
            flow=flow,
            flow_select=flow_select,
        )
    except (OSError, SyntaxError) as exc:
        print(f"graftlint: {exc}", file=sys.stderr)
        return 2

    from dynamic_load_balance_distributeddnn_tpu.analysis.flow.baseline import (
        filter_baselined,
        load_baseline,
        write_baseline,
    )

    if args.write_baseline:
        write_baseline(args.write_baseline, findings)
        print(
            f"graftlint: wrote {len(findings)} finding"
            f"{'s' if len(findings) != 1 else ''} to {args.write_baseline}"
        )
        return 0
    if args.baseline:
        try:
            findings = filter_baselined(findings, load_baseline(args.baseline))
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            print(f"graftlint: bad baseline: {exc}", file=sys.stderr)
            return 2

    if args.format == "json":
        print(_to_json(findings))
    elif args.format == "sarif":
        print(_to_sarif(findings))
    else:
        for f in findings:
            if args.quiet:
                print(f"{f.path}:{f.line}:{f.col}: {f.code} {f.message}")
            else:
                print(f.format())
        n = len(findings)
        print(f"graftlint: {n} finding{'s' if n != 1 else ''}")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
