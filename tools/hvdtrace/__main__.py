"""CLI: ``python -m tools.hvdtrace <trace-file-or-dir>... [--json]
[--no-fsm]``.

Exit status: 0 = all comparable worlds diff clean and every trace
passes the protocol FSM, 1 = divergences or FSM violations found,
2 = usage error / no loadable traces. ``--json`` replaces the text
report with one JSON document (findings + per-group summary) for
structured consumers.

Typical flows::

    # a conformance-enabled run dumped per-rank traces at shutdown
    HVD_CONFORMANCE=1 HVD_CONFORMANCE_DIR=/tmp/traces python train.py
    python -m tools.hvdtrace /tmp/traces

    # a hung world: SIGTERM the job (the abort path dumps), then
    python -m tools.hvdtrace /tmp/traces --json
"""

from __future__ import annotations

import argparse
import json
import sys

from . import format_finding, run_check


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="hvdtrace",
        description="cross-rank lockstep conformance trace differ + "
                    "protocol FSM validator (docs/conformance.md)")
    parser.add_argument("paths", nargs="*",
                        help="trace files and/or directories holding "
                             "hvdtrace-*.json dumps")
    parser.add_argument("--dir", dest="dirs", action="append",
                        metavar="DIR",
                        help="directory of trace dumps (repeatable; "
                             "same as a positional directory)")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON report instead of text")
    parser.add_argument("--no-fsm", action="store_true",
                        help="skip the per-rank protocol FSM validation "
                             "(cross-rank diff only)")
    args = parser.parse_args(argv)

    paths = list(args.paths) + list(args.dirs or [])
    if not paths:
        parser.print_usage(sys.stderr)
        print("hvdtrace: no trace files or directories given",
              file=sys.stderr)
        return 2
    findings, errors, summary = run_check(paths, fsm=not args.no_fsm)
    if summary["traces"] == 0:
        for e in errors:
            print(f"hvdtrace: {e}", file=sys.stderr)
        print("hvdtrace: no loadable conformance traces "
              f"under {', '.join(paths)}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({
            "tool": "hvdtrace",
            "clean": not findings,
            "summary": summary,
            "findings": findings,
            "errors": errors,
        }, indent=2))
        return 1 if findings else 0

    for e in errors:
        print(f"hvdtrace: warning: {e}", file=sys.stderr)
    for f in findings:
        print(format_finding(f))
    groups = summary["groups"]
    if findings:
        print(f"hvdtrace: {summary['divergences']} divergence(s), "
              f"{summary['fsm_violations']} FSM violation(s) across "
              f"{summary['traces']} trace(s) in {len(groups)} world(s)",
              file=sys.stderr)
        return 1
    print(f"hvdtrace: clean ({summary['traces']} traces, "
          f"{len(groups)} comparable world(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
