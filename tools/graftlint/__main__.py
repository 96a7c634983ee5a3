"""CLI: ``python -m tools.graftlint bigdl_tpu``.

Exit code 0 when no error-severity findings survive suppressions,
1 otherwise, 2 on usage errors.  ``--json`` prints the machine schema
(tests/test_graftlint.py asserts it); ``--changed-only`` scopes the run
to git-changed files for fast local iteration.
"""

from __future__ import annotations

import argparse
import os
import sys

# allow running from a checkout without installing: the repo root is the
# parent of tools/
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.graftlint import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="JAX-hazard static analysis (see "
                    "tools/graftlint/README.md for the rule catalog)")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files or directories to lint "
                         "(default: bigdl_tpu)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (schema version "
                         f"{core.JSON_SCHEMA_VERSION}); alias of "
                         "--format json")
    ap.add_argument("--format", default=None,
                    choices=("human", "json", "sarif"),
                    help="output format: human (default), json "
                         "(graftlint schema) or sarif (SARIF 2.1.0 — "
                         "CI inline PR annotations)")
    ap.add_argument("--stats", action="store_true",
                    help="print per-rule finding/suppression counts "
                         "(the suppression-debt dashboard) and exit 0")
    ap.add_argument("--write-baseline", nargs="?", metavar="PATH",
                    const="", default=None,
                    help="with --stats: write the per-file suppression "
                         "baseline JSON (default "
                         "tools/graftlint/suppressions_baseline.json) "
                         "— the reviewed act that admits net-new "
                         "suppression debt past the tier-1 gate")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule ids/names to run; an id "
                         "prefix selects a family (--select GL2 runs "
                         "GL201-GL206) (default: all)")
    ap.add_argument("--changed-only", action="store_true",
                    help="lint only files changed vs --base "
                         "(plus untracked)")
    ap.add_argument("--base", default="HEAD",
                    help="git ref for --changed-only (default HEAD)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in core.all_rules():
            print(f"{r.id}  {r.name:24s} [{r.severity}] {r.description}")
        return 0

    fmt = args.format or ("json" if args.json else "human")
    if args.json and args.format and args.format != "json":
        print("graftlint: --json conflicts with "
              f"--format {args.format}", file=sys.stderr)
        return 2

    # default gate paths: the library AND the tools/ tree (tools/*.py
    # threaded code is part of the product)
    paths = args.paths or [p for p in ("bigdl_tpu", "tools")
                           if os.path.exists(p)] or ["bigdl_tpu"]
    for p in paths:
        if not os.path.exists(p):
            print(f"graftlint: path not found: {p}", file=sys.stderr)
            return 2
    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    if args.write_baseline is not None and not args.stats:
        print("graftlint: --write-baseline requires --stats (the "
              "baseline is the debt table, frozen)", file=sys.stderr)
        return 2
    if args.stats:
        # --stats is a whole-tree dashboard: scoping or reformatting
        # flags it cannot honor are usage errors, not silent no-ops
        if args.changed_only:
            print("graftlint: --stats does not support --changed-only "
                  "(the debt table is whole-tree)", file=sys.stderr)
            return 2
        if fmt == "sarif":
            print("graftlint: --stats has no SARIF form; use --json",
                  file=sys.stderr)
            return 2
        stats = core.lint_paths_stats(paths, select=select)
        import json
        if args.write_baseline is not None:
            if select:
                print("graftlint: --write-baseline must cover the "
                      "full ruleset (drop --select)", file=sys.stderr)
                return 2
            out = args.write_baseline or core.BASELINE_DEFAULT_PATH
            with open(out, "w", encoding="utf-8") as fh:
                json.dump(core.baseline_document(stats, paths), fh,
                          indent=2, sort_keys=True)
                fh.write("\n")
            # stderr: stdout carries the (possibly JSON) stats payload
            print(f"graftlint: baseline written to {out}",
                  file=sys.stderr)
        if fmt == "json":
            print(json.dumps(stats, indent=2, sort_keys=True))
        else:
            print(core.stats_to_human(stats))
        return 0
    result = core.lint_paths(paths, select=select,
                             changed_only=args.changed_only,
                             base=args.base)
    if fmt == "json":
        print(core.to_json(result))
    elif fmt == "sarif":
        print(core.to_sarif(result))
    else:
        print(core.to_human(result))
    return result.exit_code


if __name__ == "__main__":
    sys.exit(main())
