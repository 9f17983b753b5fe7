#!/usr/bin/env python3
"""Static gate for the repo — the compatible thin driver (ISSUE 14).

Round 2 shipped a NameError on the TPU-only hot path; the per-file
undefined-name checker this file started as grew 10 more per-file rules
and, in ISSUE 14, a whole-program analyzer. The implementation now
lives in ``tools/analysis/``:

* per-file rules (undefined names, unbounded waits, exception hygiene,
  metric namespaces, staged purity, …): ``tools/analysis/perfile.py``,
  behind a ``RULES`` registry with ``--only``/``--skip`` selection;
* whole-program passes (knob→cache-key completeness, the
  invalidation-cascade proof, lock discipline): ``tools/analysis/
  {knobs,caches,locks}.py`` — run here on the DEFAULT sweep (no
  explicit paths), and standalone via ``python -m tools.analysis``.

This entry point keeps the legacy surface byte-for-byte: same finding
format (``path:line: message``), same exit codes (0 clean / 1
findings), same default path set — CI and tests/test_lint.py don't
churn. One ``ast.parse`` per file per run (shared parse cache), and a
syntax error in one file reports that file and keeps checking the rest.

Usage: python tools/lint.py [paths...] [--only RULE] [--skip RULE]
       (default paths: the package + entry files, plus the
       whole-program passes)
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from tools import analysis  # noqa: E402
from tools.analysis import perfile  # noqa: E402

# Back-compat re-exports: everything test_lint.py-era callers imported
# from tools.lint keeps resolving (the implementations moved to
# tools/analysis/perfile.py).
BUILTINS = perfile.BUILTINS
Checker = perfile.Checker
check_unbounded_waits = perfile.check_unbounded_waits
check_transport_bounded_io = perfile.check_transport_bounded_io
check_exception_hygiene = perfile.check_exception_hygiene
check_library_hygiene = perfile.check_library_hygiene
check_worker_timeline_coverage = perfile.check_worker_timeline_coverage
check_reducer_reduce_routing = perfile.check_reducer_reduce_routing
check_epilogue_f32_intermediates = perfile.check_epilogue_f32_intermediates
check_staged_purity = perfile.check_staged_purity
check_schedule_stage_blocking = perfile.check_schedule_stage_blocking
check_wire_edge_routing = perfile.check_wire_edge_routing
check_planner_registry_ownership = perfile.check_planner_registry_ownership
check_async_sender_blocking = perfile.check_async_sender_blocking
check_serve_scheduler_blocking = perfile.check_serve_scheduler_blocking
RULES = perfile.RULES


def check_file(path: Path) -> list:
    """Legacy single-file surface (all per-file rules)."""
    return perfile.check_file(Path(path))


DEFAULT_PATHS = ["torch_cgx_tpu", "examples", "tests", "tools",
                 "__graft_entry__.py"]


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(
        prog="tools/lint.py", add_help=True,
        description="per-file lint + (on the default sweep) the "
                    "whole-program analyzer",
    )
    all_rules = list(perfile.RULES) + list(analysis.WHOLE_PROGRAM_PASSES)
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument(
        "--only", action="append", default=None, metavar="RULE",
        help=f"run only these rules/passes (of: {', '.join(all_rules)})",
    )
    ap.add_argument(
        "--skip", action="append", default=None, metavar="RULE",
        help="skip these rules/passes",
    )
    args = ap.parse_args(argv)
    unknown = [
        r for r in (args.only or []) + (args.skip or [])
        if r not in all_rules
    ]
    if unknown:
        print(
            f"unknown rule(s) {unknown}; known: {', '.join(all_rules)}",
            file=sys.stderr,
        )
        return 2
    # Selection applies to BOTH tiers: `--only undefined-name` must not
    # leak whole-program findings into a scoped bisect, and a
    # whole-program pass name selects that pass alone.
    pf_only = [r for r in (args.only or []) if r in perfile.RULES]
    pf_skip = [r for r in (args.skip or []) if r in perfile.RULES]
    wp_only = [
        r for r in (args.only or []) if r in analysis.WHOLE_PROGRAM_PASSES
    ]
    if args.paths and wp_only:
        # Whole-program passes need the whole package; silently printing
        # "files clean" without running the requested pass would be a
        # false green. Fail loudly instead.
        print(
            f"whole-program pass(es) {wp_only} only run on the default "
            "sweep — drop the explicit paths, or use "
            "`python -m tools.analysis --only ...`",
            file=sys.stderr,
        )
        return 2
    if args.only and not pf_only:
        from collections import OrderedDict

        rules = OrderedDict()  # --only named no per-file rule: run none
    else:
        rules = perfile.select_rules(pf_only or None, pf_skip or None)
    wp_passes = list(analysis.WHOLE_PROGRAM_PASSES)
    if args.only:
        wp_passes = [p for p in wp_passes if p in args.only]
    if args.skip:
        wp_passes = [p for p in wp_passes if p not in args.skip]

    default_sweep = not args.paths
    raw = args.paths or DEFAULT_PATHS
    files: list = []
    for p in raw:
        pp = (_ROOT / p) if not Path(p).is_absolute() else Path(p)
        if pp.is_dir():
            files.extend(sorted(pp.rglob("*.py")))
        elif pp.exists():
            files.append(pp)
    findings: list = []
    for f in files:
        if "__pycache__" in f.parts:
            continue
        findings.extend(perfile.check_file(f, rules))
    if default_sweep and wp_passes:
        # The whole-program passes ride the default sweep only: explicit
        # path arguments are the single-file surface (fixture files in
        # tests, editor integrations) where cross-module invariants
        # don't apply.
        pkg = _ROOT / "torch_cgx_tpu"
        if pkg.is_dir():
            # rule=="syntax" rows are the analyzer's broken-file notes;
            # the per-file sweep above already reported those files in
            # the legacy format — don't double-count them.
            findings.extend(
                fx.render() for fx in analysis.run_project(
                    pkg, passes=wp_passes
                )
                if fx.rule != "syntax"
            )
    for line in findings:
        print(line)
    if findings:
        print(f"lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print(f"lint: {len(files)} files clean")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
