"""Knob→cache-key completeness pass (rule id: ``knob-key``).

The reference's correctness hinges on configuration reaching every
cached artifact (per-layer configs bump a registry version that re-keys
every trace, ProcessGroupCGX.cc:837-857). This port re-discovered that
invariant the hard way four times — PR 6's stale qerr cadence, PR 7's
program cache missing the mesh-grid key, PR 10's controller cadence,
PR 13's stale slice-leader memo — each found by a failing chaos run.
This pass makes the bug class unshippable:

1. every ``CGX_*`` read is extracted per function (the ``utils/env.py``
   helpers, raw ``os.environ``/``os.getenv``), and propagated through
   the whole-package reference graph — so a knob read five calls below
   ``_group_leaves`` still taints the layout builder;
2. each declared **cache surface** (the five staged-program caches) is
   split at its cache-probe line into a *key side* (everything that
   feeds the ``cache_key`` expression) and a *build side* (everything
   that runs on a miss and is therefore baked into the cached value);
3. a knob tainting the build side but absent from the key side's taint
   is a finding — unless the machine-checked :data:`INERT_KNOBS`
   allowlist carries it with a justification, or an inline
   ``# cgx-analysis: allow(knob-key) — reason`` pragma covers the
   surface.

The allowlist is itself checked: an entry whose knob no longer taints
any surface's build side is *stale* (rule id ``stale-allowlist``) — dead
suppressions rot into false confidence, so they fail the build too.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .graph import FuncKey, ModuleInfo, Project, _walk_function_body
from .report import Finding

_ENV_HELPERS = {
    "get_int_env_or_default",
    "get_float_env_or_default",
    "get_bool_env_or_default",
    "get_str_env_or_default",
    "get_optional_str_env",
}

KNOB_PREFIX = "CGX_"


# ---------------------------------------------------------------------------
# The machine-checked inert-knob allowlist. Every entry must carry a
# justification; every entry must still be LIVE (tainting at least one
# surface's build side) or the stale-allowlist rule fires. Keep this
# list short — the right fix for a staged-lowering knob is a key
# component, not a row here.
# ---------------------------------------------------------------------------

INERT_KNOBS: Dict[str, str] = {
    # The fault injector perturbs the HOST transport around a program
    # (the heartbeat/robustness plumbing reachable from the builders),
    # keyed by its own env spec at injector-construction time — a seed
    # flip re-seeds injection, never what a cached program computes.
    "CGX_FAULTS_SEED": "host-side fault injection seed; wraps, never lowers",
}


# ---------------------------------------------------------------------------
# Cache surfaces.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheSurface:
    """One staged-program cache: ``fn`` is the function that probes
    ``cache`` (reads it, and on a miss builds + stores the value) — or an
    orchestrator that calls reader/writer helpers (``reader`` names the
    helper whose call line splits key side from build side)."""

    id: str
    module: str  # dotted module name (project-relative)
    cache: str  # the cache variable probed (module global or closure var)
    fn: str  # bare name of the probing function
    reader: Optional[str] = None  # accessor fn when the probe is indirect


def default_surfaces(pkg: str) -> Tuple[CacheSurface, ...]:
    """The six staged-program caches of torch_cgx_tpu (ISSUE 14; the
    serving decode-program LRU joined with ISSUE 15)."""
    return (
        CacheSurface("layout-lru", f"{pkg}.parallel.allreduce",
                     "_LAYOUT_CACHE", "_tree_layout"),
        CacheSurface("schedule-lru", f"{pkg}.parallel.schedule",
                     "_SCHED_CACHE", "compiled_schedule"),
        CacheSurface("plan-lru", f"{pkg}.parallel.planner",
                     "_PLAN_CACHE", "plan_for_layout"),
        CacheSurface("xla-program-lru", f"{pkg}.parallel.xla_allreduce",
                     "_PROGRAM_CACHE", "staged_allreduce",
                     reader="_cache_get"),
        CacheSurface("train-step-build", f"{pkg}.parallel.grad_sync",
                     "built", "_build"),
        CacheSurface("serve-program-lru", f"{pkg}.serving.scheduler",
                     "_PROGRAM_CACHE", "_decode_program"),
    )


# ---------------------------------------------------------------------------
# Direct knob reads.
# ---------------------------------------------------------------------------


def _knob_of_arg(proj: Project, mod: ModuleInfo, arg: ast.AST) -> Optional[str]:
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.Name):
        if arg.id in mod.constants:
            return mod.constants[arg.id]
        sym = mod.symbol_imports.get(arg.id)
        if sym and sym[0] in proj.modules:
            return proj.modules[sym[0]].constants.get(sym[1])
        return None
    if isinstance(arg, ast.Attribute) and isinstance(arg.value, ast.Name):
        tmod = proj.resolve_module_alias(mod, arg.value.id)
        if tmod:
            return proj.modules[tmod].constants.get(arg.attr)
    return None


def _is_environ(mod: ModuleInfo, expr: ast.AST) -> bool:
    # os.environ (alias-aware)
    return (
        isinstance(expr, ast.Attribute)
        and expr.attr == "environ"
        and isinstance(expr.value, ast.Name)
        and (expr.value.id == "os" or mod.import_aliases.get(expr.value.id) == "os")
    ) or (isinstance(expr, ast.Name) and mod.symbol_imports.get(expr.id) == ("os", "environ"))


def direct_knob_reads(proj: Project) -> Dict[FuncKey, Set[str]]:
    """(module, func) -> set of CGX_* names it reads directly.
    Memoized on the project (several passes and every surface consult
    it)."""
    cached = getattr(proj, "_knob_direct_cache", None)
    if cached is not None:
        return cached
    out: Dict[FuncKey, Set[str]] = {}
    for mname, mod in proj.modules.items():
        for qual, fi in mod.funcs.items():
            knobs: Set[str] = set()
            for node in _walk_function_body(fi.node):
                knob: Optional[str] = None
                if isinstance(node, ast.Call):
                    fn = node.func
                    callee = (
                        fn.attr if isinstance(fn, ast.Attribute)
                        else fn.id if isinstance(fn, ast.Name) else ""
                    )
                    if callee in _ENV_HELPERS and node.args:
                        knob = _knob_of_arg(proj, mod, node.args[0])
                    elif callee == "getenv" and node.args:
                        knob = _knob_of_arg(proj, mod, node.args[0])
                    elif (
                        callee == "get"
                        and isinstance(fn, ast.Attribute)
                        and _is_environ(mod, fn.value)
                        and node.args
                    ):
                        knob = _knob_of_arg(proj, mod, node.args[0])
                elif isinstance(node, ast.Subscript) and _is_environ(
                    mod, node.value
                ):
                    knob = _knob_of_arg(proj, mod, node.slice)
                if knob and knob.startswith(KNOB_PREFIX):
                    knobs.add(knob)
            if knobs:
                out[(mname, qual)] = knobs
    proj._knob_direct_cache = out
    return out


def knob_closure(proj: Project) -> Dict[FuncKey, Set[str]]:
    """Transitive knob taint: fixpoint of direct reads over the
    reference graph (cycles converge because union is monotone)."""
    direct = direct_knob_reads(proj)
    refs = proj.refs()
    closure: Dict[FuncKey, Set[str]] = {
        k: set(direct.get(k, ())) for k in refs
    }
    changed = True
    while changed:
        changed = False
        for k, targets in refs.items():
            cur = closure[k]
            before = len(cur)
            for t in targets:
                cur |= closure.get(t, set())
            if len(cur) != before:
                changed = True
    return closure


# ---------------------------------------------------------------------------
# The surface split + check.
# ---------------------------------------------------------------------------


def _line_refs(
    proj: Project, mod: ModuleInfo, fi
) -> List[Tuple[int, FuncKey]]:
    """(line, referenced function) pairs inside one function body."""
    sysmods = proj._sys_modules_vars(mod, fi.node)
    out: List[Tuple[int, FuncKey]] = []
    for node in _walk_function_body(fi.node):
        if isinstance(node, (ast.Call,)):
            ref = proj._resolve_ref(mod, fi, node.func, sysmods)
            if ref:
                out.append((node.lineno, ref))
        elif isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
            node.ctx, ast.Load
        ):
            ref = proj._resolve_ref(mod, fi, node, sysmods)
            if ref:
                out.append((node.lineno, ref))
    # Nested defs execute when referenced; attribute their bodies to the
    # def line so a nested `body()` built after the probe counts as
    # build-side.
    for qual, sub in mod.funcs.items():
        if (
            qual.startswith(fi.qual + ".")
            and "." not in qual[len(fi.qual) + 1:]
        ):
            out.append((sub.lineno, (mod.name, qual)))
    return out


def _probe_line(
    proj: Project, mod: ModuleInfo, fi, surface: CacheSurface
) -> Optional[int]:
    """The line where the cache is first consulted inside ``fi``."""
    candidates: List[int] = []
    for node in _walk_function_body(fi.node):
        if isinstance(node, ast.Call):
            fn = node.func
            # <cache>.get(key)
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr == "get"
                and isinstance(fn.value, ast.Name)
                and fn.value.id == surface.cache
            ):
                candidates.append(node.lineno)
            # reader accessor (indirect probe)
            elif surface.reader is not None:
                callee = (
                    fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else ""
                )
                if callee == surface.reader:
                    candidates.append(node.lineno)
        elif (
            isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name)
            and node.value.id == surface.cache
            and isinstance(node.ctx, ast.Load)
        ):
            candidates.append(node.lineno)
        elif (
            isinstance(node, ast.Compare)
            and any(
                isinstance(c, ast.Name) and c.id == surface.cache
                for c in node.comparators
            )
        ):
            candidates.append(node.lineno)
    return min(candidates) if candidates else None


def _direct_knobs_in_range(
    proj: Project, mod: ModuleInfo, fi, lo: int, hi: int
) -> Set[str]:
    """Knobs read directly inside ``fi`` between lines (lo, hi]."""
    direct = direct_knob_reads(proj).get((mod.name, fi.qual), set())
    if not direct:
        return set()
    # Re-scan with line filtering (direct_knob_reads is line-blind).
    knobs: Set[str] = set()
    for node in _walk_function_body(fi.node):
        if not (lo < getattr(node, "lineno", 0) <= hi):
            continue
        if isinstance(node, ast.Call):
            fn = node.func
            callee = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            if callee in _ENV_HELPERS or callee == "getenv" or (
                callee == "get"
                and isinstance(fn, ast.Attribute)
                and _is_environ(mod, fn.value)
            ):
                if node.args:
                    k = _knob_of_arg(proj, mod, node.args[0])
                    if k and k.startswith(KNOB_PREFIX):
                        knobs.add(k)
        elif isinstance(node, ast.Subscript) and _is_environ(mod, node.value):
            k = _knob_of_arg(proj, mod, node.slice)
            if k and k.startswith(KNOB_PREFIX):
                knobs.add(k)
    return knobs


def surface_taint(
    proj: Project, surface: CacheSurface,
    closure: Optional[Dict[FuncKey, Set[str]]] = None,
) -> Optional[Tuple[Set[str], Set[str], int]]:
    """(key-side knobs, build-side knobs, probe line) for a surface, or
    None when the surface cannot be located (module/function/cache
    missing — reported by the caller as a finding so a renamed cache
    can't silently disarm the rule)."""
    mod = proj.modules.get(surface.module)
    if mod is None:
        return None
    qual = mod.func_by_name.get(surface.fn)
    if qual is None:
        return None
    fi = mod.funcs[qual]
    split = _probe_line(proj, mod, fi, surface)
    if split is None:
        return None
    if closure is None:
        closure = knob_closure(proj)
    end = max(
        getattr(n, "lineno", fi.lineno) for n in ast.walk(fi.node)
    )
    key_side: Set[str] = set()
    build_side: Set[str] = set()
    for line, ref in _line_refs(proj, mod, fi):
        knobs = closure.get(ref, set())
        if line <= split:
            key_side |= knobs
        else:
            build_side |= knobs
    key_side |= _direct_knobs_in_range(proj, mod, fi, 0, split)
    build_side |= _direct_knobs_in_range(proj, mod, fi, split, end + 1)
    return key_side, build_side, split


def check(
    proj: Project,
    surfaces: Optional[Sequence[CacheSurface]] = None,
    allowlist: Optional[Dict[str, str]] = None,
    allowlist_origin: str = __name__,
) -> List[Finding]:
    """Run the knob→cache-key pass. Returns findings for (a) build-side
    knobs missing from the key, (b) unlocatable surfaces, (c) stale or
    unjustified allowlist entries."""
    if surfaces is None:
        surfaces = default_surfaces(proj.pkg_name)
    if allowlist is None:
        allowlist = INERT_KNOBS
    closure = knob_closure(proj)
    findings: List[Finding] = []
    live_allowlisted: Set[str] = set()
    build_side_all: Set[str] = set()
    all_located = True
    for surface in surfaces:
        taint = surface_taint(proj, surface, closure)
        if taint is None:
            all_located = False
            findings.append(Finding(
                path=str(proj.module_path(surface.module)
                         or surface.module),
                line=1,
                rule="knob-key",
                message=(
                    f"[knob-key] cache surface {surface.id!r} cannot be "
                    f"located ({surface.module}.{surface.fn} probing "
                    f"{surface.cache!r}) — a renamed cache must update "
                    "tools/analysis/knobs.py default_surfaces, not "
                    "silently disarm the completeness rule"
                ),
            ))
            continue
        key_side, build_side, split = taint
        build_side_all |= build_side
        missing = build_side - key_side
        live_allowlisted |= missing & set(allowlist)
        missing -= set(allowlist)
        path = proj.module_path(surface.module)
        for knob in sorted(missing):
            if proj.suppressed(path, split, "knob-key"):
                continue
            findings.append(Finding(
                path=str(path),
                line=split,
                rule="knob-key",
                message=(
                    f"[knob-key] {knob} taints what cache surface "
                    f"{surface.id!r} builds (miss path below "
                    f"{surface.fn}:{split}) but no component of its "
                    "cache key reads it — a flip between calls would "
                    "serve a stale staged artifact; add it to the key "
                    "expression or, if provably inert, to "
                    "tools/analysis/knobs.py INERT_KNOBS with a "
                    "justification"
                ),
            ))
    for knob, reason in sorted(allowlist.items()):
        if not str(reason).strip():
            findings.append(Finding(
                path=allowlist_origin, line=1, rule="stale-allowlist",
                message=(
                    f"[stale-allowlist] allowlist entry {knob} has no "
                    "justification — every inert-knob row must say why"
                ),
            ))
        elif all_located and knob not in live_allowlisted:
            # Staleness is only provable when every surface was
            # analyzed: an unlocatable surface may be the one this row
            # suppresses, and telling the developer to delete a valid
            # row beside a "cannot be located" finding compounds the
            # breakage (caught by review). Diagnose precisely: a knob
            # that still taints a build side but is now keyed got
            # PROMOTED into the key — the row suppresses nothing.
            if knob in build_side_all:
                why = (
                    "is now covered by every surface's cache key — the "
                    "row suppresses nothing; delete it"
                )
            else:
                why = (
                    "no longer taints any cache surface's build side — "
                    "delete the row (dead suppressions rot into false "
                    "confidence)"
                )
            findings.append(Finding(
                path=allowlist_origin, line=1, rule="stale-allowlist",
                message=f"[stale-allowlist] allowlist entry {knob} {why}",
            ))
    return findings
