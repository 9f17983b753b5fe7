"""Per-file lint rules behind a ``RULES`` registry.

The 11 single-file rules that used to live inline in ``tools/lint.py``
(plus the undefined-name checker it started from), unchanged in
behavior: same messages, same scoping, same escape hatches — so the
``tests/test_lint.py`` surface doesn't churn. ``tools/lint.py`` remains
the compatible CLI entry point; ``python -m tools.analysis`` runs these
plus the whole-program passes.

What DID change (ISSUE 14 ride-along): one ``ast.parse`` per file per
run, shared across all rules through ``graph.get_source`` — the
staged-purity manifest and the timeline BRIDGE_OPS list used to be
re-parsed once per checked file — and a syntax error in one file
reports that file and keeps checking the rest.
"""

from __future__ import annotations

import ast
import builtins
import re as _re
from collections import OrderedDict
from pathlib import Path
from typing import Callable, Dict, List, Optional

from .graph import get_source

BUILTINS = set(dir(builtins)) | {"__file__", "__name__", "__doc__", "__package__",
                                 "__spec__", "__loader__", "__builtins__",
                                 "__debug__", "__path__", "__class__"}


def _bindings(node: ast.AST) -> set:
    """Names bound directly in this scope's body (no recursion into nested
    function/lambda scopes; comprehensions handled separately)."""
    bound: set = set()

    def targets(t: ast.AST) -> None:
        for n in ast.walk(t):
            if isinstance(n, ast.Name) and isinstance(
                n.ctx, (ast.Store, ast.Del)
            ):
                bound.add(n.id)

    class Scan(ast.NodeVisitor):
        def visit_FunctionDef(self, n: ast.FunctionDef) -> None:
            bound.add(n.name)  # don't recurse: nested scope

        def visit_AsyncFunctionDef(self, n: ast.AsyncFunctionDef) -> None:
            bound.add(n.name)

        def visit_ClassDef(self, n: ast.ClassDef) -> None:
            bound.add(n.name)  # don't recurse

        def visit_Lambda(self, n: ast.Lambda) -> None:
            pass  # nested scope

        def visit_Import(self, n: ast.Import) -> None:
            for a in n.names:
                bound.add((a.asname or a.name).split(".")[0])

        def visit_ImportFrom(self, n: ast.ImportFrom) -> None:
            for a in n.names:
                if a.name == "*":
                    bound.add("*")
                else:
                    bound.add(a.asname or a.name)

        def visit_Assign(self, n: ast.Assign) -> None:
            for t in n.targets:
                targets(t)
            self.generic_visit(n)

        def visit_AnnAssign(self, n: ast.AnnAssign) -> None:
            targets(n.target)
            if n.value is not None:
                self.visit(n.value)

        def visit_AugAssign(self, n: ast.AugAssign) -> None:
            targets(n.target)
            self.visit(n.value)

        def visit_NamedExpr(self, n: ast.NamedExpr) -> None:
            targets(n.target)
            self.visit(n.value)

        def visit_For(self, n: ast.For) -> None:
            targets(n.target)
            self.generic_visit(n)

        def visit_AsyncFor(self, n: ast.AsyncFor) -> None:
            targets(n.target)
            self.generic_visit(n)

        def visit_withitem(self, n: ast.withitem) -> None:
            if n.optional_vars is not None:
                targets(n.optional_vars)
            self.visit(n.context_expr)

        def visit_ExceptHandler(self, n: ast.ExceptHandler) -> None:
            if n.name:
                bound.add(n.name)
            self.generic_visit(n)

        def visit_Global(self, n: ast.Global) -> None:
            bound.update(n.names)

        def visit_Nonlocal(self, n: ast.Nonlocal) -> None:
            bound.update(n.names)

        def visit_comprehension(self, n: ast.comprehension) -> None:
            targets(n.target)
            self.visit(n.iter)
            for c in n.ifs:
                self.visit(c)

        def visit_MatchAs(self, n: ast.MatchAs) -> None:
            if n.name:
                bound.add(n.name)
            self.generic_visit(n)

        def visit_MatchStar(self, n: ast.MatchStar) -> None:
            if n.name:
                bound.add(n.name)

        def visit_MatchMapping(self, n: ast.MatchMapping) -> None:
            if n.rest:
                bound.add(n.rest)
            self.generic_visit(n)

    scan = Scan()
    body = node.body if isinstance(node.body, list) else [node.body]
    for stmt in body:
        scan.visit(stmt)
    return bound


def _params(fn) -> set:
    a = fn.args
    names = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


class Checker:
    def __init__(self, path: Path, tree: ast.Module):
        self.path = path
        self.findings: list = []
        module_scope = _bindings(tree)
        self.star_import = "*" in module_scope
        self._walk(tree, [module_scope])

    def _walk(self, node: ast.AST, scopes: list) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in child.decorator_list:
                    self._check_expr(dec, scopes)
                for d in child.args.defaults + [
                    d for d in child.args.kw_defaults if d is not None
                ]:
                    self._check_expr(d, scopes)
                inner = _params(child) | _bindings(child)
                self._walk_body(child.body, scopes + [inner])
            elif isinstance(child, ast.Lambda):
                inner = _params(child)
                for n in ast.walk(child.body):  # walrus targets
                    if isinstance(n, ast.NamedExpr) and isinstance(
                        n.target, ast.Name
                    ):
                        inner.add(n.target.id)
                self._walk(child.body, scopes + [inner])
                self._check_expr(child.body, scopes + [inner], walk=False)
            elif isinstance(child, ast.ClassDef):
                for dec in child.decorator_list:
                    self._check_expr(dec, scopes)
                for base in child.bases + [k.value for k in child.keywords]:
                    self._check_expr(base, scopes)
                # Class body names are visible inside the body statements.
                self._walk_body(child.body, scopes + [_bindings(child)])
            elif isinstance(
                child, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
            ):
                comp_names: set = set()
                for gen in child.generators:
                    for n in ast.walk(gen.target):
                        if isinstance(n, ast.Name):
                            comp_names.add(n.id)
                self._walk(child, scopes + [comp_names])
            elif isinstance(child, (ast.AnnAssign,)):
                # Skip annotation subtree (from __future__ import annotations
                # makes them unevaluated strings); check only the value.
                if child.value is not None:
                    self._check_expr(child.value, scopes)
                if isinstance(child.target, ast.Name):
                    pass
                else:
                    self._check_expr(child.target, scopes)
            elif isinstance(child, ast.arg):
                continue  # skip annotations on args
            elif isinstance(child, ast.Name):
                if isinstance(child.ctx, ast.Load):
                    self._check_name(child, scopes)
            else:
                self._walk(child, scopes)

    def _walk_body(self, body: list, scopes: list) -> None:
        wrapper = ast.Module(body=body, type_ignores=[])
        self._walk(wrapper, scopes)

    def _check_expr(
        self, expr: ast.AST, scopes: list, walk: bool = True
    ) -> None:
        if isinstance(expr, ast.Name) and isinstance(expr.ctx, ast.Load):
            self._check_name(expr, scopes)
        if walk:
            self._walk(expr, scopes)

    def _check_name(self, node: ast.Name, scopes: list) -> None:
        if self.star_import:
            return
        name = node.id
        if name in BUILTINS:
            return
        for scope in scopes:
            if name in scope:
                return
        self.findings.append((node.lineno, name))


def check_undefined_names(path: Path, tree: ast.Module) -> List[str]:
    c = Checker(path, tree)
    return [
        f"{path}:{line}: undefined name '{name}'" for line, name in c.findings
    ]


_BOUND_MARKERS = ("deadline", "timeout")
_POLL_CALLS = {"sleep", "wait"}
_WAIT_SCOPED_DIRS = ("torch_backend", "robustness")
# The polling rule additionally covers observability/: the live health
# plane (PR 6) runs background evaluator/exposition threads beside
# training, and an unbounded spin there would hang teardown exactly like
# a transport wait — park on a stop event or carry a deadline.
_POLL_SCOPED_DIRS = _WAIT_SCOPED_DIRS + ("observability",)


def _const_true(test: ast.expr) -> bool:
    return isinstance(test, ast.Constant) and bool(test.value) is True


def check_unbounded_waits(path: Path, tree: ast.Module) -> List[str]:
    """Robustness gate for the bridge transport: a bare ``while True``
    polling loop (one that sleeps/waits between probes) must carry a
    deadline — a name/attribute/keyword mentioning deadline/timeout — or
    raise. An unbounded poll turns a dead peer into a hang; the hardened
    data plane's contract is that every wait is bounded
    (docs/ROBUSTNESS.md). Scoped to torch_backend/ and robustness/, where
    the blocking waits live, plus observability/ (its health/exposition
    background threads must never outlive a stop request)."""
    if not any(d in path.parts for d in _POLL_SCOPED_DIRS):
        return []
    findings = []
    # Critical-path engine branch (ISSUE 17): the span analyzer reads
    # whole JSONL files other processes are still appending to — every
    # file read there must carry an explicit byte cap (``f.read(n)``;
    # an argless ``read``/``readlines``/``readline`` scales the
    # analysis with run length and the unbounded-read is the analyzer's
    # version of an unbounded wait). Same rule family, same register.
    if path.name == "critpath.py":
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute):
                continue
            if fn.attr in ("read", "readlines", "readline") and not (
                node.args or node.keywords
            ):
                findings.append(
                    f"{path}:{node.lineno}: unbounded span-file read: "
                    f"'.{fn.attr}()' without a byte cap — pass an "
                    "explicit size (CGX_CRITPATH_MAX_MB bounds the "
                    "analysis, not the run)"
                )
    for node in ast.walk(tree):
        if not isinstance(node, ast.While) or not _const_true(node.test):
            continue
        polls = bounded = False
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                fn = n.func
                name = (
                    fn.attr
                    if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else ""
                )
                if name in _POLL_CALLS:
                    polls = True
                for kw in n.keywords:
                    if kw.arg and any(
                        m in kw.arg.lower() for m in _BOUND_MARKERS
                    ):
                        bounded = True
            elif isinstance(n, ast.Raise):
                bounded = True
            elif isinstance(n, ast.Name) and any(
                m in n.id.lower() for m in _BOUND_MARKERS
            ):
                bounded = True
            elif isinstance(n, ast.Attribute) and any(
                m in n.attr.lower() for m in _BOUND_MARKERS
            ):
                bounded = True
        if polls and not bounded:
            findings.append(
                f"{path}:{node.lineno}: unbounded wait: 'while True' "
                "polling loop without a deadline/timeout or raise"
            )
    return findings


_BROAD_EXC_NAMES = {"Exception", "BaseException"}
_SUPERVISED_EXC_NAMES = {"BridgeTimeoutError", "WireCorruptionError"}
_SUPERVISOR_CALL_MARKERS = (
    "record_failure", "notify", "recover", "handle_failure", "supervisor",
)


def _exc_type_names(node) -> List[str]:
    """Exception class names a handler catches: bare except -> [""],
    Name/Attribute taken directly, tuples flattened."""
    if node is None:
        return [""]
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, ast.Attribute):
        return [node.attr]
    if isinstance(node, ast.Tuple):
        out: List[str] = []
        for e in node.elts:
            out.extend(_exc_type_names(e))
        return out
    return []


def check_exception_hygiene(path: Path, tree: ast.Module) -> List[str]:
    """Recovery gate for the data plane (torch_backend/ + robustness/):

    * ``except Exception: pass`` (or a bare ``except: pass``) silently
      swallows the exact failures the recovery supervisor exists to see —
      a dead peer or corrupted payload digested into nothing. Narrow the
      type (``except OSError: pass`` is fine) or do something with it.
    * a handler catching ``BridgeTimeoutError``/``WireCorruptionError``
      must either re-raise or hand the event to the supervisor/black box
      (a call mentioning record_failure/notify/recover/handle_failure/
      supervisor) — digesting a detected fault without telling anyone
      reverts the failure semantics to a silent hang-shaped bug.
    """
    if not any(d in path.parts for d in _WAIT_SCOPED_DIRS):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        names = _exc_type_names(node.type)
        body_is_pass = all(isinstance(s, ast.Pass) for s in node.body)
        if body_is_pass and any(
            n in _BROAD_EXC_NAMES or n == "" for n in names
        ):
            what = "bare except" if names == [""] else f"except {names[0]}"
            findings.append(
                f"{path}:{node.lineno}: swallowed exception: '{what}: "
                "pass' in the data plane — narrow the exception type or "
                "surface the failure (docs/ROBUSTNESS.md Recovery)"
            )
            continue
        caught = [n for n in names if n in _SUPERVISED_EXC_NAMES]
        if not caught:
            continue
        notified = False
        for n in ast.walk(node):
            if isinstance(n, ast.Raise):
                notified = True
                break
            if isinstance(n, ast.Call):
                fn = n.func
                name = (
                    fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else ""
                )
                if any(m in name.lower() for m in _SUPERVISOR_CALL_MARKERS):
                    notified = True
                    break
            if isinstance(n, (ast.Name, ast.Attribute)):
                ident = n.attr if isinstance(n, ast.Attribute) else n.id
                if "supervisor" in ident.lower():
                    notified = True
                    break
        if not notified:
            findings.append(
                f"{path}:{node.lineno}: {'/'.join(caught)} caught without "
                "re-raising or notifying the recovery supervisor/black "
                "box — a detected data-plane fault must not be digested "
                "silently (docs/ROBUSTNESS.md Recovery)"
            )
    return findings


_LIB_DIR = "torch_cgx_tpu"
_METRIC_WRITE_METHODS = {"add", "set", "observe"}
_METRIC_RECEIVERS = {"metrics", "_metrics"}
_METRIC_NAMESPACES = ("cgx.", "span.")
# Documented `cgx.<sub>.` sub-namespaces (docs/OBSERVABILITY.md "Metric
# namespaces" + "Live health plane"). A dotted name under `cgx.` outside
# this set is a typo'd family the report/dashboard prefix scans (and the
# Prometheus exposition grouping) would silently miss. Flat names
# (`cgx.arena_pressure_waits`) and dynamic prefixes that stop at `cgx.`
# stay uncheckable and pass.
_METRIC_CGX_SUBNAMESPACES = frozenset({
    # "codec" joined with the roofline round-2 work (PR 11): the
    # producer-fused gradient quantizer (cgx.codec.producer_*) and the
    # lowering ledger (cgx.codec.lowering.*) — docs/OBSERVABILITY.md.
    # "plan" is the whole-step planner family (PR 12): plan-LRU
    # hits/misses/invalidations, per-slice chunk/bit gauges, the
    # predicted-step gauge and the bridge depth hints —
    # docs/OBSERVABILITY.md "Metric namespaces".
    # "async" is the asynchronous cross-slice plane (PR 13): outer-round
    # counters, the sender-thread wire gauge, lag gauges and the
    # planner's route prediction — docs/OBSERVABILITY.md.
    # "serve" is the serving data plane (PR 15): request/token/page
    # counters, the tokens_per_s gauge and ttft_ms histogram (the SLO
    # controller's inputs), transport stream counters, prefill-failover
    # and pool-pressure incidents — docs/OBSERVABILITY.md.
    # "elastic" is the elastic membership plane (PR 16): join intents /
    # triggers / admissions, snapshot-page ship/receive/re-request
    # counters, the last_join_ms gauge and reaped-key counts —
    # docs/OBSERVABILITY.md.
    # "critpath" is the distributed critical-path engine (PR 17):
    # analysis/cache counters, per-component seconds of the last step
    # window, the dominant-rank gauge and the drift-loop trip counter —
    # docs/OBSERVABILITY.md "Critical path & drift".
    # "mem" is the memory observability plane (PR 18): per-pool
    # used/free/tte/frag gauges, the total/peak high-water gauges,
    # leak-suspect and sample counters, and the mem_leak/mem_pressure
    # event counters — docs/OBSERVABILITY.md "Memory plane".
    # "transport" is the supervised socket data plane (PR 20): framed
    # tx/rx counters, ack/ping/resend/reconnect counters, crc/dedup
    # drops, link_down + degraded-edge gauges and the store-fallback
    # counters — docs/OBSERVABILITY.md "Network transport".
    "async", "codec", "collective", "critpath", "elastic", "faults",
    "flightrec", "health", "heartbeat", "mem", "plan", "qerr",
    "recovery", "ring", "runtime", "sched", "serve", "shm", "sra",
    "step", "trace", "transport", "wire", "xla",
})


def _literal_metric_name(arg: ast.expr) -> Optional[str]:
    """The static prefix of a metric-name argument: a plain string, or the
    leading constant of an f-string (``f"cgx.faults.{mode}"`` ->
    ``"cgx.faults."``). None = dynamic, not checkable."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if (
        isinstance(arg, ast.JoinedStr)
        and arg.values
        and isinstance(arg.values[0], ast.Constant)
        and isinstance(arg.values[0].value, str)
    ):
        return arg.values[0].value
    return None


def check_library_hygiene(path: Path, tree: ast.Module) -> List[str]:
    """Observability gates, scoped to torch_cgx_tpu/ library code:

    * no bare ``print(`` — the reference's printf-only observability is the
      exact gap this codebase closes; library output goes through
      ``utils.logging.get_logger()`` (leveled) or the metric registry.
    * metric names written via ``metrics.add/set/observe`` must live in
      the documented ``cgx.`` / ``span.`` namespaces
      (docs/OBSERVABILITY.md) — an off-namespace name is invisible to the
      exporter's dashboards and the report tool's prefix scans.
    * dotted families under ``cgx.`` must use a documented sub-namespace
      (``_METRIC_CGX_SUBNAMESPACES`` — ``cgx.health.*`` joined the list
      with the live health plane): ``cgx.helth.events`` would silently
      fall out of every prefix scan.
    """
    if _LIB_DIR not in path.parts:
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "print":
            findings.append(
                f"{path}:{node.lineno}: bare print() in library code — "
                "use utils.logging.get_logger() or the metrics registry"
            )
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in _METRIC_WRITE_METHODS
            and isinstance(fn.value, ast.Name)
            and fn.value.id in _METRIC_RECEIVERS
            and node.args
        ):
            name = _literal_metric_name(node.args[0])
            if name is None:
                continue
            if not name.startswith(_METRIC_NAMESPACES):
                findings.append(
                    f"{path}:{node.lineno}: metric name {name!r} outside "
                    f"the documented namespaces {_METRIC_NAMESPACES} "
                    "(docs/OBSERVABILITY.md)"
                )
            elif name.startswith("cgx.") and "." in name[len("cgx."):]:
                sub = name[len("cgx."):].split(".", 1)[0]
                if sub not in _METRIC_CGX_SUBNAMESPACES:
                    findings.append(
                        f"{path}:{node.lineno}: metric name {name!r} uses "
                        f"undocumented cgx sub-namespace {sub!r} — add it "
                        "to the documented families (docs/OBSERVABILITY.md"
                        " Metric namespaces) or fix the name"
                    )
    return findings


_REDUCE_ROUTE_ESCAPES = ("_reference", "_staged", "_unrolled")


def check_reducer_reduce_routing(path: Path, tree: ast.Module) -> List[str]:
    """Perf gate for the SRA/Ring hot path (parallel/reducers.py only): a
    reducer variant that decodes peer rows with ``_dequantize_rows`` and
    then reduces them with ``.sum(``/``jnp.sum`` re-materializes exactly
    the (ws, chunk) f32 intermediate the fused epilogue kernel eliminates
    — new variants must route the decompress-accumulate through
    ``ops.dispatch.reduce_rows`` (fused Pallas kernel on TPU dispatch,
    staged reference elsewhere; docs/COMPRESSION_GUIDE.md). Functions
    whose names end in ``_reference``/``_staged``/``_unrolled`` are the
    documented escape hatch — the suite's oracles keep the spelled-out
    staged form."""
    if (
        _LIB_DIR not in path.parts
        or "parallel" not in path.parts
        or path.name != "reducers.py"
    ):
        return []
    flagged: Dict[int, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if any(node.name.endswith(sfx) for sfx in _REDUCE_ROUTE_ESCAPES):
            continue
        deq_line = None
        has_sum = False
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            name = (
                fn.attr
                if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            if name == "_dequantize_rows" and deq_line is None:
                deq_line = n.lineno
            if name == "sum":
                has_sum = True
        if deq_line is not None and has_sum:
            flagged.setdefault(
                deq_line,
                f"{path}:{deq_line}: `_dequantize_rows` decode reduced "
                "with `.sum(`/`jnp.sum` in reducer variant "
                f"{node.name!r} — route the decompress-accumulate "
                "through ops.dispatch.reduce_rows (fused on TPU, staged "
                "reference elsewhere); suffix the function _reference/"
                "_staged/_unrolled if it IS the staged oracle",
            )
    return [flagged[k] for k in sorted(flagged)]


# Fused-epilogue kernel bodies (names matching this pattern anywhere
# under ops/) may never materialize a full-width f32 intermediate from
# decoded peer rows: the audited f32 fold lives in ONE place —
# ``codec_pallas._decode_accumulate`` (with ``_requant_cast``/
# ``_read_raw4`` for the small requantize-cast and raw-chunk reads) —
# and the int8 fixed-point accumulation mode exists precisely so new
# kernel code folds rows in the integer level domain. ``_reference``/
# ``_staged``-suffixed functions are the suite's escape hatch, as in the
# reducer-routing rule.
_EPILOGUE_KERNEL_RE = r"(_sra_epilogue|_reduce_rows).*_kernel$"


def check_epilogue_f32_intermediates(path: Path, tree: ast.Module) -> List[str]:
    """Reject ``.astype(jnp.float32)`` (and bare ``float32``) calls inlined
    into fused-epilogue kernel bodies in ops/ — decoded peer rows must
    fold through ``_decode_accumulate`` (the one audited f32 conversion
    site) or stay in the integer domain (``CGX_SRA_ACCUM=int8``)."""
    if _LIB_DIR not in path.parts or "ops" not in path.parts:
        return []
    out: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _re.search(_EPILOGUE_KERNEL_RE, node.name):
            continue
        if any(s in node.name for s in ("_reference", "_staged")):
            continue
        for n in ast.walk(node):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "astype"
                and n.args
            ):
                continue
            arg = n.args[0]
            is_f32 = (
                isinstance(arg, ast.Attribute) and arg.attr == "float32"
            ) or (isinstance(arg, ast.Name) and arg.id == "float32")
            if is_f32:
                out.append(
                    f"{path}:{n.lineno}: `.astype(float32)` inside fused-"
                    f"epilogue kernel body {node.name!r} — full-width f32 "
                    "intermediates on decoded peer rows belong in "
                    "_decode_accumulate (the audited fold) or the int8 "
                    "accumulation domain; suffix the function "
                    "_reference/_staged if it IS the staged oracle"
                )
    return out


_STAGED_PURE_MANIFEST = "xla_allreduce.py"
_CALLBACK_NAMES = {"io_callback", "pure_callback"}
# Last-resort coverage when the manifest FILE itself is gone (deleted or
# renamed): the committed staged-pure set, hardcoded so the rule stays
# armed — a missing manifest must degrade loudly, never silently disarm.
_STAGED_PURE_FALLBACK = (
    ("torch_cgx_tpu", "parallel", "xla_allreduce.py"),
    ("torch_cgx_tpu", "parallel", "topology.py"),
    ("torch_cgx_tpu", "parallel", "schedule.py"),
)


def _staged_pure_suffixes(manifest_path: Path):
    """The ``STAGED_PURE`` path list declared in
    parallel/xla_allreduce.py (parsed through the shared parse cache,
    never imported — lint must not execute library code). Entries are
    repo-relative paths, returned as part tuples for suffix matching.
    None = file missing or no parseable declaration."""
    src = get_source(manifest_path)
    if src.tree is None:
        return None
    tree = src.tree
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "STAGED_PURE"
            for t in node.targets
        ):
            continue
        out = []
        for n in ast.walk(node.value):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                out.append(tuple(n.value.split("/")))
        return out
    return None


def check_staged_purity(path: Path, tree: ast.Module) -> List[str]:
    """Staged-purity gate for the in-XLA single-program allreduce: the
    modules ``parallel/xla_allreduce.py`` lists in its ``STAGED_PURE``
    manifest (and that file itself) must never import or reference
    ``io_callback``/``pure_callback`` — one host callback inside the
    staged program silently reintroduces the host round trip the staged
    path exists to remove, and nothing at runtime would flag it (the
    program still computes correct values, just slower). The jaxpr guard
    in tests/test_xla_allreduce.py catches staged impurity at trace
    time; this rule catches it at review time, in any code path."""
    parts = tuple(path.parts)
    if _LIB_DIR not in parts:
        return []
    # Manifest lives at a fixed repo-relative spot (<lib>/parallel/) so
    # the rule still arms for STAGED_PURE entries anywhere under the lib,
    # not just siblings of the manifest.
    lib_root = Path(*parts[: parts.index(_LIB_DIR) + 1])
    manifest = lib_root / "parallel" / _STAGED_PURE_MANIFEST
    if path.name == _STAGED_PURE_MANIFEST and path.parent.name == "parallel":
        suffixes = _staged_pure_suffixes(path)
        if suffixes is None:
            return [
                f"{path}:1: staged-pure manifest missing: "
                "xla_allreduce.py must declare a STAGED_PURE tuple of the "
                "modules the purity rule covers"
            ]
    else:
        suffixes = _staged_pure_suffixes(manifest)
        missing_manifest = not manifest.exists()
        if missing_manifest:
            # Deleted/renamed manifest: stay armed on the committed
            # fallback set, and say so on any file it covers.
            suffixes = list(_STAGED_PURE_FALLBACK)
        if not suffixes:
            return []
        if not any(
            len(s) <= len(parts) and parts[len(parts) - len(s):] == s
            for s in suffixes
        ):
            return []
        if missing_manifest:
            return [
                f"{path}:1: staged-pure manifest "
                f"{manifest} is missing — the purity rule is running on "
                "lint.py's built-in fallback list; restore the "
                "STAGED_PURE declaration"
            ] + _staged_purity_findings(path, tree)
    return _staged_purity_findings(path, tree)


def _staged_purity_findings(path: Path, tree: ast.Module) -> List[str]:
    findings: List[str] = []

    def flag(lineno: int, what: str) -> None:
        findings.append(
            f"{path}:{lineno}: {what} in a staged-pure module — the "
            "in-XLA single-program allreduce must not contain host "
            "callbacks (xla_allreduce.STAGED_PURE; docs/PERF_NOTES.md "
            "Single-program allreduce)"
        )

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name in _CALLBACK_NAMES:
                    flag(node.lineno, f"import of {a.name!r}")
        elif isinstance(node, ast.Import):
            for a in node.names:
                leaf = a.name.rsplit(".", 1)[-1]
                if leaf in _CALLBACK_NAMES:
                    flag(node.lineno, f"import of {a.name!r}")
        elif isinstance(node, ast.Attribute):
            if node.attr in _CALLBACK_NAMES:
                flag(node.lineno, f"reference to .{node.attr}")
        elif isinstance(node, ast.Name):
            if node.id in _CALLBACK_NAMES and isinstance(node.ctx, ast.Load):
                flag(node.lineno, f"reference to {node.id!r}")
    return findings


_SCHED_BLOCKING_CALLS = {"block_until_ready"}


def _is_sched_stage_scope(path: Path, fn_name: str) -> bool:
    """Whether a function body is schedule-executed pipeline code: anything
    in ``parallel/schedule.py``, or a worker-loop pipelined section in
    ``torch_backend/backend.py`` (functions/methods named ``*pipelined*``
    or ``*sched*`` — the ``_qreduce_sra_pipelined`` family and its
    helpers)."""
    if _LIB_DIR not in path.parts:
        return False
    if "parallel" in path.parts and path.name == "schedule.py":
        return True
    if "torch_backend" in path.parts and path.name == "backend.py":
        return "pipelined" in fn_name or "sched" in fn_name
    return False


def check_schedule_stage_blocking(path: Path, tree: ast.Module) -> List[str]:
    """Pipeline-purity gate for the compiled collective schedules: a stage
    body executed by the schedule (``parallel/schedule.py``, and the
    worker-loop pipelined sections of ``torch_backend/backend.py``) must
    never synchronize the pipeline it exists to overlap —

    * ``x.block_until_ready()`` inside a staged stage body drains every
      in-flight chunk's collective before the next stage is even issued
      (and on the staged-pure plane would not even lint as a callback,
      since it is a host-side sync, not an ``io_callback``);
    * an UNCONDITIONAL ``.result()`` (no ``timeout=``) on a
      future/async handle parks the worker thread forever behind a chunk
      a dead peer will never deliver — every pipelined wait must be
      bounded, like every other bridge wait (docs/ROBUSTNESS.md).

    ``.result(timeout=...)`` is the sanctioned form. Scoped tightly so
    the monolithic paths (and tests/benches, which legitimately sync)
    stay unconstrained."""
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _is_sched_stage_scope(path, node.name):
            continue
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            if name in _SCHED_BLOCKING_CALLS:
                findings.append(
                    f"{path}:{n.lineno}: blocking '{name}()' inside "
                    f"schedule-executed stage body {node.name!r} — a "
                    "device sync serializes the very pipeline the "
                    "schedule compiles (parallel/schedule.py contract; "
                    "docs/PERF_NOTES.md Compiled schedules)"
                )
            elif name == "result" and isinstance(fn, ast.Attribute):
                if not any(
                    kw.arg and "timeout" in kw.arg.lower()
                    for kw in n.keywords
                ) and not n.args:
                    findings.append(
                        f"{path}:{n.lineno}: unconditional '.result()' "
                        f"inside schedule-executed stage body "
                        f"{node.name!r} — bound it with timeout= so a "
                        "dead peer cannot park the pipeline forever "
                        "(docs/ROBUSTNESS.md; parallel/schedule.py "
                        "contract)"
                    )
    return findings


# Wire-plane routing gate: the modules whose collectives are EDGES of the
# unified wire plane must send payloads through wire.dispatch (so the edge
# registry, the per-edge counters and the closed-loop controller see
# them), never via a bare lax collective the dispatcher cannot intercept.
# Control/index tensors (bool masks riding beside a K/V block) are the
# documented exemption — they live in functions named in the allowlist.
_WIRE_EDGE_FILES = ("moe.py", "ring_attention.py", "pipeline.py")
_WIRE_PAYLOAD_COLLECTIVES = {"ppermute", "all_to_all"}
_WIRE_RAW_ALLOWLIST = frozenset({"_rotate_control"})


def check_wire_edge_routing(path: Path, tree: ast.Module) -> List[str]:
    """Every ``ppermute``/``all_to_all`` call in
    ``parallel/{moe,ring_attention,pipeline}.py`` must go through
    ``wire.dispatch`` (``wire_ppermute``/``wire_all_to_all``) — a direct
    ``lax`` payload send bypasses the edge registry, ships raw bytes no
    matter what the operator configured, and is invisible to the
    ``cgx.wire.*`` accounting. Functions in ``_WIRE_RAW_ALLOWLIST``
    (control/index tensors that must never quantize) are exempt."""
    if (
        _LIB_DIR not in path.parts
        or "parallel" not in path.parts
        or path.name not in _WIRE_EDGE_FILES
    ):
        return []
    findings: List[str] = []

    def walk(node: ast.AST, fn_name: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk(child, child.name)
                continue
            if isinstance(child, ast.Call):
                fn = child.func
                name = (
                    fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else ""
                )
                if (
                    name in _WIRE_PAYLOAD_COLLECTIVES
                    and fn_name not in _WIRE_RAW_ALLOWLIST
                ):
                    findings.append(
                        f"{path}:{child.lineno}: direct '{name}' payload "
                        f"send in {fn_name or '<module>'!r} bypasses the "
                        "wire dispatcher — route it through "
                        "wire.dispatch.wire_ppermute/wire_all_to_all, or "
                        "move control-tensor sends into an allowlisted "
                        "function (tools/analysis/perfile.py "
                        "_WIRE_RAW_ALLOWLIST; docs/COMPRESSION_GUIDE.md "
                        "'Every wire, one dispatcher')"
                    )
            walk(child, fn_name)

    walk(tree, "")
    return findings


# Registry-ownership gate (ISSUE 12): the whole-step planner
# (parallel/planner.py) owns the decision registries — the layout LRU,
# the schedule LRU and the controller's bit writes. New library code must
# route registry mutations through the planner (a new perf lever is a
# cost-model change, not a new registry writer). The allowlist is the
# planner itself plus the LEGACY INERT PATH: the registries' own modules
# (their internal clear/invalidate plumbing), the recovery supervisor's
# invalidation ladder, and the pre-planner writers (adaptive.apply_bit_
# allocation, the WireController's _apply, checkpoint restore) that the
# planner drives but does not replace.
_REGISTRY_MUTATORS = frozenset({
    "invalidate_layout_cache", "invalidate_schedule_cache",
    "invalidate_plan_cache", "layout_cache_clear", "schedule_cache_clear",
    "plan_cache_clear", "set_edge_config", "set_layer_pattern_config",
})
_REGISTRY_OWNER_SUFFIXES = (
    ("parallel", "planner.py"),      # the owner
    ("parallel", "allreduce.py"),    # layout LRU home + cascade
    ("parallel", "schedule.py"),     # schedule LRU home
    ("parallel", "adaptive.py"),     # legacy offline bit solver
    ("wire", "controller.py"),       # legacy closed-loop bit writes
    ("wire", "edges.py"),            # edge-registry home
    ("serving", "slo.py"),           # SLO-scoped kv_page bit writes: the
    #                                  serving objective of the same
    #                                  closed loop (label-prefix-scoped,
    #                                  so it can never touch a training
    #                                  edge's allocation)
    ("robustness", "supervisor.py"),  # recovery invalidation ladder
    ("config.py",),                  # registry definitions themselves
    ("checkpoint.py",),              # snapshot restore re-registers
)


def check_planner_registry_ownership(path: Path, tree: ast.Module) -> List[str]:
    """Reject direct layout-LRU / schedule-LRU / plan-LRU / controller
    registry writes in library code outside ``parallel/planner.py`` and
    the legacy inert path above — once the planner owns the registries,
    a new subsystem mutating them directly would fork the decision plane
    the planner exists to unify (docs/PERF_NOTES.md "Whole-step
    mega-schedule"). Tests/tools/benches are out of scope (they
    legitimately poke registries to set up scenarios)."""
    parts = tuple(path.parts)
    if _LIB_DIR not in parts:
        return []
    rel = parts[parts.index(_LIB_DIR) + 1:]
    if any(
        len(s) <= len(rel) and rel[len(rel) - len(s):] == s
        for s in _REGISTRY_OWNER_SUFFIXES
    ):
        return []
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.attr
            if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name) else ""
        )
        if name in _REGISTRY_MUTATORS:
            findings.append(
                f"{path}:{node.lineno}: registry mutation '{name}()' "
                "outside parallel/planner.py and the legacy inert path — "
                "the step planner owns the layout/schedule/plan LRUs and "
                "the controller registry writes; route the decision "
                "through the planner (tools/analysis/perfile.py "
                "_REGISTRY_OWNER_SUFFIXES; docs/PERF_NOTES.md 'Whole-step "
                "mega-schedule')"
            )
    return findings


# Async-plane blocking gate (PR 13): the whole point of the decoupled
# cross-slice exchange is that the train step NEVER blocks on DCN — so
# nothing in parallel/async_plane.py or torch_backend/async_bridge.py may
# park a thread on an unbounded wait. An unconditional `.result()` (no
# timeout) or a `_wait_key`-style call without a timeout keyword would put
# a dead peer right back on the critical path the plane exists to leave.
_ASYNC_PLANE_FILES = (
    ("parallel", "async_plane.py"),
    ("torch_backend", "async_bridge.py"),
)


def _is_async_plane_file(path: Path) -> bool:
    parts = tuple(path.parts)
    if _LIB_DIR not in parts:
        return False
    rel = parts[parts.index(_LIB_DIR) + 1:]
    return any(
        len(s) <= len(rel) and rel[len(rel) - len(s):] == s
        for s in _ASYNC_PLANE_FILES
    )


def check_async_sender_blocking(path: Path, tree: ast.Module) -> List[str]:
    """No blocking store/shm waits in the async plane's bodies:

    * an UNCONDITIONAL ``.result()`` (no ``timeout=``) on a future parks
      the sender thread (or worse, the training loop) forever behind a
      payload a dead peer will never deliver;
    * any call whose name contains ``wait_key`` without a timeout-ish
      keyword is the bridge's blocking header wait — the async plane
      must only touch bytes that are already published
      (publish-after-write counters), never wait for ones that are not.

    ``.result(timeout=...)`` and explicitly-bounded waits pass. Scope is
    the two async-plane files only (the sync bridge keeps its own
    bounded-wait rules)."""
    if not _is_async_plane_file(path):
        return []
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            bounded = any(
                kw.arg and "timeout" in kw.arg.lower() for kw in n.keywords
            )
            if name == "result" and isinstance(fn, ast.Attribute):
                if not bounded and not n.args:
                    findings.append(
                        f"{path}:{n.lineno}: unconditional '.result()' in "
                        f"async-plane body {node.name!r} — the decoupled "
                        "cross-slice exchange must never block on DCN; "
                        "bound it with timeout= (tools/analysis/perfile.py "
                        "check_async_sender_blocking; docs/PERF_NOTES.md "
                        "'Asynchronous cross-slice plane')"
                    )
            elif "wait_key" in name and not bounded:
                findings.append(
                    f"{path}:{n.lineno}: blocking '{name}' without a "
                    f"timeout in async-plane body {node.name!r} — the "
                    "async plane only touches already-published bytes "
                    "(publish-after-write), it never waits for a header "
                    "(tools/analysis/perfile.py check_async_sender_blocking)"
                )
    return findings


# Serving-plane blocking gate (PR 15, the check_async_sender_blocking
# family): the continuous-batching decode loop must NEVER park — an
# unbounded wait anywhere in torch_cgx_tpu/serving/ puts a dead prefill
# worker (or a slow store) on the critical path of every admitted lane,
# which is exactly the wedge the publish-after-write streams + bounded
# failover exist to prevent (docs/SERVING.md "Never block").
_SERVE_PLANE_DIR = "serving"


def _is_serve_plane_file(path: Path) -> bool:
    parts = tuple(path.parts)
    if _LIB_DIR not in parts:
        return False
    rel = parts[parts.index(_LIB_DIR) + 1:]
    return len(rel) >= 2 and rel[0] == _SERVE_PLANE_DIR


def check_serve_scheduler_blocking(path: Path, tree: ast.Module) -> List[str]:
    """No unbounded waits in the serving plane's bodies:

    * an UNCONDITIONAL ``.result()`` (no ``timeout=``) parks the decode
      loop behind a payload a dead prefill worker will never deliver;
    * any call whose name contains ``wait_key`` without a timeout-ish
      keyword is the bridge's blocking header wait — the serving plane
      only touches already-published bytes (publish-after-write
      counters), it never waits for a header;
    * a bare ``.join()`` (no args, no ``timeout=``) parks forever on a
      thread that may never exit (sender threads are joined bounded in
      ``stop()``; string ``sep.join(parts)`` calls carry an argument
      and pass).

    Scope: every file under ``torch_cgx_tpu/serving/``."""
    if not _is_serve_plane_file(path):
        return []
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            bounded = any(
                kw.arg and "timeout" in kw.arg.lower() for kw in n.keywords
            )
            if name == "result" and isinstance(fn, ast.Attribute):
                if not bounded and not n.args:
                    findings.append(
                        f"{path}:{n.lineno}: unconditional '.result()' in "
                        f"serving-plane body {node.name!r} — the decode "
                        "loop must never block; bound it with timeout= "
                        "(tools/analysis/perfile.py "
                        "check_serve_scheduler_blocking; docs/SERVING.md)"
                    )
            elif "wait_key" in name and not bounded:
                findings.append(
                    f"{path}:{n.lineno}: blocking '{name}' without a "
                    f"timeout in serving-plane body {node.name!r} — the "
                    "serving plane only touches already-published bytes "
                    "(publish-after-write counters) "
                    "(tools/analysis/perfile.py "
                    "check_serve_scheduler_blocking)"
                )
            elif (
                name == "join"
                and isinstance(fn, ast.Attribute)
                and not n.args
                and not bounded
            ):
                findings.append(
                    f"{path}:{n.lineno}: unbounded '.join()' in "
                    f"serving-plane body {node.name!r} — a thread that "
                    "never exits would park the serving loop forever; "
                    "pass timeout= (tools/analysis/perfile.py "
                    "check_serve_scheduler_blocking)"
                )
    return findings


def _timeline_bridge_ops(timeline_path: Path):
    """The ``BRIDGE_OPS`` name list declared in observability/timeline.py
    (parsed through the shared parse cache, never imported — lint must
    not execute library code). None = file missing or no parseable
    frozenset literal."""
    src = get_source(timeline_path)
    if src.tree is None:
        return None
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "BRIDGE_OPS"
            for t in node.targets
        ):
            continue
        names = set()
        for n in ast.walk(node.value):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                names.add(n.value)
        return names
    return None


def check_worker_timeline_coverage(path: Path, tree: ast.Module) -> List[str]:
    """Timeline-coverage gate for the bridge worker loop: every literal
    ``op="..."`` a collective passes to ``_submit`` (the name the worker
    loop emits a timeline span under) must appear in
    ``observability/timeline.py``'s ``BRIDGE_OPS`` list — the name-list
    the trace merger's per-op attribution and the docs key off. A new
    collective added to the backend without a timeline entry would
    produce spans the tooling cannot categorize; make it a lint failure
    (same style as the print/metric-namespace rules)."""
    if (
        _LIB_DIR not in path.parts
        or "torch_backend" not in path.parts
        or path.name != "backend.py"
    ):
        return []
    ops: Dict[str, int] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "_submit"):
            continue
        for kw in node.keywords:
            if (
                kw.arg == "op"
                and isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, str)
                and kw.value.value
            ):
                ops.setdefault(kw.value.value, node.lineno)
    if not ops:
        return []
    timeline_path = path.parent.parent / "observability" / "timeline.py"
    declared = _timeline_bridge_ops(timeline_path)
    if declared is None:
        return [
            f"{path}:1: worker-loop ops cannot be cross-checked: "
            f"{timeline_path} missing or lacks a BRIDGE_OPS frozenset"
        ]
    return [
        f"{path}:{line}: worker-loop op {op!r} missing from "
        "observability/timeline.py BRIDGE_OPS — its timeline span would "
        "be uncategorized in cgx_trace attribution"
        for op, line in sorted(ops.items())
        if op not in declared
    ]


def _health_event_kinds(health_path: Path):
    """The ``EVENT_KINDS`` registry declared in observability/health.py
    (parsed through the shared parse cache, never imported), with the
    tuple's Name references resolved against the module's own
    ``KIND = "string"`` constants. None = file missing or no registry."""
    src = get_source(health_path)
    if src.tree is None:
        return None
    consts: Dict[str, str] = {}
    kinds_node = None
    for node in src.tree.body:
        if not (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            continue
        name = node.targets[0].id
        if isinstance(node.value, ast.Constant) and isinstance(
            node.value.value, str
        ):
            consts[name] = node.value.value
        elif name == "EVENT_KINDS":
            kinds_node = node.value
    if kinds_node is None:
        return None
    out = set()
    for n in ast.walk(kinds_node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.add(n.value)
        elif isinstance(n, ast.Name) and n.id in consts:
            out.add(consts[n.id])
    return out or None


def check_health_event_kinds(path: Path, tree: ast.Module) -> List[str]:
    """HealthEvent-kind registry gate (ISSUE 17): every ``kind=`` a
    ``HealthEvent(...)`` construction site passes — a string literal or
    a Name resolvable against the file's own module-level string
    constants — must appear in observability/health.py's
    ``EVENT_KINDS`` tuple. The docs event table, cgx_top's event pane
    and the flight recorder's rename all key off that registry; an
    event emitted under an unregistered kind is invisible to all of
    them (same cross-check style as timeline-coverage)."""
    if _LIB_DIR not in path.parts:
        return []
    consts: Dict[str, str] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            consts[node.targets[0].id] = node.value.value
    sites: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.attr if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name) else ""
        )
        if name != "HealthEvent":
            continue
        for kw in node.keywords:
            if kw.arg != "kind":
                continue
            if isinstance(kw.value, ast.Constant) and isinstance(
                kw.value.value, str
            ):
                sites.append((node.lineno, kw.value.value))
            elif isinstance(kw.value, ast.Name) and kw.value.id in consts:
                sites.append((node.lineno, consts[kw.value.id]))
    if not sites:
        return []
    idx = path.parts.index(_LIB_DIR)
    health_path = Path(*path.parts[: idx + 1]) / "observability" / "health.py"
    declared = _health_event_kinds(health_path)
    if declared is None:
        return [
            f"{path}:1: HealthEvent kinds cannot be cross-checked: "
            f"{health_path} missing or lacks an EVENT_KINDS registry"
        ]
    return [
        f"{path}:{line}: HealthEvent kind {kind!r} missing from "
        "observability/health.py EVENT_KINDS — the docs table, cgx_top "
        "event pane and flightrec rename key off that registry"
        for line, kind in sorted(sites)
        if kind not in declared
    ]


_SOCKET_IO_CALLS = frozenset({
    "recv", "recv_into", "recvfrom", "accept", "connect", "connect_ex",
})
_SOCKET_CREATE_CALLS = frozenset({"socket", "create_connection"})


def check_transport_bounded_io(path: Path, tree: ast.Module) -> List[str]:
    """Socket-plane discipline gate (PR 20), scoped to torch_cgx_tpu/:

    * every function performing blocking socket i/o (``recv*`` /
      ``accept`` / ``connect``) must arm a deadline in the same scope —
      a ``settimeout(...)`` call, a ``timeout=`` keyword, or a
      deadline/timeout-named binding. An unbounded recv is the
      transport's version of an unbounded wait: a cut link becomes a
      hang instead of a reconnect/degrade verdict (docs/ROBUSTNESS.md
      "Network transport").
    * ``settimeout(None)`` and ``setblocking(True)`` are forbidden
      outright — both silently re-arm the infinite-block mode the
      whole plane is designed to exclude.
    * a function that CREATES a socket (``socket.socket(...)`` /
      ``create_connection(...)``) must either close it on the failure
      path (a ``.close()`` inside a ``try`` handler/finally) or hand
      ownership to an attribute (``self._sock = ...``) whose owner's
      ``close()`` is supervised — otherwise a mid-construction raise
      leaks the fd every reconnect attempt."""
    if _LIB_DIR not in path.parts:
        return []
    findings: List[str] = []
    funcs = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn_node in funcs:
        io_lines: List[int] = []
        creates: List[int] = []
        bounded = False
        closed_in_handler = False
        attr_owned = False
        for n in ast.walk(fn_node):
            if isinstance(n, ast.Call):
                f = n.func
                name = (
                    f.attr if isinstance(f, ast.Attribute)
                    else f.id if isinstance(f, ast.Name) else ""
                )
                if name in _SOCKET_IO_CALLS and isinstance(
                    f, ast.Attribute
                ):
                    io_lines.append(n.lineno)
                if name in _SOCKET_CREATE_CALLS:
                    # socket.socket(...) / socket.create_connection(...)
                    # — the bare Name form (a local helper called
                    # ``socket``) is not a creation site.
                    if isinstance(f, ast.Attribute):
                        creates.append(n.lineno)
                if name == "settimeout":
                    if n.args and isinstance(
                        n.args[0], ast.Constant
                    ) and n.args[0].value is None:
                        findings.append(
                            f"{path}:{n.lineno}: settimeout(None) re-arms "
                            "unbounded blocking socket i/o — arm a real "
                            "deadline (docs/ROBUSTNESS.md)"
                        )
                    else:
                        bounded = True
                if name == "setblocking" and n.args and isinstance(
                    n.args[0], ast.Constant
                ) and n.args[0].value is True:
                    findings.append(
                        f"{path}:{n.lineno}: setblocking(True) re-arms "
                        "unbounded blocking socket i/o — use settimeout "
                        "with a bounded deadline"
                    )
                if any(
                    kw.arg and "timeout" in kw.arg.lower()
                    for kw in n.keywords
                ):
                    bounded = True
            elif isinstance(n, ast.Name) and any(
                m in n.id.lower() for m in _BOUND_MARKERS
            ):
                bounded = True
            elif isinstance(n, ast.Attribute) and any(
                m in n.attr.lower() for m in _BOUND_MARKERS
            ):
                bounded = True
            elif isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Attribute):
                        attr_owned = True
        for n in ast.walk(fn_node):
            if not isinstance(n, ast.Try):
                continue
            cleanup = list(n.finalbody)
            for h in n.handlers:
                cleanup.extend(h.body)
            for c in cleanup:
                for cn in ast.walk(c):
                    if (
                        isinstance(cn, ast.Call)
                        and isinstance(cn.func, ast.Attribute)
                        and cn.func.attr == "close"
                    ):
                        closed_in_handler = True
        if io_lines and not bounded:
            findings.append(
                f"{path}:{io_lines[0]}: unbounded socket i/o: "
                f"'{fn_node.name}' calls recv/connect/accept without a "
                "settimeout/deadline in scope — a cut link becomes a "
                "hang instead of a reconnect verdict"
            )
        if creates and not (closed_in_handler or attr_owned):
            findings.append(
                f"{path}:{creates[0]}: socket created in "
                f"'{fn_node.name}' with no failure-path close() and no "
                "attribute ownership — a mid-construction raise leaks "
                "the fd on every reconnect attempt"
            )
    return findings


# ---------------------------------------------------------------------------
# The registry + driver.
# ---------------------------------------------------------------------------

RuleFn = Callable[[Path, ast.Module], List[str]]

RULES: "OrderedDict[str, RuleFn]" = OrderedDict([
    ("undefined-name", check_undefined_names),
    ("unbounded-wait", check_unbounded_waits),
    ("transport-bounded-io", check_transport_bounded_io),
    ("exception-hygiene", check_exception_hygiene),
    ("library-hygiene", check_library_hygiene),
    ("timeline-coverage", check_worker_timeline_coverage),
    ("health-event-kinds", check_health_event_kinds),
    ("reducer-routing", check_reducer_reduce_routing),
    ("epilogue-f32", check_epilogue_f32_intermediates),
    ("staged-purity", check_staged_purity),
    ("schedule-blocking", check_schedule_stage_blocking),
    ("wire-routing", check_wire_edge_routing),
    ("registry-ownership", check_planner_registry_ownership),
    ("async-blocking", check_async_sender_blocking),
    ("serve-blocking", check_serve_scheduler_blocking),
])


def select_rules(
    only: Optional[List[str]] = None, skip: Optional[List[str]] = None
) -> "OrderedDict[str, RuleFn]":
    unknown = [
        r for r in (list(only or []) + list(skip or [])) if r not in RULES
    ]
    if unknown:
        raise ValueError(
            f"unknown rule(s) {unknown}; known: {', '.join(RULES)}"
        )
    out: "OrderedDict[str, RuleFn]" = OrderedDict()
    for name, fn in RULES.items():
        if only and name not in only:
            continue
        if skip and name in skip:
            continue
        out[name] = fn
    return out


def check_file(
    path: Path,
    rules: Optional["OrderedDict[str, RuleFn]"] = None,
) -> List[str]:
    """All selected per-file rules over one file, via the shared parse
    cache. A file that does not parse yields exactly one syntax-error
    finding (the legacy format) and never aborts the caller's sweep."""
    src = get_source(path)
    if src.tree is None:
        return [f"{path}:{src.error}"]
    if rules is None:
        rules = RULES
    out: List[str] = []
    for fn in rules.values():
        out.extend(fn(path, src.tree))
    return out
