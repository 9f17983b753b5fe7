"""Whole-program analyzer suite (ISSUE 14).

Three layers:

* fixture packages proving each cross-module rule fires exactly where it
  should (synthetic missing-key cache, orphaned memo, lock-order cycle,
  cross-thread unlocked write, stale allowlist entry) and stays quiet on
  the clean twin — including the three acceptance mutations: deleting
  the wire component from a layout-style cache key, detaching one memo
  from the invalidation root, and inverting one lock pair;
* the repo gate: ``run_project`` over ``torch_cgx_tpu/`` is clean and
  fits the wall-clock budget (parse results are cached per mtime, so
  the whole-program passes stay cheap enough for tier-1);
* regressions for the true positives the passes found in the tree
  (ISSUE 14 satellite: the program-cache cascade, the producer-fuse
  orphan, the env components missing from the trace-cache keys).
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from tools import analysis  # noqa: E402
from tools.analysis import caches as caches_pass  # noqa: E402
from tools.analysis import knobs as knobs_pass  # noqa: E402
from tools.analysis import locks as locks_pass  # noqa: E402
from tools.analysis import mempairs as mempairs_pass  # noqa: E402
from tools.analysis.graph import Project, get_source  # noqa: E402


def make_pkg(tmp_path, files, name="fixpkg"):
    root = tmp_path / name
    root.mkdir(exist_ok=True)
    (root / "__init__.py").write_text("")
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return root


# ---------------------------------------------------------------------------
# knob-key: the synthetic missing-key cache.
# ---------------------------------------------------------------------------

_CACHEMOD_TMPL = """\
import os

_CACHE = {{}}


def knob_a():
    return os.environ.get("CGX_FIX_A", "")


def knob_b():
    return os.environ.get("CGX_FIX_B", "")


def _key():
    return {key_expr}


def build(x):
    key = _key()
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    val = x + len(knob_b())
    _CACHE[key] = val
    return val
"""


def _knob_findings(root, key_expr, allowlist=None):
    proj = Project(root)
    surface = knobs_pass.CacheSurface(
        "fix-cache", f"{root.name}.cachemod", "_CACHE", "build"
    )
    return knobs_pass.check(
        proj, surfaces=[surface], allowlist=allowlist or {},
    )


def test_knob_key_flags_missing_build_side_knob(tmp_path):
    root = make_pkg(tmp_path, {
        "cachemod.py": _CACHEMOD_TMPL.format(key_expr='("k", knob_a())'),
    })
    found = _knob_findings(root, None)
    assert len(found) == 1, [f.render() for f in found]
    f = found[0]
    assert f.rule == "knob-key"
    assert "CGX_FIX_B" in f.message
    # names the file and the probe line (the `_CACHE.get` consultation)
    assert f.path.endswith("cachemod.py")
    src = (root / "cachemod.py").read_text().splitlines()
    assert "_CACHE.get" in src[f.line - 1]


def test_knob_key_quiet_when_key_complete(tmp_path):
    root = make_pkg(tmp_path, {
        "cachemod.py": _CACHEMOD_TMPL.format(
            key_expr="(knob_a(), knob_b())"
        ),
    })
    assert _knob_findings(root, None) == []


def test_knob_key_allowlist_and_stale_entry(tmp_path):
    root = make_pkg(tmp_path, {
        "cachemod.py": _CACHEMOD_TMPL.format(key_expr='("k", knob_a())'),
    })
    # live allowlist entry suppresses the finding
    found = _knob_findings(root, None, allowlist={"CGX_FIX_B": "inert"})
    assert [f for f in found if f.rule == "knob-key"] == []
    assert [f for f in found if f.rule == "stale-allowlist"] == []
    # a row for a knob that taints nothing is stale
    found = _knob_findings(
        root, None,
        allowlist={"CGX_FIX_B": "inert", "CGX_GONE": "left over"},
    )
    stale = [f for f in found if f.rule == "stale-allowlist"]
    assert len(stale) == 1 and "CGX_GONE" in stale[0].message
    # a justification is mandatory
    found = _knob_findings(
        root, None, allowlist={"CGX_FIX_B": "  "},
    )
    assert any(
        f.rule == "stale-allowlist" and "no justification" in f.message
        for f in found
    )


def test_stale_allowlist_diagnoses_promoted_knob(tmp_path):
    # Review regression: a knob that still taints the build side but got
    # promoted into the key must be reported as "covered by the key",
    # not the factually-wrong "no longer taints any build side".
    root = make_pkg(tmp_path, {
        "cachemod.py": _CACHEMOD_TMPL.format(
            key_expr="(knob_a(), knob_b())"
        ),
    })
    found = _knob_findings(root, None, allowlist={"CGX_FIX_B": "was inert"})
    assert len(found) == 1 and found[0].rule == "stale-allowlist"
    assert "covered by every surface's cache key" in found[0].message


def test_knob_key_renamed_surface_degrades_loudly(tmp_path):
    # A deleted/renamed cache must not silently disarm the rule.
    root = make_pkg(tmp_path, {
        "cachemod.py": "X = 1\n",
    })
    found = _knob_findings(root, None)
    assert len(found) == 1
    assert "cannot be located" in found[0].message
    # Review regression: with a surface unlocatable, allowlist rows must
    # NOT be reported stale (the missing surface may be what they
    # suppress — staleness is only provable on a full analysis).
    found = _knob_findings(root, None, allowlist={"CGX_ROW": "justified"})
    assert [f for f in found if f.rule == "stale-allowlist"] == []
    assert any("cannot be located" in f.message for f in found)


# The acceptance mutation: a layout-style key assembled from components,
# one of them the wire plane's — deleting it yields exactly one finding.
_LAYOUT_TMPL = """\
import os

from . import wire

_LAYOUT_CACHE = {{}}


def _registry_version():
    return os.environ.get("CGX_FIX_VERSION", "0")


def _resolve(leaf):
    return (leaf, wire.resolve_bits(leaf))


def _layout_key(tree):
    return ({key_components})


def tree_layout(tree):
    key = _layout_key(tree)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    layout = tuple(_resolve(l) for l in tree)
    _LAYOUT_CACHE[key] = layout
    return layout
"""

_WIRE_FIX = """\
import os


def cache_key_component():
    return (os.environ.get("CGX_FIX_WIRE", ""),)


def resolve_bits(leaf):
    return len(os.environ.get("CGX_FIX_WIRE", "")) or len(leaf)
"""


def _layout_fixture_findings(tmp_path, key_components):
    root = make_pkg(tmp_path, {
        "layoutmod.py": _LAYOUT_TMPL.format(key_components=key_components),
        "wire.py": _WIRE_FIX,
    })
    proj = Project(root)
    surface = knobs_pass.CacheSurface(
        "layout-lru", f"{root.name}.layoutmod", "_LAYOUT_CACHE",
        "tree_layout",
    )
    return knobs_pass.check(proj, surfaces=[surface], allowlist={})


def test_layout_key_with_wire_component_is_clean(tmp_path):
    found = _layout_fixture_findings(
        tmp_path,
        "tree, _registry_version(), wire.cache_key_component()",
    )
    assert found == [], [f.render() for f in found]


def test_deleting_wire_component_yields_exactly_one_finding(tmp_path):
    found = _layout_fixture_findings(
        tmp_path, "tree, _registry_version()"
    )
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "knob-key"
    assert "CGX_FIX_WIRE" in found[0].message
    assert found[0].path.endswith("layoutmod.py")


# ---------------------------------------------------------------------------
# orphan-memo: the invalidation-cascade proof.
# ---------------------------------------------------------------------------

_STATE_ATTACHED = """\
_MEMO = {}


def grow(k, v):
    _MEMO[k] = v


def reset_memo():
    _MEMO.clear()
"""

_RESET_ATTACHED = """\
from . import state


def invalidate_trace_caches():
    state.reset_memo()
"""

_RESET_DETACHED = """\
def invalidate_trace_caches():
    pass
"""


def _cascade_findings(tmp_path, files):
    root = make_pkg(tmp_path, files)
    proj = Project(root)
    return caches_pass.check(
        proj, roots=[("reset", "invalidate_trace_caches")]
    )


def test_attached_memo_is_clean(tmp_path):
    assert _cascade_findings(tmp_path, {
        "state.py": _STATE_ATTACHED, "reset.py": _RESET_ATTACHED,
    }) == []


def test_detached_memo_yields_exactly_one_finding(tmp_path):
    found = _cascade_findings(tmp_path, {
        "state.py": _STATE_ATTACHED, "reset.py": _RESET_DETACHED,
    })
    assert len(found) == 1, [f.render() for f in found]
    f = found[0]
    assert f.rule == "orphan-memo" and "_MEMO" in f.message
    assert f.path.endswith("state.py")
    src = Path(f.path).read_text().splitlines()
    assert src[f.line - 1].startswith("_MEMO")


def test_sys_modules_indirection_counts_as_reached(tmp_path):
    # The supervisor's lazy-cascade idiom: resets through
    # sys.modules.get("...") must prove reachability.
    found = _cascade_findings(tmp_path, {
        "state.py": _STATE_ATTACHED,
        "reset.py": (
            "import sys\n\n\n"
            "def invalidate_trace_caches():\n"
            f"    m = sys.modules.get('fixpkg.state')\n"
            "    if m is not None:\n"
            "        m._MEMO.clear()\n"
        ),
    })
    assert found == [], [f.render() for f in found]


def test_reset_hook_registration_counts_as_root(tmp_path):
    found = _cascade_findings(tmp_path, {
        "state.py": (
            "_MEMO = {}\n\n\n"
            "def grow(k, v):\n    _MEMO[k] = v\n\n\n"
            "def _zero():\n    _MEMO.clear()\n\n\n"
            "def register_reset_hook(fn):\n    pass\n\n\n"
            "def install():\n    register_reset_hook(_zero)\n"
        ),
        "reset.py": _RESET_DETACHED,
    })
    assert found == [], [f.render() for f in found]


def test_module_level_reset_hook_registration_counts_as_root(tmp_path):
    # Review regression: the package's real registration idiom is
    # MODULE-level (`edges.register_reset_hook(_reset_all)` runs at
    # import in wire/controller.py) — the root scan must see it.
    found = _cascade_findings(tmp_path, {
        "state.py": (
            "_MEMO = {}\n\n\n"
            "def grow(k, v):\n    _MEMO[k] = v\n\n\n"
            "def _zero():\n    _MEMO.clear()\n\n\n"
            "def register_reset_hook(fn):\n    pass\n\n\n"
            "register_reset_hook(_zero)\n"
        ),
        "reset.py": _RESET_DETACHED,
    })
    assert found == [], [f.render() for f in found]


def test_lru_cache_needs_reachable_cache_clear(tmp_path):
    base = (
        "import functools\n\n\n"
        "@functools.lru_cache(maxsize=32)\n"
        "def classify(x):\n    return x * 2\n"
    )
    found = _cascade_findings(tmp_path, {
        "state.py": base, "reset.py": _RESET_DETACHED,
    })
    assert len(found) == 1 and "classify" in found[0].message
    found = _cascade_findings(tmp_path, {
        "state.py": base,
        "reset.py": (
            "from . import state\n\n\n"
            "def invalidate_trace_caches():\n"
            "    state.classify.cache_clear()\n"
        ),
    })
    assert found == []


def test_constant_lookup_tables_are_not_registries(tmp_path):
    found = _cascade_findings(tmp_path, {
        "state.py": "_TABLE = {'a': 1}\n\n\ndef get(k):\n    return _TABLE[k]\n",
        "reset.py": _RESET_DETACHED,
    })
    assert found == []


def test_local_shadow_assignment_does_not_prove_reset(tmp_path):
    # Review regression: a function-local `_MEMO = ...` in a reachable
    # function must NOT count as resetting the module registry — only a
    # `global`-declared rebind touches module state.
    found = _cascade_findings(tmp_path, {
        "state.py": (
            "_MEMO = {}\n\n\n"
            "def grow(k, v):\n    _MEMO[k] = v\n\n\n"
            "def helper():\n"
            "    _MEMO = {}\n"  # local shadow, not a reset
            "    return _MEMO\n"
        ),
        "reset.py": (
            "from . import state\n\n\n"
            "def invalidate_trace_caches():\n"
            "    state.helper()\n"
        ),
    })
    assert len(found) == 1 and "_MEMO" in found[0].message
    # ... while a global-declared rebind IS a reset
    found = _cascade_findings(tmp_path, {
        "state.py": (
            "_MEMO = {}\n\n\n"
            "def grow(k, v):\n    _MEMO[k] = v\n\n\n"
            "def helper():\n"
            "    global _MEMO\n"
            "    _MEMO = {}\n"
        ),
        "reset.py": (
            "from . import state\n\n\n"
            "def invalidate_trace_caches():\n"
            "    state.helper()\n"
        ),
    })
    assert found == [], [f.render() for f in found]


def test_orphan_memo_pragma_suppresses_with_reason(tmp_path):
    found = _cascade_findings(tmp_path, {
        "state.py": (
            "# cgx-analysis: allow(orphan-memo) — test-scoped memo\n"
            "_MEMO = {}\n\n\n"
            "def grow(k, v):\n    _MEMO[k] = v\n"
        ),
        "reset.py": _RESET_DETACHED,
    })
    assert found == []


# ---------------------------------------------------------------------------
# lock discipline.
# ---------------------------------------------------------------------------


def _lock_findings(tmp_path, text, name="worker.py"):
    root = make_pkg(tmp_path, {name: text})
    proj = Project(root)
    return locks_pass.check(proj, scopes=(str(root),))


def test_lock_order_cycle_yields_exactly_one_finding(tmp_path):
    found = _lock_findings(tmp_path, (
        "import threading\n\n"
        "_A = threading.Lock()\n_B = threading.Lock()\n\n\n"
        "def f1():\n    with _A:\n        with _B:\n            pass\n\n\n"
        "def f2():\n    with _B:\n        with _A:\n            pass\n"
    ))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "lock-order"
    assert "_A" in found[0].message and "_B" in found[0].message


def test_consistent_lock_order_is_clean(tmp_path):
    found = _lock_findings(tmp_path, (
        "import threading\n\n"
        "_A = threading.Lock()\n_B = threading.Lock()\n\n\n"
        "def f1():\n    with _A:\n        with _B:\n            pass\n\n\n"
        "def f2():\n    with _A:\n        with _B:\n            pass\n"
    ))
    assert found == [], [f.render() for f in found]


def test_lock_order_sees_through_called_functions(tmp_path):
    # f2 holds _B and calls helper(), which takes _A: the B->A edge
    # closes the cycle against f1's direct A->B nesting.
    found = _lock_findings(tmp_path, (
        "import threading\n\n"
        "_A = threading.Lock()\n_B = threading.Lock()\n\n\n"
        "def f1():\n    with _A:\n        with _B:\n            pass\n\n\n"
        "def helper():\n    with _A:\n        pass\n\n\n"
        "def f2():\n    with _B:\n        helper()\n"
    ))
    assert any(f.rule == "lock-order" for f in found)


def test_blocking_sleep_under_lock_flagged(tmp_path):
    found = _lock_findings(tmp_path, (
        "import threading\nimport time\n\n"
        "_L = threading.Lock()\n\n\n"
        "def g():\n    with _L:\n        time.sleep(0.1)\n"
    ))
    assert len(found) == 1 and found[0].rule == "lock-blocking"
    assert "sleep" in found[0].message


def test_bounded_result_under_lock_is_clean_unbounded_flagged(tmp_path):
    found = _lock_findings(tmp_path, (
        "import threading\n\n"
        "_L = threading.Lock()\n\n\n"
        "def ok(fut):\n    with _L:\n        return fut.result(timeout=1)\n\n\n"
        "def bad(fut):\n    with _L:\n        return fut.result()\n"
    ))
    assert len(found) == 1 and found[0].rule == "lock-blocking"
    assert ".result()" in found[0].message


def test_lock_blocking_pragma_suppresses(tmp_path):
    found = _lock_findings(tmp_path, (
        "import threading\nimport time\n\n"
        "_L = threading.Lock()\n\n\n"
        "def g():\n    with _L:\n"
        "        # cgx-analysis: allow(lock-blocking) — test fixture\n"
        "        time.sleep(0.1)\n"
    ))
    assert found == []


_RACE_TMPL = """\
import threading


class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.x = 0
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        {write}

    def read(self):
        {read}
"""


def test_cross_thread_unlocked_write_flagged(tmp_path):
    found = _lock_findings(tmp_path, _RACE_TMPL.format(
        write="self.x = 1", read="return self.x",
    ))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "thread-shared-write"
    assert "self.x" in found[0].message or "'self.x'" in found[0].message


def test_cross_thread_write_with_common_lock_is_clean(tmp_path):
    found = _lock_findings(tmp_path, _RACE_TMPL.format(
        write="with self._lock:\n            self.x = 1",
        read="with self._lock:\n            return self.x",
    ))
    assert found == [], [f.render() for f in found]


# The socket transport (PR 20) guards all of its cross-thread state with
# threading.Condition — `with cond:` acquires the condition's underlying
# lock, so the discipline pass must treat a Condition exactly like a
# Lock: a common-Condition write/read pair is clean, dropping the guard
# on the writer side is one thread-shared-write finding.
_COND_TMPL = """\
import threading


class PeerLink:
    def __init__(self):
        self._cond = threading.Condition()
        self.seq = 0
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        {write}

    def read(self):
        {read}
"""


def test_cross_thread_write_under_condition_is_clean(tmp_path):
    found = _lock_findings(tmp_path, _COND_TMPL.format(
        write="with self._cond:\n            self.seq = 1",
        read="with self._cond:\n            return self.seq",
    ))
    assert found == [], [f.render() for f in found]


def test_unlocked_write_beside_condition_flagged(tmp_path):
    # The firing twin: same class, writer skips the Condition the reader
    # holds — exactly the transport.py bug class the sweep caught
    # (last_send_t / resends bumped outside self._cond).
    found = _lock_findings(tmp_path, _COND_TMPL.format(
        write="self.seq = 1",
        read="with self._cond:\n            return self.seq",
    ))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "thread-shared-write"
    assert "self.seq" in found[0].message or "'self.seq'" in found[0].message


def test_inverting_one_lock_pair_is_one_finding(tmp_path):
    # The acceptance mutation: the clean twin passes, the scratch-branch
    # inversion of f2's nesting produces exactly one finding.
    clean = (
        "import threading\n\n"
        "_A = threading.Lock()\n_B = threading.Lock()\n\n\n"
        "def f1():\n    with _A:\n        with _B:\n            pass\n\n\n"
        "def f2():\n    with _A:\n        with _B:\n            pass\n"
    )
    inverted = clean.replace(
        "def f2():\n    with _A:\n        with _B:",
        "def f2():\n    with _B:\n        with _A:",
    )
    assert _lock_findings(tmp_path, clean, name="a.py") == []
    found = _lock_findings(tmp_path, inverted, name="b.py")
    assert len(found) == 1 and found[0].rule == "lock-order"
    assert found[0].path.endswith("b.py")


# ---------------------------------------------------------------------------
# pragmas.
# ---------------------------------------------------------------------------


def test_malformed_pragma_is_a_finding(tmp_path):
    root = make_pkg(tmp_path, {
        "mod.py": "# cgx-analysis: allow(orphan-memo)\nX = {}\n",
    })
    found = analysis.check_pragma_format(Project(root))
    assert len(found) == 1 and found[0].rule == "pragma-format"
    assert found[0].line == 1


def test_wellformed_pragma_variants_parse(tmp_path):
    root = make_pkg(tmp_path, {
        "mod.py": (
            "# cgx-analysis: allow(orphan-memo) — em-dash reason\n"
            "A = {}\n"
            "# cgx-analysis: allow(lock-blocking) -- ascii reason\n"
            "B = {}\n"
        ),
    })
    proj = Project(root)
    assert analysis.check_pragma_format(proj) == []
    assert len(proj.used_pragmas()) == 2


# ---------------------------------------------------------------------------
# parse cache + syntax resilience (the lint.py ride-along).
# ---------------------------------------------------------------------------


def test_syntax_error_reports_file_and_keeps_checking(tmp_path):
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    other = tmp_path / "other.py"
    other.write_text("def g(x):\n    return _undefined_thing(x)\n")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py"), str(tmp_path)],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert proc.returncode == 1
    assert "syntax error" in proc.stdout
    assert "_undefined_thing" in proc.stdout  # the sweep went on


def test_run_project_syntax_finding_keeps_line_contract(tmp_path):
    # Review regression: the broken-file note must render as
    # `path:<lineno>: message`, not `path:1: <lineno>: message`.
    root = make_pkg(tmp_path, {"broken.py": "def f(:\n"})
    found = [f for f in analysis.run_project(root) if f.rule == "syntax"]
    assert len(found) == 1
    f = found[0]
    assert f.line == 1 and f.path.endswith("broken.py")
    assert not f.message.lstrip().startswith("1:")
    assert "syntax error" in f.message


def test_lint_only_scopes_whole_program_passes_too(tmp_path, monkeypatch, capsys):
    # Review regression: `--only undefined-name` must not leak
    # whole-program findings into a scoped bisect, and a pass name in
    # --only selects that pass alone.
    from tools import lint as lint_mod

    pkg = tmp_path / "torch_cgx_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "state.py").write_text(
        "_MEMO = {}\n\n\ndef grow(k, v):\n    _MEMO[k] = v\n"
    )
    monkeypatch.setattr(lint_mod, "_ROOT", tmp_path)
    # full default sweep: the orphan memo fires
    rc = lint_mod.main([])
    out = capsys.readouterr()
    assert rc == 1 and "orphan-memo" in out.out
    assert "finding(s)" in out.err
    # scoped to a per-file rule: the whole-program passes stay out
    rc = lint_mod.main(["--only", "undefined-name"])
    out = capsys.readouterr()
    assert rc == 0, out.out
    # scoped to the pass: it runs alone and still fires
    rc = lint_mod.main(["--only", "orphan-memo"])
    out = capsys.readouterr()
    assert rc == 1 and "orphan-memo" in out.out
    # skipping the pass silences it (knob-key skipped too: the fixture
    # package deliberately lacks the five real cache surfaces, so its
    # cannot-be-located guard fires — loud degradation, by design)
    rc = lint_mod.main(
        ["--skip", "orphan-memo", "--skip", "knob-key",
         "--skip", "stale-allowlist"]
    )
    out = capsys.readouterr()
    assert rc == 0, out.out


def test_default_sweep_reports_syntax_error_once(tmp_path, monkeypatch, capsys):
    # Review regression: on the default sweep a package syntax error is
    # reported by the per-file rules only — the analyzer's duplicate
    # broken-file note is filtered out.
    from tools import lint as lint_mod

    pkg = tmp_path / "torch_cgx_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "broken.py").write_text("def f(:\n")
    monkeypatch.setattr(lint_mod, "_ROOT", tmp_path)
    rc = lint_mod.main([])
    out = capsys.readouterr().out
    assert rc == 1
    assert out.count("broken.py") == 1, out
    assert "syntax error" in out


def test_parse_cache_serves_same_tree_until_mtime_changes(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("X = 1\n")
    first = get_source(p)
    assert get_source(p) is first
    time.sleep(0.01)
    p.write_text("X = 2\n")
    assert get_source(p) is not first


def test_lint_only_skip_rule_selection(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x):\n    return _renamed_away(x)\n")
    base = [sys.executable, str(ROOT / "tools" / "lint.py")]
    r = subprocess.run(base + [str(bad), "--only", "unbounded-wait"],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(base + [str(bad), "--skip", "undefined-name"],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(base + [str(bad)],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 1
    r = subprocess.run(base + [str(bad), "--only", "nope"],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 2
    # Review regression: explicit paths + --only <whole-program pass>
    # would run NOTHING — must fail loudly, never print "files clean".
    r = subprocess.run(base + [str(bad), "--only", "knob-key"],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 2
    assert "default sweep" in r.stderr


# ---------------------------------------------------------------------------
# The repo gate.
# ---------------------------------------------------------------------------


def test_repo_clean():
    """The analyzer runs clean on the tree inside the wall-clock budget
    (< 30 s on the container; in practice ~2 s — parse results are
    cached per mtime and shared across passes)."""
    t0 = time.monotonic()
    findings = analysis.run_project(ROOT / "torch_cgx_tpu")
    elapsed = time.monotonic() - t0
    assert findings == [], "\n".join(f.render() for f in findings)
    assert elapsed < 30.0, f"analyzer blew its tier-1 budget: {elapsed:.1f}s"


def test_repo_pragmas_all_carry_reasons():
    proj = Project(ROOT / "torch_cgx_tpu")
    pragmas = proj.used_pragmas()
    assert pragmas, "the tree documents its deliberate exceptions inline"
    for path, p in pragmas:
        assert p.reason.strip(), f"{path}:{p.line} pragma without reason"


def test_analysis_cli_json_mode():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--json"],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["clean"] is True
    assert payload["count"] == 0
    assert "knob-key" in payload["passes"]
    assert payload["files_checked"] > 50


# ---------------------------------------------------------------------------
# Regressions: the true positives ISSUE 14's passes found in the tree.
# ---------------------------------------------------------------------------


def test_invalidate_layout_cache_cascades_into_program_cache():
    # orphan-memo regression: _PROGRAM_CACHE held compiled executables of
    # the dead world with no invalidation path.
    from torch_cgx_tpu.parallel import allreduce as ar
    from torch_cgx_tpu.parallel import xla_allreduce as xr

    xr._PROGRAM_CACHE[("sentinel",)] = lambda: None
    try:
        ar.invalidate_layout_cache("test cascade")
        assert ("sentinel",) not in xr._PROGRAM_CACHE
        assert len(xr._PROGRAM_CACHE) == 0
    finally:
        xr.program_cache_clear()


def test_supervisor_invalidation_reaches_producer_fuse():
    # orphan-memo regression: the producer-fuse context kept the dead
    # generation's mesh/axis and stashed payloads across a recovery.
    from torch_cgx_tpu.ops import fused_producer as fp
    from torch_cgx_tpu.robustness import supervisor as sup

    fp.configure(object(), ("dp",), divisor=4, active=True)
    fp._STASH[123] = "stale-entry"
    epoch_before = fp._CFG["epoch"]
    try:
        sup.invalidate_trace_caches()
        assert fp._CFG["active"] is False
        assert fp._CFG["mesh"] is None
        assert fp._CFG["epoch"] == epoch_before + 1
        assert fp._STASH == {}
    finally:
        fp.deconfigure()


def test_trace_knob_fingerprint_moves_with_env(monkeypatch):
    # knob-key regression: the train-step build cache ignored the env
    # tier (a CGX_QERR_STATS / bits flip served a stale trace).
    from torch_cgx_tpu import config as cfg

    base = cfg.trace_knob_fingerprint()
    monkeypatch.setenv("CGX_QERR_STATS", "1")
    assert cfg.trace_knob_fingerprint() != base
    monkeypatch.delenv("CGX_QERR_STATS")
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    assert cfg.trace_knob_fingerprint() != base
    monkeypatch.delenv("CGX_COMPRESSION_QUANTIZATION_BITS")
    assert cfg.trace_knob_fingerprint() == base


def test_xla_trace_fingerprint_covers_pr11_kernel_knobs(monkeypatch):
    # knob-key regression: CGX_SRA_ACCUM lowered into the staged program
    # body without re-keying the program LRU.
    from torch_cgx_tpu.parallel import xla_allreduce as xr

    base = xr._trace_env_fingerprint()
    monkeypatch.setenv("CGX_SRA_ACCUM", "int8")
    assert xr._trace_env_fingerprint() != base
    monkeypatch.delenv("CGX_SRA_ACCUM")
    monkeypatch.setenv("CGX_PALLAS_TILE_CHUNKS", "2")
    assert xr._trace_env_fingerprint() != base


# ---------------------------------------------------------------------------
# mem-ledger-pairing: alloc/release hook pairing (ISSUE 18).
# ---------------------------------------------------------------------------


def _mem_findings(tmp_path, files):
    return mempairs_pass.check(Project(make_pkg(tmp_path, files)))


def test_mem_pairing_flags_unpaired_and_nonliteral_sites(tmp_path):
    found = _mem_findings(tmp_path, {
        "pool.py": (
            "from obs import memledger\n\n\n"
            "def grab():\n"
            "    memledger.note_alloc('pool.orphan', 1, nbytes=4096)\n\n\n"
            "def drop():\n"
            "    memledger.note_release('pool.ghost', 1)\n\n\n"
            "def tagged(owner):\n"
            "    memledger.note_alloc(owner, 1)\n"
        ),
    })
    rules = sorted(f.rule for f in found)
    assert rules == ["mem-ledger-pairing"] * 3, [f.render() for f in found]
    msgs = " | ".join(f.message for f in found)
    assert "'pool.orphan'" in msgs and "never released" in msgs
    assert "'pool.ghost'" in msgs and "never allocated" in msgs
    assert "not a string literal" in msgs


def test_mem_pairing_clean_twins(tmp_path):
    # Three legitimate shapes: a label paired across modules, an
    # alloc-only label whose module tears down through reset_ledger,
    # and a pragma'd deliberately one-sided site.
    found = _mem_findings(tmp_path, {
        "writer.py": (
            "from obs import memledger\n\n\n"
            "def grab():\n"
            "    memledger.note_alloc('ring.page', 1)\n"
        ),
        "reaper.py": (
            "from obs import memledger\n\n\n"
            "def reap():\n"
            "    memledger.note_release('ring.page', 1)\n"
        ),
        "cachemod.py": (
            "from obs import memledger\n\n\n"
            "def fill():\n"
            "    memledger.note_alloc('cache.slot', 1)\n\n\n"
            "def invalidate():\n"
            "    memledger.reset_ledger('cachemod invalidate')\n"
        ),
        "bridge.py": (
            "from obs import memledger\n\n\n"
            "def handoff():\n"
            "    # cgx-analysis: allow(mem-ledger-pairing) — released by "
            "the peer package's reaper\n"
            "    memledger.note_alloc('bridge.slab', 1)\n"
        ),
    })
    assert found == [], [f.render() for f in found]


def test_mem_pairing_one_mutation_away_fires(tmp_path):
    # The acceptance mutation: delete the release and the clean twin
    # produces exactly one finding, at the alloc site.
    files = {
        "pool.py": (
            "from obs import memledger\n\n\n"
            "def grab():\n"
            "    memledger.note_alloc('kv.page', 1)\n\n\n"
            "def drop():\n"
            "    memledger.note_release('kv.page', 1)\n"
        ),
    }
    assert _mem_findings(tmp_path, files) == []
    files["pool.py"] = files["pool.py"].replace(
        "    memledger.note_release('kv.page', 1)\n", "    pass\n")
    found = _mem_findings(tmp_path, files)
    assert len(found) == 1 and found[0].rule == "mem-ledger-pairing"
    assert found[0].line == 5 and "'kv.page'" in found[0].message


def test_mem_pairing_ledger_module_and_method_forms(tmp_path):
    # memledger.py itself is exempt (its shims forward parameter
    # labels); direct register_alloc/register_release method calls and
    # a ledger-ish ``.reset()`` receiver participate like the shims.
    found = _mem_findings(tmp_path, {
        "memledger.py": (
            "def note_alloc(owner, n=1, nbytes=0):\n"
            "    _ledger.register_alloc(owner, n, nbytes)\n"
        ),
        "direct.py": (
            "def grab(led):\n"
            "    led.register_alloc('direct.buf', 1)\n\n\n"
            "def settle(led):\n"
            "    led.register_release('direct.buf', 1)\n"
        ),
        "resetter.py": (
            "def fill(mem_ledger):\n"
            "    mem_ledger.register_alloc('reset.paired', 1)\n\n\n"
            "def teardown(mem_ledger):\n"
            "    mem_ledger.reset('teardown')\n"
        ),
    })
    assert found == [], [f.render() for f in found]


def test_mem_pairing_registered_in_default_sweep(tmp_path):
    assert "mem-ledger-pairing" in analysis.WHOLE_PROGRAM_PASSES
    root = make_pkg(tmp_path, {
        "leaky.py": (
            "from obs import memledger\n\n\n"
            "def grab():\n"
            "    memledger.note_alloc('sweep.orphan', 1)\n"
        ),
    })
    found = analysis.run_project(root, passes=["mem-ledger-pairing"])
    assert [f.rule for f in found] == ["mem-ledger-pairing"]
