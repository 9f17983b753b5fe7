"""The serving plane's four layers (docs/SERVING.md, "The pieces"):
``adapter.py`` under the adapters (``gpt2.py``, ``latent.py``, ``hybrid.py``,
``window.py``, ``loop.py``, ``block.py``) and under ``programs.py``, which is under ``scheduler.py``.
Imports point one way, every server is an ``Adapter`` with the defaults the
copies it lost had, the names the benchmark reaches into the scheduler for
are where it looks, the state ``programs.fresh_state`` lays out is the one
the scheduler built itself before the split, and a cached program keeps no
model alive.

Tiny sizes, the geometries of the adapters' own test files; traced or
parsed, nothing but the last test runs a program.
"""

from __future__ import annotations

import ast
import dataclasses
import gc
import hashlib
import sys
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import weights as gpt2_weights  # noqa: E402
from torch_cgx_tpu.models.gpt2 import GPT2Config  # noqa: E402
from torch_cgx_tpu.ops import paged_kv  # noqa: E402
from torch_cgx_tpu.serving import adapter as adapter_mod  # noqa: E402
from torch_cgx_tpu.serving import programs as programs_mod  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving.gpt2 import GPT2Server  # noqa: E402
from torch_cgx_tpu.serving.hybrid import (  # noqa: E402
    HybridGDNServer,
    HybridLatentMoEServer,
    HybridSSMServer,
)
from torch_cgx_tpu.serving.latent import LatentMoEServer  # noqa: E402
from torch_cgx_tpu.serving.loop import LoopServer  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving.window import (  # noqa: E402
    AfmoeServer,
    WindowMoEServer,
)
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

import test_afmoe_serving as afmoe  # noqa: E402
import test_hybrid_serving as granite  # noqa: E402
import test_latent_serving as latent  # noqa: E402
import test_ling_hybrid_serving as ling  # noqa: E402
import test_loop_serving as loop  # noqa: E402
import test_olmo_hybrid_serving as olmo  # noqa: E402
import test_window_moe_serving as window  # noqa: E402

SERVING = Path(adapter_mod.__file__).parent
ADAPTERS = ("gpt2", "latent", "hybrid", "window", "loop", "block")
GPT2_HF = dict(vocab_size=512, n_layer=2, n_head=4, n_embd=64,
               n_positions=128, init={})
GPT2_CFG = GPT2Config(vocab_size=512, n_layer=2, n_head=4, d_model=64,
                      max_seq=128)
# kind -> (class, model config, serve config): what each adapter's own test
# file serves.
SERVERS = {
    "gpt2": (GPT2Server, lambda: GPT2_CFG, granite._serve),
    "mla_moe": (LatentMoEServer, latent._cfg, latent._serve),
    "hybrid_ssm": (HybridSSMServer, granite._cfg, granite._serve),
    "hybrid_gdn": (HybridGDNServer, olmo._cfg, olmo._serve),
    "hybrid_kda_mla": (HybridLatentMoEServer, ling._cfg, ling._serve),
    "window_moe": (WindowMoEServer, window._cfg, window._serve),
    "afmoe": (AfmoeServer, afmoe._cfg, afmoe._serve),
    "loop": (LoopServer, loop._cfg, loop._serve),
}
HYBRIDS = ("hybrid_ssm", "hybrid_gdn", "hybrid_kda_mla")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("CGX_KV_BITS", "8")
    edges.clear_edges()
    yield
    edges.clear_edges()


def _server(kind, params=None):
    """The adapter of ``kind`` over no model: geometry alone."""
    cls, cfg, serve = SERVERS[kind]
    return cls(cfg(), {} if params is None else params, serve())


def _sibling_imports(module):
    """``[(sibling module, the names taken from it, whether the import
    stands at module top)]`` of ``serving/<module>.py``."""
    tree = ast.parse((SERVING / f"{module}.py").read_text())
    top = {id(node) for node in tree.body}
    found = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ImportFrom) and node.level == 1):
            continue
        names = [alias.name for alias in node.names]
        if node.module is None:  # from . import a, b
            found += [(name, [], id(node) in top) for name in names]
        else:
            found.append((node.module, names, id(node) in top))
    return found


@pytest.mark.parametrize("module", [
    "adapter", *ADAPTERS, "programs", "scheduler", "prefill"])
def test_imports_point_one_way(module):
    found = _sibling_imports(module)
    siblings = {name for name, _, _ in found}
    assert all(at_top for _, _, at_top in found), found
    if module == "adapter":
        assert sorted((name, names) for name, names, _ in found) == [
            ("kv_cache", ["resolve_kv_config"]),
            ("transport", ["DEFAULT_SHIP_DEPTH"]),
        ]
    elif module in (*ADAPTERS, "programs"):
        assert siblings == {"adapter"}
    elif module == "scheduler":
        assert "programs" in siblings
        assert not siblings & {"prefill", *ADAPTERS}
    else:  # the prefill worker sits on top of the scheduler
        assert siblings == {"transport", "programs", "scheduler"}


@pytest.mark.parametrize("kind", list(SERVERS))
def test_adapter_defaults(kind):
    server = _server(kind)
    cfg, serve = server.cfg, server.serve
    layers = range(server.n_layer)
    assert isinstance(server, adapter_mod.Adapter) and server.kind == kind
    assert server.n_layer == cfg.n_layer
    assert [server.layer_name(l) for l in layers] == [
        f"layer_{l}" for l in layers]
    assert server.geometry == tuple(
        (f.name, str(getattr(cfg, f.name))) for f in dataclasses.fields(cfg))
    hash(server.geometry)
    assert server.kv_bytes_per_token() == cfg.kv_bytes_per_token()
    assert [server.page_window(l) for l in layers] == (
        list(cfg.windows) if kind in ("window_moe", "afmoe")
        else [0] * cfg.n_layer)
    if kind in HYBRIDS:
        recurrent = [l for l in layers if l not in cfg.attention_layers]
        assert recurrent and all(server.state_streams(l) for l in recurrent)
        assert all(server.state_streams(l) == ()
                   for l in cfg.attention_layers)
        assert server.state_bytes_per_lane() == cfg.state_bytes_per_lane()
    else:
        assert all(server.state_streams(l) == () for l in layers)
        assert server.state_bytes_per_lane() == 0
    counts = {"gpt2": False, "hybrid_ssm": False, "hybrid_gdn": False}
    assert bool(server.step_counters) == counts.get(kind, True)
    tree = {"a": "tree"}
    other = server.with_params(tree)
    assert type(other) is type(server)
    assert other.p is tree and other.serve is serve and other.cfg is cfg
    assert other.geometry == server.geometry
    if kind in HYBRIDS:
        half = type(server)(cfg, {}, serve, state_dtype=jnp.bfloat16)
        assert half.with_params(tree).state_dtype == jnp.bfloat16
        assert (half.state_bytes_per_lane()
                == cfg.state_bytes_per_lane() // 2)


def test_benchmark_seams_hold(monkeypatch):
    """What ``benchmark/`` reaches for: ``scheduler._build_programs`` is the
    name a cache miss calls, the namespace it returns takes another
    ``decode_step``, and the scheduler's ``_prog`` has the stream tables the
    drivers read."""
    built = []

    def altered(state, params):
        raise AssertionError("never run here")

    def build(server):
        prog = programs_mod.build(server)
        prog.decode_step = altered
        built.append(prog)
        return prog

    sched_mod.invalidate_decode_cache("test")
    monkeypatch.setattr(sched_mod, "_build_programs", build)
    try:
        sched = ContinuousBatchScheduler(_server("gpt2"))
        assert built == [sched._prog]
        assert sched._prog.decode_step is altered
        for table in ("streams", "names", "state_names", "specs", "windows"):
            assert len(getattr(sched._prog, table)) == (
                0 if table == "state_names" else 2)
        assert sched._state["pools"][1]["v"][0].shape[0] == (
            sched.server.serve.max_pages + 1)
        # the same geometry again is a hit: nothing is built
        assert ContinuousBatchScheduler(_server("gpt2"))._prog is built[0]
        assert len(built) == 1
    finally:
        sched_mod.invalidate_decode_cache("test")


# The first 16 hex digits of the SHA-256 of the state's tree structure and
# of its leaves' ``(shape, dtype)``, and the number of leaves, as
# ``ContinuousBatchScheduler._fresh_state`` built them at the parent of the
# PR that moved the construction to ``programs.fresh_state`` (PR 44), at
# 8-bit pages and at raw ones. A PR that changes the state's layout on
# purpose reads the new values off this test's failure. (PR 46 did, for the
# 8-bit leaves: a quantized pool's meta is ``(pages, 2, buckets)``, where the
# parent's was ``(pages, buckets, 2)``; the structure, the count and the raw
# pools are the parent's, and so are the leaves of the two geometries here
# whose pages are two buckets.)
PARENT_STATE = {
    "gpt2": {"8": ("5b9dad382d4ed71c", "9950256bbc392522", 18),
             "0": ("67e35581ee0cc71b", "1510cfbeead82042", 14)},
    "mla_moe": {"8": ("020ed118abbfcd76", "f1520cc4a8e25931", 24),
                "0": ("2ec0ae25e92fab99", "915b96f138911ac3", 18)},
    "hybrid_ssm": {"8": ("2c88912c733a2545", "6c99d1f0a21e3886", 24),
                   "0": ("ba44336ef65a826e", "4b4a5ab248401e47", 20)},
    "hybrid_gdn": {"8": ("54a0f16dd737b3cd", "3cdee69f1c41e847", 24),
                   "0": ("af707e80a7f9164f", "7bfd3a6d01cddf41", 20)},
    "hybrid_kda_mla": {"8": ("759b4ac5f883dd18", "a357a271a02115f0", 24),
                       "0": ("123b5cc3f6ed322f", "b54095ffdec82137", 22)},
    "window_moe": {"8": ("9463bd2b906d9e9b", "64e2b799c23c01b4", 55),
                   "0": ("2f58a4980b47dff1", "19b0adb4db25b31e", 39)},
    # No parent: as PR 45 first built it (five layers, four of them rings).
    "afmoe": {"8": ("8b7c0e9004b7cc2e", "977e0ea45e30c2b2", 37),
              "0": ("ac233ee66a5ea676", "11b35486f9f7b485", 27)},
    # No parent: as PR 52 first built it (two layers, three passes: the
    # tree is GPT-2's, the pools' and the tails' leaves carry the passes).
    "loop": {"8": ("5b9dad382d4ed71c", "3869820bd16b3b0f", 18),
             "0": ("67e35581ee0cc71b", "d46c8a7b5634a377", 14)},
}


def _sha(x) -> str:
    return hashlib.sha256(str(x).encode()).hexdigest()[:16]


@pytest.mark.parametrize("kind", list(SERVERS))
def test_fresh_state_is_the_parents_tree(kind, monkeypatch):
    got = {}
    for bits in PARENT_STATE[kind]:
        monkeypatch.setenv("CGX_KV_BITS", bits)
        server = _server(kind)
        state = programs_mod.fresh_state(programs_mod.build(server),
                                         server.serve)
        leaves = [(tuple(a.shape), str(a.dtype))
                  for a in jax.tree_util.tree_leaves(state)]
        got[bits] = (_sha(jax.tree_util.tree_structure(state)),
                     _sha(leaves), len(leaves))
    assert got == PARENT_STATE[kind]


def test_a_cached_program_holds_no_model():
    """The LRU keeps geometry alone: with the scheduler and the server
    gone, the model's leaves are freed while the entry stays, and another
    server of the same geometry is handed that entry and serves the same
    tokens from it."""
    def serve():
        server = GPT2Server(GPT2_CFG, gpt2_weights.make_params(GPT2_HF, 1),
                            granite._serve())
        sched = ContinuousBatchScheduler(server)
        req = Request(id="r", tokens=granite._prompt(21), max_new_tokens=6)
        sched.submit(req)
        assert sched.run(deadline_s=300.0)
        leaf = weakref.ref(server.p["wte"]["embedding"])
        return req.output, sched._prog, leaf

    sched_mod.invalidate_decode_cache("test")
    tokens, prog, leaf = serve()
    gc.collect()
    assert leaf() is None
    assert list(sched_mod._PROGRAM_CACHE.values()) == [prog]
    again, prog_again, _ = serve()
    assert prog_again is prog and len(sched_mod._PROGRAM_CACHE) == 1
    assert again == tokens and len(tokens) == 6


# ---------------------------------------------------------------------------
# Which unpack each adapter's read of its page tables asks the kernel for
# (ISSUE 53): the five adapters whose attention is ``adapter.attend_paged``
# ask for the byte unpack, ring and tables alike; the three that call
# ``adapter.layer_cache_rows`` themselves keep the plane loop, and their
# decode step is the parent's.
# ---------------------------------------------------------------------------

ATTEND_PAGED = ("hybrid_ssm", "hybrid_gdn", "window_moe", "afmoe", "loop")


def _kernel_server(kind):
    """The adapter of ``kind`` over abstract weights at the smallest
    geometry whose pages the kernel fetches by id, as on the chip: rows of
    128 values (256 latents) in buckets of 128, a page one whole chunk.
    Returns ``(server, table reads a step, ring reads a step)``."""
    serve = adapter_mod.ServeConfig(page_tokens=32, max_batch=2, max_pages=12,
                                    max_seq=128, ship_depth=2)

    def abstract(weights, hf):
        return jax.eval_shape(lambda: weights.make_params(hf, 1))

    if kind == "gpt2":
        hf = dict(GPT2_HF, n_head=2, n_embd=128)
        cfg = dataclasses.replace(GPT2_CFG, n_head=2, d_model=128)
        return GPT2Server(cfg, abstract(gpt2_weights, hf), serve), 4, 0
    if kind == "mla_moe":
        server = latent._guard_server(
            abstract(latent.weights, latent.GUARD_HF))
        return server, server.cfg.n_layer, 0  # ``c``; ``kr`` gathers
    if kind == "hybrid_kda_mla":
        return ling._guard_server(
            abstract(ling.weights, ling.GUARD_HF)), 1, 0
    if kind == "hybrid_ssm":
        hf = dict(granite.HF, hidden_size=256, num_attention_heads=4)
        return HybridSSMServer(
            granite.HybridConfig.from_hf(hf, dtype=jnp.float32),
            abstract(granite.weights, hf), serve), 4, 0
    if kind == "hybrid_gdn":
        hf = dict(olmo.HF, hidden_size=128)
        return HybridGDNServer(
            olmo.OlmoHybridConfig.from_hf(hf, dtype=jnp.float32,
                                          chunk=olmo.PAGE),
            abstract(olmo.weights, hf), serve), 4, 0
    if kind == "loop":  # the passes are a scan: a layer's read traces once
        hf = dict(loop.HF, head_dim=32)
        return LoopServer(loop._cfg(hf), abstract(loop.weights, hf),
                          serve), 4, 0
    mod, cls, config, rings, tables = {
        "window_moe": (window, WindowMoEServer, window.WindowMoeConfig, 6, 2),
        "afmoe": (afmoe, AfmoeServer, afmoe.AfmoeConfig, 4, 1),
    }[kind]
    hf = dict(mod.HF, head_dim=64)
    return cls(config.from_hf(hf, dtype=jnp.float32, q_block=16),
               abstract(mod.weights, hf), serve), 2 * tables, 2 * rings


# The first 16 hex digits of the SHA-256 of ``decode_step``'s jaxpr as text
# at :func:`_kernel_server`'s geometry on the kernel (interpreted), at 8-
# and at 4-bit pages, computed on PR 53's parent (ebbaf2a, its ``git
# archive``): every table's read on the plane loop, the rings' asked for
# bytes since PR 51.
PARENT_KERNEL_STEP = {
    "gpt2": {"8": "579193f0068eb78b", "4": "2f21e17dfa565a4f"},
    "mla_moe": {"8": "a8b7eaa21f65575d", "4": "cbe0c91f0eebf6f4"},
    "hybrid_ssm": {"8": "646de725b4584808", "4": "14e8daa87c6cfa49"},
    "hybrid_gdn": {"8": "f80db0efdf8fa8ed", "4": "86809c5a33e73eea"},
    "hybrid_kda_mla": {"8": "9ac1be021363005b", "4": "7725f9fb8e843115"},
    "window_moe": {"8": "665d439d91f618b1", "4": "093cbf73ab4f7310"},
    "afmoe": {"8": "adb419342ce0b89b", "4": "37805c9e828029b2"},
    "loop": {"8": "fb036e33cbfc8bfc", "4": "e1fa866ac55d0b76"},
}
# ... and of the five adapters' whose tables' read ISSUE 53 moved to the byte
# unpack, at 8 bits, as PR 53 left them (no parent: the next PR to change one
# on purpose reads the new value off this test's failure).
PR53_KERNEL_STEP = {
    "hybrid_ssm": "53aa69a9e6e0fc2e", "hybrid_gdn": "6702f0db8699dd68",
    "window_moe": "6ffc4d17d7628139", "afmoe": "cc99593cea862e20",
    "loop": "b08a792abdfb5822",
}


def _kernel_step(server):
    """``(sha of the traced decode_step, what its reads noted)``; a program
    of its own a trace (``jit`` caches by function)."""
    prog = programs_mod.build(server)
    state = jax.eval_shape(
        lambda: programs_mod.fresh_state(prog, server.serve))
    metrics.reset()
    text = str(jax.make_jaxpr(prog.decode_step)(server.p, state))
    site = "cgx.codec.lowering.dequantize_pages."
    return _sha(text), {name[len(site):]: int(count) for name, count
                        in metrics.snapshot(site).items()}


@pytest.mark.parametrize("bits", ["8", "4"])
@pytest.mark.parametrize("kind", list(SERVERS))
def test_attend_paged_asks_for_the_byte_unpack_and_an_adapters_own_read_does_not(
        kind, bits, monkeypatch):
    """Traced on the kernel, every read of a page table in the decode step
    of the five ``attend_paged`` adapters (``LoopServer``'s with
    ``at_pass``) notes ``dequantize_pages.unpack.bytes`` beside its
    lowering, two a global layer, and the step with the tables' ask alone
    put back is the parent's program; ``GPT2Server``, ``LatentMoEServer``
    and ``HybridLatentMoEServer`` note ``.unpack.planes`` and trace the
    parent's program as they stand. At 4 bits the kernel does not honour
    the ask: every adapter notes ``.planes`` and keeps the parent's
    program."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "128")
    monkeypatch.setenv("CGX_KV_BITS", bits)
    server, tables, rings = _kernel_server(kind)
    asks = kind in ATTEND_PAGED
    taken = "bytes" if asks and bits == "8" else "planes"
    sha, noted = _kernel_step(server)
    gathers = noted.pop("xla_gather", 0)  # a latent cache's rotated key
    assert gathers == (tables if kind in ("mla_moe", "hybrid_kda_mla") else 0)
    ring = {"window.pallas_paged.meta_planes": rings,
            f"window.unpack.{taken}": rings} if rings else {}
    assert noted == {"pallas_paged.meta_planes": tables,
                     f"unpack.{taken}": tables, **ring}
    parent = PARENT_KERNEL_STEP[kind][bits]
    if taken == "planes":
        assert sha == parent
        return
    assert sha == PR53_KERNEL_STEP[kind] != parent
    read = paged_kv.gather_dequant_pages

    def the_parents_ask(*args, window=False, unpack=None, **kw):
        return read(*args, window=window, **kw,
                    unpack="bytes" if window else "planes")

    monkeypatch.setattr(paged_kv, "gather_dequant_pages", the_parents_ask)
    assert _kernel_step(server)[0] == parent
