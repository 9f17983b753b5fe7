"""Producer-fused gradient quantization: the backward matmul emits the
layer's SRA stage-1 wire payload directly.

Every compressed dp_grad used to pay an HBM round trip the codec cannot
see past: the backward matmul writes the f32 gradient to HBM, and the
allreduce's quantize kernel reads all of it back just to shrink it to
``bits/32`` of the footprint. EQuARX (arxiv 2506.17615) makes the case
that an XLA-native quantized collective wins its ~2x precisely by
eliminating that producer->wire round trip; "Fused
Computation-Collective Operations" (arxiv 2305.06942) is the fusion
blueprint. This module implements it for the dominant gradient producer
— the dense-layer matmul:

* :func:`matmul` / :class:`~torch_cgx_tpu.models.layers.CgxDense` wrap
  the forward contraction in a ``custom_vjp``. The backward rule still
  returns the exact f32 cotangent (so plain ``jax.grad`` users see
  nothing different), but it ALSO stages the layer's wire payload — the
  quantized ``(ws, chunk)`` SRA stage-1 rows of ``grad / ws`` — plus the
  raw own-chunk row (computed by a 1/ws-sized matmul against the
  device's own chunk rows), and stashes both in a trace-scoped side
  table keyed by cotangent identity.
* ``allreduce_tree`` (parallel/allreduce.py) checks the stash for each
  standalone fused group: on a hit, the staged SRA consumes the
  pre-quantized payload (``reducers._sra_exchange(pre=...)``) and the
  raw own row directly. The f32 cotangent and its producing matmul are
  then DEAD CODE — XLA's DCE removes them, so the staged program
  contains ONE fused matmul+quantize kernel (or the compose pair) and
  the full-size f32 gradient never exists in HBM.
* On any mismatch (config drift, topology route, schedule table, guard
  or EF transforms rewriting the gradient) the stash entry is simply
  not consumed — the plain path runs bit-identically and the fallback
  is counted (``cgx.codec.producer_fallbacks``), never silent.

Two producer lowerings emit the payload:

* **Fused Pallas kernel** (``_matmul_quantize_impl``): grid over
  (row-block, k-block) with an f32 VMEM accumulator; the final k step
  divides by the averaging divisor and runs the SAME
  ``_requantize_block`` body as the flat quantize kernel, writing only
  packed words + meta. Engages when the geometry aligns (see
  :func:`_kernel_geometry`) on TPU (``CGX_PRODUCER_KERNEL=on`` forces it
  in interpret mode for the byte suite).
* **Compose fallback**: the plain cotangent matmul followed by the
  dispatcher's row quantize — byte-identical to what the allreduce
  would have produced from the same values, still saving the
  allreduce-side quantize pass via consumption.

Because the producer's matmul accumulation order may differ from the
XLA-native cotangent matmul by float association, producer-fused wire
bytes are bit-equal to the staged quantize-after-grad exactly when the
gradient values are (decode-exact data pins this in the tests); on
general data the parity is the quantization envelope — the contract the
``producer_fused_vs_staged`` bench record pre-flights.

``CGX_PRODUCER_FUSE`` gates everything (auto = TPU only): with the knob
off, :func:`matmul` lowers to the bare ``lax.dot_general`` — the staged
program is bit-identical to the unwrapped model, jaxpr-pinned like
``CGX_WIRE``/``CGX_SCHEDULE``.

Deterministic rounding only: stage-1 stochastic keys derive from the
fused group's fold index inside ``allreduce_tree``, which the producer
cannot know at backward time — stochastic configs fall back (counted).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import config as cfg_mod
from ..config import CompressionConfig
from ..utils import env as _env
from ..utils.logging import metrics
from . import codec, codec_pallas
from .dispatch import _on_tpu

CHUNK_BUCKETS = codec.CHUNK_BUCKETS


def engaged() -> bool:
    """Whether the producer-fuse plane may engage under the current
    mode/backend (the CGX_WIRE discipline: auto = real TPU only, so every
    CPU/CI path stays bit-identical with the knob unset)."""
    mode = cfg_mod.producer_fuse()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return _on_tpu()


def _kernel_mode() -> str:
    """CGX_PRODUCER_KERNEL: lowering of the payload producer — "auto"
    (fused Pallas matmul+quantize on TPU, compose elsewhere), "on"
    (force the kernel, interpret mode included — the byte-suite knob),
    "off" (always compose)."""
    raw = (_env.get_optional_str_env("CGX_PRODUCER_KERNEL") or "auto").lower()
    if raw not in ("auto", "on", "off"):
        raise ValueError(
            f"CGX_PRODUCER_KERNEL must be auto|on|off, got {raw!r}"
        )
    return raw


def cache_key_component() -> Tuple:
    """The producer-fuse component of trace-cache keys
    (``make_train_step`` build cache, like the schedule/wire
    components): a knob flip must retrace, never serve a program from
    another producer era."""
    return (cfg_mod.producer_fuse(), _kernel_mode())


# ---------------------------------------------------------------------------
# Trace-scoped configuration + stash.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Produced:
    """One layer's staged wire payload, waiting for the allreduce to
    claim it. ``cotangent`` keeps a strong reference to the exact tracer
    the backward returned — consumption matches on identity, so any
    transformation of the gradient between backward and allreduce
    (guard zeroing, EF residuals, optax chains) makes the entry
    unclaimable and the plain path run instead."""

    cotangent: Any
    q: Optional[codec.QTensor]  # monolithic (ws, chunk) stage-1 rows
    q_blocks: Optional[Tuple[codec.QTensor, ...]]  # per-schedule-block rows
    table: Optional[Tuple[Tuple[int, int], ...]]  # the block plan q_blocks used
    raw_row: jax.Array  # this device's raw own chunk (flat, divided)
    cc: CompressionConfig
    ws: int
    n: int
    divisor: int
    epoch: int
    name: str
    consumed: bool = False


_CFG: Dict[str, Any] = {
    "mesh": None, "axis": None, "divisor": 1, "active": False, "epoch": 0,
}
_STASH: Dict[int, Produced] = {}


def configure(
    mesh, axes, *, divisor: int = 1, active: bool = True
) -> None:
    """Install the sync context the producer needs at backward-trace time
    (``make_train_step`` calls this; standalone ``gradient_sync`` users
    may too). Only a single plain dp axis is supported — hierarchical
    two-axis sync and the bridge plane keep the unfused path."""
    axes = tuple(axes)
    _CFG["mesh"] = mesh
    _CFG["axis"] = axes[0] if len(axes) == 1 else None
    _CFG["divisor"] = int(divisor)
    _CFG["active"] = bool(active) and len(axes) == 1

def deconfigure() -> None:
    _CFG.update(mesh=None, axis=None, divisor=1, active=False)
    _STASH.clear()


def begin_step() -> None:
    """Open a fresh stash epoch (called at the top of each traced step):
    entries from an earlier trace can never be claimed by a later one."""
    _CFG["epoch"] += 1
    _STASH.clear()


def invalidate(reason: str = "reconfigure") -> None:
    """Recovery invalidation entry point
    (``supervisor.invalidate_trace_caches``): the configured mesh/axis
    belong to the dead generation and any stashed entry holds tracers of
    a retired trace — deactivate, open a fresh epoch and drop the stash,
    so post-recovery builds reconfigure from the survivor mesh instead
    of staging payloads against the evicted world. ISSUE 14's
    invalidation-cascade pass caught this module as the orphan memo the
    supervisor's ladder never reached."""
    deconfigure()
    begin_step()  # fresh epoch: pre-recovery entries can never claim
    metrics.add("cgx.codec.producer_invalidations")
    from ..utils.logging import get_logger

    get_logger().info("producer-fuse state invalidated (%s)", reason)


def stash_size() -> int:
    return len(_STASH)


def lookup(leaf) -> Optional[Produced]:
    """The stash entry whose cotangent IS this leaf (identity), current
    epoch only. Stale-epoch entries are dropped on sight — they hold
    tracers of a completed trace and can never be claimed."""
    ent = _STASH.get(id(leaf))
    if ent is None or ent.cotangent is not leaf:
        return None
    if ent.epoch != _CFG["epoch"]:
        _STASH.pop(id(leaf), None)
        return None
    return ent


def claim(leaf) -> None:
    """Mark a consumed entry so a second group can never double-spend it."""
    _STASH.pop(id(leaf), None)


def drain() -> None:
    """Drop every remaining entry — ``allreduce_tree`` calls this after
    its group sweep so unclaimed (fallback) payloads don't pin the
    trace's tracers until the next step begins. A later allreduce of the
    same tree in the same trace simply re-quantizes normally."""
    _STASH.clear()


# ---------------------------------------------------------------------------
# The wrapped contraction.
# ---------------------------------------------------------------------------


def _plain(x, w, precision):
    """The exact nn.Dense contraction: contract x's last dim with w's
    first (lax.dot_general, the op flax stages)."""
    return lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())), precision=precision
    )


def matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    name: str,
    compute_dtype=None,
    precision=None,
) -> jax.Array:
    """``x @ w`` whose backward emits the producer-fused wire payload for
    ``dw`` when the plane is engaged and the layer resolves compressible.

    ``compute_dtype``: the cast-for-compute dtype (flax's
    ``promote_dtype`` role) — folded INSIDE the custom_vjp so the
    cotangent this function returns is the f32 param-dtype gradient leaf
    the allreduce will see (identity-matchable). With the knob off this
    lowers to the bare cast + ``lax.dot_general`` — bit-identical jaxpr
    to an unwrapped dense layer."""
    cd = compute_dtype
    if not engaged() or not _CFG["active"]:
        w_c = w.astype(cd) if cd is not None and w.dtype != cd else w
        return _plain(x, w_c, precision)

    @jax.custom_vjp
    def mm(x, w):
        w_c = w.astype(cd) if cd is not None and w.dtype != cd else w
        return _plain(x, w_c, precision)

    def fwd(x, w):
        w_c = w.astype(cd) if cd is not None and w.dtype != cd else w
        return _plain(x, w_c, precision), (x, w)

    def bwd(res, g):
        x, w = res
        w_c = w.astype(cd) if cd is not None and w.dtype != cd else w
        # dx = g . w^T (contract g's last dim with w's output dim).
        dx = lax.dot_general(
            g, w_c, (((g.ndim - 1,), (1,)), ((), ())), precision=precision
        ).astype(x.dtype)
        # dw = x^T . g (contract every batch dim).
        bdims = tuple(range(x.ndim - 1))
        dw = lax.dot_general(
            x, g, ((bdims, bdims), ((), ())), precision=precision
        ).astype(w.dtype)
        _maybe_stash(name, w, dw, x, g)
        return dx, dw

    mm.defvjp(fwd, bwd)
    return mm(x, w)


# ---------------------------------------------------------------------------
# Payload staging (backward-trace time).
# ---------------------------------------------------------------------------


def _eligible_cc(name: str, w) -> Optional[CompressionConfig]:
    """The layer's resolved compression config, or None when the leaf
    would not be compressed (or is stochastic — the producer cannot
    reproduce the fused group's fold-index key derivation)."""
    from ..parallel import allreduce as ar_mod

    proxy = jax.ShapeDtypeStruct(w.shape, w.dtype)
    cc = ar_mod.resolve_leaf_config(name, proxy)
    if not cc.enabled or cc.stochastic:
        return None
    return cc


def _fallback(reason: str) -> None:
    metrics.add("cgx.codec.producer_fallbacks")
    metrics.add(f"cgx.codec.producer_fallback_{reason}")


def _axis_bound(axis: str) -> bool:
    """Whether the sync axis is bound at this trace point — a grad taken
    outside the configured shard_map must take the plain cotangent, never
    crash on ``axis_index``. The probe is the narrowest possible catch:
    only the unbound-axis NameError from ``axis_index`` itself, so a real
    NameError bug anywhere else in the staging path still surfaces."""
    try:
        lax.axis_index(axis)
        return True
    except NameError:
        return False


def _maybe_stash(name: str, w, dw, x, g) -> None:
    """Stage the wire payload for this layer's gradient, when everything
    lines up; otherwise count the fallback and stage nothing (the plain
    path is always staged anyway — unclaimed work is DCE'd)."""
    from ..parallel import reducers, schedule as sched_mod
    from ..parallel import topology as topo_router

    if not _CFG["active"]:
        return
    mesh, axis = _CFG["mesh"], _CFG["axis"]
    if mesh is None or axis is None:
        return _fallback("unconfigured")
    if not _axis_bound(axis):
        return _fallback("no_axis")
    ws = mesh.shape[axis]
    if ws <= 1:
        return _fallback("ws1")
    cc = _eligible_cc(name, w)
    if cc is None:
        return _fallback("config")
    if cfg_mod.dummy_compression() or cfg_mod.fake_ratio() is not None:
        return _fallback("debug_mode")
    n = int(np.prod(w.shape))
    if n < cfg_mod.standalone_layer_elems():
        return _fallback("fused_group")  # only standalone groups consumable
    if n > cfg_mod.fusion_threshold_elems(4):
        return _fallback("multi_slice")
    chunk, total = reducers.chunk_layout(n, ws)
    if chunk * ws != n or w.shape[0] % ws:
        return _fallback("layout")  # padding/row-split would misalign
    topo = cfg_mod.topology_from_env()
    from ..parallel import mesh as mesh_mod

    red = (
        topo.intra_reduction
        if axis != mesh_mod.CROSS_AXIS
        else topo.cross_reduction
    )
    if red != cfg_mod.REDUCTION_SRA:
        return _fallback("reduction")
    decision = topo_router.route(mesh, (axis,))
    # Step-plan depth (CGX_PLANNER): the consumer's allreduce will chunk
    # this slice at the PLANNER'S depth when engaged, so the producer
    # must quantize its blocks against the same table or the pre-staged
    # payload falls back on every step (pre.table == sched.table check).
    # decide_slice gates engagement itself; bits may differ under an
    # avg-bits budget — the consumer's cc-identity check handles that
    # (counted fallback), so only the depth is adopted here.
    from ..parallel import planner as planner_mod

    dec = planner_mod.decide_slice(n, ws, cc, red, route=decision.route)
    sched = sched_mod.compiled_schedule(
        n, ws, cc, reduction=red, dtype=np.dtype(jnp.float32).str,
        route=decision.route,
        route_staged=decision.route == topo_router.ROUTE_STAGED,
        chunks=dec.chunks if dec is not None else None,
    )
    div = _CFG["divisor"]

    # The raw own-chunk row: a 1/ws-sized matmul over this device's own
    # slice of dw's leading rows — the SRA exactness rule's operand,
    # produced WITHOUT materializing the full f32 gradient.
    rows_per = w.shape[0] // ws
    own_idx = lax.axis_index(axis)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    x_own = lax.dynamic_slice(
        x2, (0, own_idx * rows_per), (x2.shape[0], rows_per)
    )
    dw_own = lax.dot_general(
        x_own, g2, (((0,), (0,)), ((), ())), precision=None
    ).astype(w.dtype)
    raw_row = (dw_own.reshape(-1).astype(jnp.float32) / div) if div != 1 else (
        dw_own.reshape(-1).astype(jnp.float32)
    )

    flat = (dw.reshape(-1).astype(jnp.float32) / div) if div != 1 else (
        dw.reshape(-1).astype(jnp.float32)
    )
    xs = flat.reshape(ws, chunk)

    q = None
    q_blocks = None
    table = None
    if sched is not None:
        # Pipelined era: one independently-quantized payload per column
        # block (the schedule's bit-equality contract quantizes each
        # block as its own call — same grid the consumer will expect).
        table = sched.table
        q_blocks = tuple(
            reducers._quantize_rows(
                lax.slice(xs, (0, off), (ws, off + wd)), cc, None
            )
            for off, wd in table
        )
    else:
        q = _produce_q(xs, x2, g2, cc, ws=ws, chunk=chunk, div=div)
    metrics.add("cgx.codec.producer_staged")
    metrics.add("cgx.codec.producer_staged_elems", float(n))
    ent = Produced(
        cotangent=dw, q=q, q_blocks=q_blocks, table=table, raw_row=raw_row,
        cc=cc, ws=ws, n=n, divisor=div, epoch=_CFG["epoch"], name=name,
    )
    _STASH[id(dw)] = ent


def _produce_q(xs, x2, g2, cc, *, ws, chunk, div) -> codec.QTensor:
    """The monolithic stage-1 payload: the fused Pallas matmul+quantize
    kernel when the geometry aligns and the kernel mode allows, else the
    compose path (quantize of the same rows — byte-identical to what the
    allreduce's own quantize would emit for these values)."""
    from ..parallel import reducers

    kmode = _kernel_mode()
    geo = (
        _kernel_geometry(
            x2.shape[0], x2.shape[1], g2.shape[1], ws, chunk, cc
        )
        if kmode != "off"
        else None
    )
    if geo is not None and (kmode == "on" or _on_tpu()):
        tm, tk = geo
        metrics.add("cgx.codec.producer_kernel_slices")
        return _matmul_quantize_q(
            x2, g2, cc, ws=ws, chunk=chunk, div=div, tm=tm, tk=tk,
            interpret=not _on_tpu(),
        )
    metrics.add("cgx.codec.producer_compose_slices")
    return reducers._quantize_rows(xs, cc, None)


# ---------------------------------------------------------------------------
# The fused matmul+quantize Pallas kernel.
# ---------------------------------------------------------------------------

_KERNEL_MAX_ACC_ELEMS = 1 << 18  # f32 VMEM accumulator budget (1 MB)


def _kernel_geometry(
    k_total: int, din: int, o: int, ws: int, chunk: int,
    cc: CompressionConfig,
) -> Optional[Tuple[int, int]]:
    """(tm, tk) grid tiling for the fused kernel, or None when the shapes
    don't align: output row-blocks must cover whole 32-bucket chunks of
    the flat layout, nest inside the (ws, chunk) wire rows, and leave a
    VMEM-sized accumulator; the contraction dim splits evenly; and the
    ``(tk, tm)`` block of ``x`` must be one Mosaic accepts — ``tm`` a
    multiple of 128 lanes (or all of ``din``), ``tk`` a multiple of 16
    sublanes (or all of ``k_total``). GPT-2's qkv / mlp_in kernels
    (din=768 over 4 ranks -> tm=64) are refused by that rule and take
    the compose path."""
    import math

    b = cc.bucket_size
    if b % 128 or o % 128 or chunk % (CHUNK_BUCKETS * b):
        return None
    rows_per = din // ws  # dw rows per wire row (caller checked din % ws)
    # tm rows of dw = tm*O flat elems: needs whole chunks + row nesting.
    t0 = (CHUNK_BUCKETS * b) // math.gcd(CHUNK_BUCKETS * b, o)
    if t0 == 0 or rows_per % t0:
        return None
    tm = t0
    while (
        tm * 2 <= rows_per
        and rows_per % (tm * 2) == 0
        and (tm * 2) * o <= _KERNEL_MAX_ACC_ELEMS
    ):
        tm *= 2
    if tm * o > _KERNEL_MAX_ACC_ELEMS:
        return None
    if tm % 128 and tm != din:
        return None
    tk = next(
        (c for c in (512, 256, 128, 64, 32, 16) if k_total % c == 0),
        k_total,
    )
    return tm, tk


def _matmul_quantize_q(
    x2, g2, cc, *, ws, chunk, div, tm, tk, interpret
) -> codec.QTensor:
    """Run the fused kernel and assemble the (ws, chunk) row-batched
    QTensor (identical pytree layout to ``quantize_batch(xs)``)."""
    b = cc.bucket_size
    bits = cc.bits
    words, meta = _matmul_quantize_impl(
        x2, g2,
        bits=bits, bucket_size=b, div=div, tm=tm, tk=tk,
        pack=codec_pallas._pack_strategy(),
        encode=codec_pallas._encode_strategy(),
        interpret=interpret,
    )
    nb_r = chunk // b
    return codec.QTensor(
        packed=jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
            ws, chunk * bits // 32
        ),
        meta=meta.reshape(ws, nb_r, 2).astype(jnp.float32),
        residual=jnp.zeros((ws, 0), jnp.float32),
        numel=chunk,
        bits=bits,
        bucket_size=b,
        dtype=np.dtype(jnp.float32),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "bucket_size", "div", "tm", "tk", "pack", "encode",
        "interpret",
    ),
)
def _matmul_quantize_impl(
    x2: jax.Array,
    g2: jax.Array,
    *,
    bits: int,
    bucket_size: int,
    div: int,
    tm: int,
    tk: int,
    pack: str,
    encode: str,
    interpret: bool = False,
):
    """dw = x2^T @ g2, divided by ``div`` and quantized block-by-block in
    VMEM — packed words + meta are the ONLY HBM writes (the f32 gradient
    never exists). Grid (m, k): k sweeps the contraction with an f32
    accumulator; the last k step runs ``_requantize_block`` (the flat
    quantize kernel's shared body, so wire bytes match a quantize of the
    same values exactly)."""
    k_total, din = x2.shape
    o = g2.shape[1]
    b = bucket_size
    rb = b // 128
    cb = tm * o // (CHUNK_BUCKETS * b)  # chunks per row-block
    nm = din // tm
    nk = -(-k_total // tk)
    w_rows = cb * bits * rb
    m_rows = cb * CHUNK_BUCKETS

    def _matmul_quantize_kernel(x_ref, g_ref, words_ref, meta_ref, acc_ref):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += lax.dot_general(
            x_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k == nk - 1)
        def _():
            acc = acc_ref[:]
            if div != 1:
                acc = acc / div
            x4 = acc.reshape(cb, CHUNK_BUCKETS, rb, 128)
            words, meta = codec_pallas._requantize_block(
                x4, None, bits=bits, tc=cb, rb=rb, stochastic=False,
                pack=pack, encode=encode,
            )
            words_ref[:] = words
            meta_ref[:] = meta

    words, meta = pl.pallas_call(
        _matmul_quantize_kernel,
        name="cgx_matmul_quantize",
        grid=(nm, nk),
        in_specs=[
            pl.BlockSpec((tk, tm), lambda m, k: (k, m),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, o), lambda m, k: (k, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((w_rows, 128), lambda m, k: (m, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((m_rows, 2), lambda m, k: (m, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nm * w_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((nm * m_rows, 2), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tm, o), jnp.float32)],
        interpret=interpret,
    )(x2, g2)
    return words, meta
