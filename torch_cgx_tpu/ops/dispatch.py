"""Codec implementation dispatch: fused Pallas kernels on TPU, pure-XLA
elsewhere (CGX_CODEC_IMPL = auto|pallas|xla).

Both implementations emit bit-identical wire payloads (see codec_pallas.py),
so the choice is purely about speed and can differ between producer and
consumer.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import config as cfg_mod
from ..config import CompressionConfig
from . import codec, codec_pallas, gdn, grouped_matmul as gmm, mhc
from . import prefill_attention as pfa, ssm


def _on_tpu() -> bool:
    """Whether the default backend is a TPU. A backend that fails to come
    up raises here: answering False would silently select the interpret
    kernels and the XLA codec — the CPU program — on a TPU host."""
    return jax.default_backend() == "tpu"


def _pick(n: int, cc: CompressionConfig) -> str:
    impl = cfg_mod.codec_impl()
    ok = codec_pallas.supports(n, cc.bits, cc.bucket_size, cc.skip_incomplete_buckets)
    if impl == "xla" or not ok:
        return "xla"
    if impl == "pallas":
        return "pallas"
    return "pallas" if _on_tpu() else "xla"


def quantize_batch(
    xs: jax.Array, cc: CompressionConfig, key: Optional[jax.Array] = None
) -> codec.QTensor:
    """Quantize each row of ``xs (rows, m)``; stochastic iff cc.stochastic
    and a key is given."""
    stochastic = cc.stochastic and key is not None
    # pltpu.prng_* has no CPU interpreter lowering — stochastic rounding off
    # TPU always takes the XLA (threefry) path.
    if _pick(xs.shape[1], cc) == "pallas" and not (stochastic and not _on_tpu()):
        return codec_pallas.quantize_batch(
            xs,
            cc.bits,
            cc.bucket_size,
            stochastic=stochastic,
            key=key,
            interpret=not _on_tpu(),
            skip_incomplete_buckets=cc.skip_incomplete_buckets,
        )
    codec_pallas.note_lowering("quantize", "xla")
    if stochastic:
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(xs.shape[0])
        )
        return jax.vmap(
            lambda r, k: codec.quantize(
                r,
                cc.bits,
                cc.bucket_size,
                stochastic=True,
                key=k,
                skip_incomplete_buckets=cc.skip_incomplete_buckets,
            )
        )(xs, keys)
    return jax.vmap(
        lambda r: codec.quantize(
            r,
            cc.bits,
            cc.bucket_size,
            skip_incomplete_buckets=cc.skip_incomplete_buckets,
        )
    )(xs)


def dequantize_batch(
    q: codec.QTensor,
    *,
    add_to: Optional[jax.Array] = None,
    out_dtype=None,
    row_width: Optional[int] = None,
) -> jax.Array:
    """Decode a batched QTensor (leading rows dim) -> (rows, numel), or
    ``(rows, numel // row_width, row_width)`` when the consumer reads rows
    of ``row_width`` values (``codec_pallas.dequantize_batch`` says what
    that saves on the chip)."""
    cc = CompressionConfig(
        bits=q.bits or 32,
        bucket_size=q.bucket_size or 512,
        skip_incomplete_buckets=bool(q.residual.shape[-1]),
    )
    if q.bits and _pick(q.numel, cc) == "pallas":
        return codec_pallas.dequantize_batch(
            q, add_to=add_to, out_dtype=out_dtype, interpret=not _on_tpu(),
            row_width=row_width,
        )
    codec_pallas.note_lowering("dequantize", "xla")
    if add_to is not None:
        vals = jax.vmap(
            lambda qq, acc: codec.dequantize(qq, add_to=acc, out_dtype=out_dtype)
        )(q, add_to)
    else:
        vals = jax.vmap(
            lambda qq: codec.dequantize(qq, out_dtype=out_dtype)
        )(q)
    return codec_pallas.as_rows(vals, row_width)


def takes_pallas(n: int, cc: CompressionConfig) -> bool:
    """Whether a codec call over rows of ``n`` values at ``cc`` takes the
    Pallas kernels on this backend (what :func:`quantize_batch` and
    :func:`dequantize_batch` decide for themselves)."""
    return _pick(n, cc) == "pallas"


def dequantize_pages(
    words: jax.Array,
    meta: jax.Array,
    page_ids: jax.Array,
    cc: CompressionConfig,
    *,
    tile: int,
    out_dtype,
    row_width: int,
    name: str = "cgx_dequantize_flat",
    live: Optional[jax.Array] = None,
    unpack: str = "planes",
) -> jax.Array:
    """The paged cache read on Pallas dispatch
    (``codec_pallas.dequantize_pages``); ``ops/paged_kv.py`` decides
    whether a pool takes it (:func:`takes_pallas` and
    ``PageSpec.paged_read_tile``), what the kernel is called, which
    entries it guards (``live``) and how it unpacks 8-bit planes
    (``unpack``)."""
    return codec_pallas.dequantize_pages(
        words, meta, page_ids, bits=cc.bits, bucket_size=cc.bucket_size,
        tc=tile, out_dtype=out_dtype, row_width=row_width,
        interpret=not _on_tpu(), name=name, live=live, unpack=unpack,
    )


# ---------------------------------------------------------------------------
# Fused SRA epilogue dispatch (CGX_SRA_EPILOGUE = auto|fused|staged).
#
# The reducers' decompress-accumulate(-requantize) hot path routes through
# these two entry points instead of composing dequantize_batch + jnp.sum +
# quantize_batch at each call site: one place decides between the fused
# Pallas kernels (TPU — the decoded floats never round-trip HBM) and the
# staged reference path (everywhere else, and the oracle the fused kernels
# are byte-checked against). tools/lint.py enforces the routing for new
# reducer variants.
# ---------------------------------------------------------------------------


def _use_fused_reduce(q: codec.QTensor, *, stochastic: bool = False) -> bool:
    """Fused-kernel eligibility for this QTensor under the current mode.
    "fused" forces the kernel (interpret mode off TPU — the test knob);
    "auto" takes it only on real TPU dispatch with the Pallas codec
    allowed AND a payload at or above the size crossover
    (``CGX_SRA_EPILOGUE_MIN_ELEMS`` — small fused buckets measured SLOWER
    than the staged ops, BENCH_LOG ``sra_epilogue_fused_vs_staged``).
    Stochastic requantize needs the TPU hardware PRNG, which has no
    interpret lowering — staged off-TPU regardless of mode."""
    mode = cfg_mod.sra_epilogue()
    if mode == "staged":
        return False
    if not codec_pallas.supports_reduce(q):
        return False
    if stochastic and not _on_tpu():
        return False
    if mode == "fused":
        return True
    if q.batch_rows * q.numel < cfg_mod.sra_epilogue_min_elems():
        return False
    return _on_tpu() and cfg_mod.codec_impl() != "xla"


def fused_epilogue_would_run(
    q: codec.QTensor, *, stochastic: bool = False
) -> bool:
    """True when :func:`reduce_rows_requantize` would take the fused
    kernel for this QTensor under the current mode/backend. The ws==1
    force-codec proxy (reducers.quantized_allreduce) keys off this so the
    single-chip train-step probe emulates the kernel sequence a real rank
    runs in the same era — staged three-kernel shape or fused two-kernel
    shape."""
    return _use_fused_reduce(q, stochastic=stochastic)


# ---------------------------------------------------------------------------
# Staged-allreduce capability (CGX_XLA_ALLREDUCE = auto|on|off).
#
# The in-XLA single-program quantized allreduce (parallel/xla_allreduce.py)
# compiles quantize -> collective exchange -> fused epilogue -> all_gather
# into ONE staged XLA program for intra-slice groups. Whether a group is
# *eligible* for that routing is a backend/knob question answered here, in
# the same module that already decides codec and epilogue lowerings; the
# *topology* question (is the group intra-slice?) belongs to
# parallel/topology.py, which consults this gate.
# ---------------------------------------------------------------------------


def staged_allreduce_capable() -> bool:
    """True when the current backend + ``CGX_XLA_ALLREDUCE`` mode allow
    routing intra-slice traffic to the staged single-program allreduce:
    "on" stages anywhere (CPU multi-device included — the bench/test
    configuration), "auto" only on a real TPU backend (so the default is
    inert on every CI/CPU path — staged programs, store keys and wire
    bytes unchanged), "off" never."""
    mode = cfg_mod.xla_allreduce()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return _on_tpu()


def ordered_rowsum(vals: jax.Array) -> jax.Array:
    """Row accumulation with the association pinned: ``v0 + v1 + ...``
    ascending. A bare ``jnp.sum(axis=0)`` leaves the fold order to the XLA
    lowering (measured: CPU re-trees a 4-row reduce pairwise), which would
    put the staged and fused lowerings a last-ulp apart — and a last-ulp
    apart in the accumulate is a different requantized WIRE BYTE. Both
    lowerings spell this fold explicitly; the row count is the (small,
    static) world size, so the unrolled chain costs nothing."""
    red = vals[0]
    for r in range(1, vals.shape[0]):
        red = red + vals[r]
    return red


def _own_row(raw_rows: jax.Array, own_idx, numel: int) -> jax.Array:
    """The raw own chunk: row ``own_idx`` of the (ws, chunk) stage-1
    matrix, sliced outside the kernel so the fused path streams one chunk
    of raw values instead of all ws rows."""
    return lax.dynamic_slice(raw_rows, (own_idx, 0), (1, numel))[0]


def reduce_rows(
    q: codec.QTensor,
    *,
    raw_rows: Optional[jax.Array] = None,
    raw_row: Optional[jax.Array] = None,
    own_idx: Optional[jax.Array] = None,
    add_to: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Dequantize-accumulate a row-batched QTensor -> flat (numel,)
    reduced values: decode every row, substitute the raw own chunk
    (``raw_rows[own_idx]``) for its own decode when given (the SRA
    own-chunk-exact rule, scatter_reduce_allgather.cc:116-155), and sum.
    ``raw_row`` is the pre-sliced alternative — the flat own chunk
    itself, from a caller that never materializes the full (ws, chunk)
    raw matrix (the producer-fused path). ``add_to`` (flat) is a
    pre-accumulator (the Ring hop's decompress-add, UnpackArray<ADD>).
    Fused Pallas kernel on TPU; staged reference ops elsewhere —
    identical values by construction (interpret-mode byte-check in the
    suite)."""
    if raw_rows is not None and raw_row is not None:
        raise ValueError("pass raw_rows or raw_row, not both")
    rows = q.batch_rows
    have_raw = raw_rows is not None or raw_row is not None
    if rows > 1 and add_to is None and _use_fused_reduce(q):
        rr = (
            _own_row(raw_rows, own_idx, q.numel)
            if raw_rows is not None
            else raw_row
        )
        return codec_pallas.reduce_rows_batch(
            q, raw_row=rr, own_idx=own_idx, interpret=not _on_tpu()
        ).astype(out_dtype)
    # Staged reference path (also the fused kernels' byte oracle).
    codec_pallas.note_lowering("reduce_rows", "staged")
    if rows == 1 and not have_raw:
        return dequantize_batch(
            q,
            add_to=None if add_to is None else add_to[None],
            out_dtype=out_dtype,
        )[0]
    vals = dequantize_batch(q, out_dtype=jnp.float32)
    if have_raw:
        own = (jnp.arange(rows) == own_idx)[:, None]
        raw_b = (
            raw_rows if raw_rows is not None else raw_row[None]
        ).astype(jnp.float32)
        vals = jnp.where(own, raw_b, vals)
    red = ordered_rowsum(vals)
    if add_to is not None:
        red = add_to.astype(jnp.float32) + red
    return red.astype(out_dtype)


def reduce_rows_requantize(
    q: codec.QTensor,
    cc: CompressionConfig,
    *,
    raw_rows: Optional[jax.Array] = None,
    raw_row: Optional[jax.Array] = None,
    own_idx: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> codec.QTensor:
    """The full SRA epilogue: :func:`reduce_rows` + requantize of the
    reduced chunk into a rows=1 QTensor (the stage-2 allgather payload) —
    one fused HBM pass on TPU, the staged decode/sum/quantize reference
    elsewhere. ``raw_row`` is the pre-sliced own chunk (producer-fused
    callers — see :func:`reduce_rows`). Wire bytes are identical between
    the two lowerings on the default deterministic ``div`` encode;
    ``CGX_CODEC_ENCODE=mul`` applies inside the fused requantize exactly
    as in the staged quantize (same one-knob flip, PERF_NOTES.md)."""
    stochastic = cc.stochastic and key is not None
    if _use_fused_reduce(q, stochastic=stochastic):
        rr = (
            _own_row(raw_rows, own_idx, q.numel)
            if raw_rows is not None
            else raw_row
        )
        return codec_pallas.sra_epilogue_batch(
            q,
            raw_row=rr,
            own_idx=own_idx,
            key=key if stochastic else None,
            out_dtype=out_dtype,
            interpret=not _on_tpu(),
        )
    codec_pallas.note_lowering("sra_epilogue", "staged")
    reduced = reduce_rows(
        q, raw_rows=raw_rows, raw_row=raw_row, own_idx=own_idx,
        out_dtype=jnp.float32,
    )
    return quantize_batch(
        reduced.astype(out_dtype)[None], cc, key if stochastic else None
    )


def _kernels() -> bool:
    """Whether the state and expert kernels run: on the TPU, and
    interpreted wherever ``CGX_CODEC_IMPL=pallas`` asks for the kernels."""
    impl = cfg_mod.codec_impl()
    return impl == "pallas" or (impl == "auto" and _on_tpu())


def ssm_update(state, decay, dtx, bm, cm):
    """One token's update of a state-space layer's recurrent state, all
    lanes (``ops/ssm.py``): the ``cgx_ssm_update`` kernel on the TPU (and,
    interpreted, wherever ``CGX_CODEC_IMPL=pallas`` asks for the kernels),
    its ``jax.numpy`` form elsewhere; counted per call site as
    ``cgx.codec.lowering.ssm_update.pallas`` / ``.xla``."""
    if _kernels():
        codec_pallas.note_lowering("ssm_update", "pallas")
        return ssm.ssm_update_pallas(
            state, decay, dtx, bm, cm, interpret=not _on_tpu()
        )
    codec_pallas.note_lowering("ssm_update", "xla")
    return ssm.ssm_update_xla(state, decay, dtx, bm, cm)


def _delta_rule_update(site, kernel, state, q, k, v, alpha, beta):
    """``ops/gdn.py``'s one-step update under the kernel name ``kernel``,
    counted as ``cgx.codec.lowering.<site>.pallas`` / ``.xla``."""
    if _kernels():
        codec_pallas.note_lowering(site, "pallas")
        return gdn.gdn_update_pallas(
            state, q, k, v, alpha, beta, name=kernel,
            interpret=not _on_tpu(),
        )
    codec_pallas.note_lowering(site, "xla")
    return gdn.gdn_update_xla(state, q, k, v, alpha, beta)


def gdn_update(state, q, k, v, alpha, beta):
    """One token's update of a gated delta-rule layer's recurrent state,
    all lanes (``ops/gdn.py``): the ``cgx_gdn_update`` kernel on the TPU
    (and, interpreted, wherever ``CGX_CODEC_IMPL=pallas`` asks for the
    kernels), its ``jax.numpy`` form elsewhere; counted per call site as
    ``cgx.codec.lowering.gdn_update.pallas`` / ``.xla``."""
    return _delta_rule_update("gdn_update", "cgx_gdn_update", state, q, k, v,
                              alpha, beta)


def kda_update(state, q, k, v, alpha, beta):
    """:func:`gdn_update` for a layer whose decay is a number a key channel
    (``alpha (B, H, dk)``: KDA): the same kernel, ``cgx_kda_update`` in the
    device's trace, the same ``jax.numpy`` form; counted per call site as
    ``cgx.codec.lowering.kda_update.pallas`` / ``.xla``."""
    return _delta_rule_update("kda_update", "cgx_kda_update", state, q, k, v,
                              alpha, beta)


def mhc_pre(x, phi, alpha, base, *, kernel: str, phi_t=None, **how):
    """What a hyper-connection computes in front of its sublayer, a token's
    flattened streams a row of ``x`` (``ops/mhc.py``; ``how`` is its
    keywords; ``phi_t`` is ``mhc.kernel_phi(phi)`` where the caller keeps it
    beside ``phi``, made here without). Where :func:`mhc.token_tile` gives the tokens a tile, the
    kernel named ``kernel`` (``cgx_mhc_pre_decode`` in a decode step,
    ``cgx_mhc_pre_prefill`` in a prefill: a trace tells a call over a
    step's lanes from one over a prompt) on the TPU (and, interpreted,
    wherever ``CGX_CODEC_IMPL=pallas`` asks for the kernels); the
    ``jax.numpy`` form elsewhere; counted per call site as
    ``cgx.codec.lowering.mhc_pre.pallas`` / ``.xla``."""
    if _kernels() and mhc.token_tile(x.shape[0]) is not None:
        codec_pallas.note_lowering("mhc_pre", "pallas")
        return mhc.mhc_pre_pallas(
            x, mhc.kernel_phi(phi) if phi_t is None else phi_t, alpha, base,
            name=kernel, interpret=not _on_tpu(), **how
        )
    codec_pallas.note_lowering("mhc_pre", "xla")
    return mhc.mhc_pre_xla(x, phi, alpha, base, **how)


def grouped_matmul(lhs, rhs, sizes):
    """The experts' grouped product (``ops/grouped_matmul.py``): ``lhs (M,
    K)`` sorted by group times ``rhs (E, K, N)`` by ``sizes (E,)``. Where
    :func:`grouped_matmul.takes_kernel` says the shapes are the kernel's,
    the ``cgx_grouped_matmul`` kernel on the TPU (and, interpreted, wherever
    ``CGX_CODEC_IMPL=pallas`` asks for the kernels); ``jax.lax.ragged_dot``
    elsewhere; counted per call site as
    ``cgx.codec.lowering.grouped_matmul.pallas`` / ``.xla``."""
    if _kernels() and gmm.takes_kernel(lhs.shape[0], *rhs.shape,
                                       rhs.dtype.itemsize):
        codec_pallas.note_lowering("grouped_matmul", "pallas")
        return gmm.grouped_matmul_pallas(
            lhs, rhs, sizes, interpret=not _on_tpu()
        )
    codec_pallas.note_lowering("grouped_matmul", "xla")
    return gmm.grouped_matmul_xla(lhs, rhs, sizes)


def prefill_attention(q, k, v, q_rope=None, k_rope=None, *, window: int = 0,
                      scale: float, q_block: int, dtype, block: int = 1):
    """The attention of a whole prompt from position 0
    (``ops/prefill_attention.py``): ``q (B, S, H, d)``, ``k (B, S, Hk,
    d)``, ``v (B, S, Hk, dv)`` and, for a key of two parts, ``q_rope (B, S,
    H, dr)``, ``k_rope (B, S, dr)`` -> ``(B, S, H * dv)`` in ``dtype``,
    query ``i`` over the keys ``i - window < j <= i`` (every ``j <= i``
    without a window; with ``block = L > 1`` every ``j`` up to the end of
    ``i``'s block of ``L`` positions, ``j < (i // L + 1) * L``: a model
    that generates by diffusion over blocks, given by the caller alone that
    has one, so every other call is what it was). Where
    :func:`prefill_attention.takes_kernel` says the
    shapes are the kernel's (a prompt of more than ``q_block`` positions),
    the ``cgx_prefill_attention`` kernel on the TPU (and, interpreted,
    wherever ``CGX_CODEC_IMPL=pallas`` asks for the kernels); the loop over
    blocks of ``q_block`` queries elsewhere; counted per call site as
    ``cgx.codec.lowering.prefill_attention.pallas`` / ``.xla``."""
    s, h, d = q.shape[1:]
    compiled = _on_tpu()
    if _kernels() and pfa.takes_kernel(
        s, q_block, h, k.shape[2], d, v.shape[3],
        0 if k_rope is None else k_rope.shape[-1], window, compiled,
    ):
        codec_pallas.note_lowering("prefill_attention", "pallas")
        return pfa.prefill_attention_pallas(
            q.astype(dtype), k, v, q_rope, k_rope, window=window,
            scale=float(scale), interpret=not compiled,
            **({"block": block} if block > 1 else {}),
        )
    codec_pallas.note_lowering("prefill_attention", "xla")
    return pfa.prefill_attention_xla(
        q, k, v, q_rope, k_rope, window=window, scale=scale,
        q_block=q_block, dtype=dtype, **({"block": block} if block > 1 else {}),
    )
