"""The attention of a whole prompt from position 0: one pass over its keys.

A prefill attends every query of a prompt to the keys it can see::

    out[i, h] = softmax_j(q[i, h] . k[j, kv(h)] * scale) v[j, kv(h)]
                over  i - window < j <= i      (window = 0: every j <= i)

With ``block = L > 1`` (a model that generates by diffusion over blocks,
``models/sdar_moe.py``) the causal edge is the end of the query's block of
``L`` positions: ``j < (i // L + 1) * L``, causal over blocks and both ways
inside one. Tiles and key blocks are multiples of ``L``, so the items of the
work list are the same and only the mask of an edge block changes.

:func:`prefill_attention_xla` is the query-block loop the models ran before
PR 43 (``window_moe.attend_blocks``, ``mla_moe.attend_expanded``), moved here
unchanged: ``jax.lax.map`` over blocks of ``q_block`` queries, each block's
``(heads, q_block, keys)`` float32 scores written to HBM, masked, normed and
read again. It is the CPU's path, the form of a prompt of one block
(:func:`takes_kernel`), and the oracle the tests compare with.

:func:`prefill_attention_pallas` is one kernel, ``cgx_prefill_attention``: a
grid step a (tile of queries, block of keys) item of a work list made from
the shapes and the window when the call is traced, the running maximum, the
running sum and the float32 accumulator of the tile in scratch, the output
written once in the operands' type. A block of keys no query of the tile can
see (past the causal edge, before the window's band) is no item: it is
neither fetched nor contracted, and a block every query sees whole takes no
mask. The query heads that read one K/V head are stacked as rows of one tile
against the one key block (grouped queries); a key of two parts (``q . k``
over ``d`` plus ``q_rope . k_rope`` over ``d_rope``, ``k_rope`` one a token
for all heads: latent attention) is two products summed into the scores.
Operands are multiplied as they are given (``cfg.dtype``), scores, maximum,
sum and accumulator are float32, the probabilities are cast to the operands'
type for the second product as the loop casts them, and the accumulator is
rounded once, at the end. ``ops.dispatch.prefill_attention`` picks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = np.float32(-1e30)
VMEM_LIMIT_BYTES = 64 << 20
# Keys a block.
KEY_BLOCK = 1024
# Rows a tile: the query heads of a grid step times the queries of each.
TILE_ROWS = 2048
# An item's flags.
FIRST, LAST, EDGE = 1, 2, 4


def _seen(key_pos, q_pos, block: int):
    """The causal edge: ``key_pos <= q_pos``; with ``block > 1`` the end of
    the query's block of ``block`` positions."""
    if block == 1:
        return key_pos <= q_pos
    return key_pos < (q_pos // block + 1) * block


def _grouped_loop(q, k, v, *, window, scale, q_block, dtype, block=1):
    """``window_moe.attend_blocks`` as PR 41 wrote it (it divided by
    ``sqrt(d)``, which is ``1 / scale``)."""
    dt = dtype
    b, s, h, d = q.shape
    hk = k.shape[2]
    blk = min(q_block, s)
    n_blk = -(-s // blk)
    pad = n_blk * blk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(b, n_blk * blk, hk, h // hk, d)
    k_dt, v_dt = k.astype(dt), v.astype(dt)
    band = min(s, window + blk) if window else s

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 1)
        # The band's first key: it ends with the block's last query, and
        # stays inside the prompt.
        lo = jnp.clip((i + 1) * blk - band, 0, s - band)
        kb = jax.lax.dynamic_slice_in_dim(k_dt, lo, band, 1)
        vb = jax.lax.dynamic_slice_in_dim(v_dt, lo, band, 1)
        scores = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                            preferred_element_type=jnp.float32
                            ) / np.float32(1.0 / scale)
        q_pos = (i * blk + jnp.arange(blk))[:, None]
        key_pos = (lo + jnp.arange(band))[None, :]
        seen = _seen(key_pos, q_pos, block)
        if window:
            seen &= q_pos - key_pos < window
        scores = jnp.where(seen, scores, np.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bkgqt,btkd->bqkgd", probs, vb)

    o = jax.lax.map(one, jnp.arange(n_blk))  # (n_blk, B, blk, Hk, G, dh)
    o = jnp.moveaxis(o, 0, 1).reshape(b, n_blk * blk, h * d)
    return o[:, :s]


def _two_part_loop(q_nope, q_rope, k_nope, k_rope, v, *, scale, q_block):
    """``mla_moe.attend_expanded``'s loop as PR 27 wrote it."""
    b, s, h, _ = q_nope.shape
    blk = min(q_block, s)
    n_blk = -(-s // blk)
    pad = n_blk * blk - s
    if pad:
        q_nope = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_pos = jnp.arange(s)

    def one(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, 1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * blk, blk, 1)
        scores = (
            jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bkr->bhqk", qr, k_rope,
                         preferred_element_type=jnp.float32)
        ) * scale
        q_pos = i * blk + jnp.arange(blk)
        causal = key_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(causal, scores, np.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", probs, v)

    o = jax.lax.map(one, jnp.arange(n_blk))  # (n_blk, B, blk, H, dv)
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, n_blk * blk, h * v.shape[-1])
    return o[:, :s]


def prefill_attention_xla(q, k, v, q_rope=None, k_rope=None, *, window: int,
                          scale, q_block: int, dtype, block: int = 1):
    """``q (B, S, H, d)``, ``k (B, S, Hk, d)``, ``v (B, S, Hk, dv)`` and,
    for a key of two parts, ``q_rope (B, S, H, dr)``, ``k_rope (B, S, dr)``
    -> ``(B, S, H * dv)`` in ``dtype``: the loop over blocks of ``q_block``
    queries. One-part keys are read by groups of ``H / Hk`` query heads,
    ``k`` and ``v`` cast to ``dtype`` here, the scores divided by ``1 /
    scale``; two-part keys come in ``dtype``, a K/V head a query head, the
    scores multiplied by ``scale`` (each as its model wrote it). ``block``:
    the module's text; one-part keys without a window, a prompt of whole
    blocks."""
    if block > 1 and (k_rope is not None or window or q.shape[1] % block
                      or min(q_block, q.shape[1]) % block):
        raise ValueError(
            f"a block mask of {block} takes one-part keys, no window and a "
            f"prompt ({q.shape[1]}) and query blocks ({q_block}) of whole "
            "blocks")
    if k_rope is None:
        return _grouped_loop(q, k, v, window=window, scale=scale,
                             q_block=q_block, dtype=dtype, block=block)
    if window:
        raise ValueError("two-part keys take no window")
    return _two_part_loop(q, q_rope, k, k_rope, v, scale=scale,
                          q_block=q_block)


def heads_a_step(h: int, hk: int, d_rope: int) -> int:
    """Query heads a grid step: the group that reads one K/V head; where
    every head has its own, as many as make ``q_rope``'s block whole lanes
    (1 without)."""
    if hk < h:
        return h // hk
    return math.gcd(h, max(1, 128 // d_rope)) if d_rope else 1


def supports(h: int, hk: int, d: int, dv: int, d_rope: int, window: int,
             compiled: bool = True) -> bool:
    """Whether the kernel is written for these widths: grouped queries or a
    window with one-part keys, a K/V head a query head with two-part keys;
    compiled, heads of whole lanes and a rotated part that tiles them
    (interpreted, any width)."""
    hb = heads_a_step(h, hk, d_rope)
    if h % hk or (d_rope and (hk < h or window)):
        return False
    return not compiled or not (d % 128 or dv % 128 or (hb * d_rope) % 128)


def takes_kernel(s: int, q_block: int, h: int, hk: int, d: int, dv: int,
                 d_rope: int = 0, window: int = 0, compiled: bool = True
                 ) -> bool:
    """Whether a prompt of ``s`` positions is the kernel's, by shape alone:
    one of several query blocks, at widths :func:`supports` knows. A prompt
    of one block (``s <= q_block``) loops over nothing and holds ``(H, S,
    S)`` scores: it stays the loop's, the program it was."""
    return s > q_block and supports(h, hk, d, dv, d_rope, window, compiled)


def tiles(s: int, hb: int, tq: int = 0, tk: int = 0, unit: int = 128):
    """``(queries a tile, keys a block)`` for a prompt of ``s`` positions
    and ``hb`` query heads a step: ``TILE_ROWS`` rows a tile, ``KEY_BLOCK``
    keys, each a multiple of ``unit`` and neither longer than the prompt
    rounded up to one."""
    most = -(-s // unit) * unit
    tq = tq or max(unit, TILE_ROWS // hb // unit * unit)
    return min(tq, most), min(tk or KEY_BLOCK, most)


def work_list(s: int, tq: int, tk: int, window: int):
    """The kernel's items for ``s`` positions padded to whole tiles and
    blocks: ``(tile, block, flags)``, each ``(items,)`` int32, a tile's
    items in the order of its key blocks, from the first block a query of
    the tile can see to the block of its last query (of the prompt's last
    position, in a tile that ends past it). ``FIRST`` and ``LAST``
    mark a tile's ends, ``EDGE`` a block some query of the tile sees part
    of."""
    tile, block, flags = [], [], []
    for t in range(-(-s // tq)):
        q0, q1 = t * tq, (t + 1) * tq - 1
        first = max(0, q0 - window + 1) // tk if window else 0
        last = min(q1, s - 1) // tk
        for j in range(first, last + 1):
            k0, k1 = j * tk, (j + 1) * tk - 1
            whole = k1 <= q0 and (not window or q1 - k0 < window)
            tile.append(t)
            block.append(j)
            flags.append(FIRST * (j == first) + LAST * (j == last)
                         + EDGE * (not whole))
    return tuple(np.asarray(x, np.int32) for x in (tile, block, flags))


def _kernel(kb, window, scale, two_part, block, tile_ref, block_ref,
            flags_ref, *refs):
    if two_part:
        (q_ref, qr_ref, k_ref, kr_ref, v_ref, o_ref,
         qs_ref, qrs_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, qs_ref, m_ref, l_ref, acc_ref) = refs
    (tq, _), (tk, _) = q_ref.shape, k_ref.shape
    (rows, d), dv = qs_ref.shape, acc_ref.shape[1]
    hb = rows // tq
    i = pl.program_id(2)
    t, j, flags = tile_ref[i], block_ref[i], flags_ref[i]

    @pl.when((flags & FIRST) != 0)
    def _():
        # The step's query heads, one under another.
        for g in range(hb):
            qs_ref[g * tq:(g + 1) * tq, :] = q_ref[:, g * d:(g + 1) * d]
            if two_part:
                dr = qrs_ref.shape[1]
                qrs_ref[g * tq:(g + 1) * tq, :] = (
                    qr_ref[:, g * dr:(g + 1) * dr])
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def nt(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def step(edge):
        if kb == 1:
            s = nt(qs_ref[...], k_ref[...])
        else:
            s = jnp.concatenate(
                [nt(qs_ref[g * tq:(g + 1) * tq, :],
                    k_ref[:, g * d:(g + 1) * d]) for g in range(hb)], axis=0)
        if two_part:
            s = s + nt(qrs_ref[...], kr_ref[...])
        s = s * scale
        if edge:
            q_pos = t * tq + jnp.concatenate(
                [jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)] * hb,
                axis=0)
            key_pos = j * tk + jax.lax.broadcasted_iota(
                jnp.int32, (1, tk), 1)
            seen = _seen(key_pos, q_pos, block)
            if window:
                seen &= q_pos - key_pos < window
            s = jnp.where(seen, s, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # A row that has seen masked keys alone so far holds exp(0) of
        # them: its next block's alpha is 0 and wipes it, and every row's
        # own position is in its tile's last block.
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        p = p.astype(v_ref.dtype)
        if kb == 1:
            pv = jnp.dot(p, v_ref[...], preferred_element_type=jnp.float32)
        else:
            pv = jnp.concatenate(
                [jnp.dot(p[g * tq:(g + 1) * tq], v_ref[:, g * dv:(g + 1) * dv],
                         preferred_element_type=jnp.float32)
                 for g in range(hb)], axis=0)
        acc_ref[...] = alpha * acc_ref[...] + pv

    @pl.when((flags & EDGE) != 0)
    def _():
        step(True)

    @pl.when((flags & EDGE) == 0)
    def _():
        step(False)

    @pl.when((flags & LAST) != 0)
    def _():
        out = acc_ref[...] / l_ref[...]
        for g in range(hb):
            o_ref[:, g * dv:(g + 1) * dv] = (
                out[g * tq:(g + 1) * tq].astype(o_ref.dtype))


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "tq", "tk", "interpret", "block"))
def prefill_attention_pallas(q, k, v, q_rope=None, k_rope=None, *,
                             window: int, scale: float, tq: int = 0,
                             tk: int = 0, interpret: bool = False,
                             block: int = 1):
    """:func:`prefill_attention_xla` as one Pallas kernel; the products take
    every operand in ``q``'s type, which is the output's. ``tq`` queries a
    tile and ``tk`` keys a block (:func:`tiles` where 0). ``block``: the
    module's text; tiles and key blocks are then whole blocks of it, so a
    tile's last key block is still the block of its last query and a key
    block before the tile's first query is still seen whole."""
    b, s, h, d = q.shape
    hk, dv = k.shape[2], v.shape[3]
    two_part = k_rope is not None
    dr = k_rope.shape[-1] if two_part else 0
    assert supports(h, hk, d, dv, dr, window, not interpret), (
        q.shape, k.shape, v.shape)
    hb = heads_a_step(h, hk, dr)
    kb = 1 if hk < h else hb
    tq, tk = tiles(s, hb, tq, tk, 8 if interpret else 128)
    assert block == 1 or not (window or two_part or s % block or tq % block
                              or tk % block), (block, window, s, tq, tk)
    # Whole tiles and whole blocks: a key past the prompt is past every
    # query's position, and a query past it is cut off below.
    sq, sk = -(-s // tq) * tq, -(-s // tk) * tk

    def flat(x, to):
        x = x.astype(q.dtype).reshape(b, s, -1)
        return jnp.pad(x, ((0, 0), (0, to - s), (0, 0))) if to > s else x

    tile, key_block, flags = work_list(s, tq, tk, window)

    def at_tile(bi, hi, i, ti, bl, fl):
        return (bi, ti[i], hi)

    def at_block(bi, hi, i, ti, bl, fl):
        return (bi, bl[i], hi)

    def shared(bi, hi, i, ti, bl, fl):
        return (bi, bl[i], 0)

    operands = [flat(q, sq)]
    in_specs = [pl.BlockSpec((None, tq, hb * d), at_tile)]
    scratch = [pltpu.VMEM((hb * tq, d), q.dtype)]
    if two_part:
        operands.append(flat(q_rope, sq))
        in_specs.append(pl.BlockSpec((None, tq, hb * dr), at_tile))
        scratch.append(pltpu.VMEM((hb * tq, dr), q.dtype))
    operands.append(flat(k, sk))
    in_specs.append(pl.BlockSpec((None, tk, kb * d), at_block))
    if two_part:
        operands.append(flat(k_rope, sk))
        in_specs.append(pl.BlockSpec((None, tk, dr), shared))
    operands.append(flat(v, sk))
    in_specs.append(pl.BlockSpec((None, tk, kb * dv), at_block))
    scratch += [pltpu.VMEM((hb * tq, 1), jnp.float32),
                pltpu.VMEM((hb * tq, 1), jnp.float32),
                pltpu.VMEM((hb * tq, dv), jnp.float32)]
    pairs = len(tile) * tq * tk
    out = pl.pallas_call(
        functools.partial(_kernel, kb, window, np.float32(scale), two_part,
                          block),
        name="cgx_prefill_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h // hb, len(tile)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tq, hb * dv), at_tile),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * pairs * (d + dr + dv),
            transcendentals=b * h * pairs,
            bytes_accessed=q.dtype.itemsize * b * (
                sq * h * (d + dr + dv)
                + (h // hb) * len(tile) * tk * (kb * (d + dv) + dr)),
        ),
        interpret=interpret,
    )(jnp.asarray(tile), jnp.asarray(key_block), jnp.asarray(flags),
      *operands)
    return out[:, :s]
