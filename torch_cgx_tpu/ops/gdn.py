"""The one-step update of a gated delta-rule layer's recurrent state, all
lanes.

A gated delta-rule layer keeps, a lane and a head, a matrix ``S (d_k,
d_v)`` float32, and a decode step first reads it against the token's own
key, corrects it by the error, and reads it again against the query::

    S~ = Diag(alpha) S               beta: one a head
    u  = beta (v - S~^T k)           k, q, alpha: (d_k,) a head
    S' = S~ + k u^T                  v, u, o: (d_v,) a head
    o  = S'^T q

``alpha`` scales the state's rows: one number a head
(``models/olmo_hybrid.py``, ``(B, H)``) or one a key channel a head (KDA:
``models/ling_hybrid.py``, ``(B, H, d_k)``). Two contractions over the key
dimension with a dependency between them: the whole ``d_k`` of a head has to
be in the block before the rank-one write. The state is laid ``(d_k, heads x
d_v)`` a lane (``models/olmo_hybrid.py``): the key dimension down the
sublanes, the heads' values side by side along the lanes, so that ``v``,
``u``, ``o`` and the per-head ``beta`` and ``alpha`` (spread over the head's
values by the caller: a sixtieth of the state's bytes) are rows, both
contractions are sums down the sublanes, and nothing is padded:
Olmo-Hybrid's 192 values a head are one and a half 128-lane vectors, so heads
are taken two at a time (384 lanes, three whole vectors). ``k``, ``q`` and a
per-channel ``alpha`` are a head's own columns; the kernel is handed them
``(d_k, heads of the block)`` and spreads each over its head's lanes itself.
The decay's rank picks its operand when the kernel is traced: one kernel
body, and a layer gated a head keeps the row, which costs it nothing (the
column's spread costs 0.5 % of the kernel's time: PERF.md section 6, PR 37).

At 96 lanes and Olmo-Hybrid-7B's 96 x 5,760 that is 212 MB read and 212 MB
written a layer, twelve layers a step: a buffer that has to be updated where
it lies. :func:`gdn_update_pallas` is one kernel, ``cgx_gdn_update``, a grid
step a ``(d_k, heads x d_v)`` block of one lane's state (ten heads, 737 KB,
at those sizes; sixteen heads of 128 x 128, 1 MB, at Ling-3.0-flash's 128 x
4,096, where the call site names it ``cgx_kda_update``), the state aliased
in place; :func:`gdn_update_xla` is the same arithmetic in ``jax.numpy``
(the CPU's path and the fallback). The two agree to float32 rounding: the
kernel adds the ``d_k`` products of a contraction in another order.
``ops.dispatch.gdn_update`` / ``.kda_update`` pick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of one grid step's float32 state block: in and out double-buffered
# four times this in VMEM, beside a pair of heads' temporaries.
MAX_BLOCK_BYTES = 1 << 20


def gdn_update_xla(state, q, k, v, alpha, beta):
    """``state (B, dk, H * dv)``; ``q``, ``k (B, H, dk)``, ``v (B, H, dv)``,
    ``beta (B, H)``, ``alpha (B, H)`` (a head) or ``(B, H, dk)`` (a key
    channel a head) float32 -> ``(new state (B, dk, H * dv), o (B, H *
    dv))``. A state kept in a narrower type is widened, updated in
    float32 and rounded as it is stored; ``o`` is of the unrounded one. The
    contractions are products and sums, not dots: exact float32 on any
    backend."""
    b, dk, width = state.shape
    h = q.shape[1]
    s = state.astype(jnp.float32).reshape(b, dk, h, width // h)
    s = s * (alpha[:, None, :, None] if alpha.ndim == 2
             else alpha.transpose(0, 2, 1)[..., None])  # (B, 1 | dk, H, 1)
    kc = k.transpose(0, 2, 1)[..., None]  # (B, dk, H, 1)
    u = beta[..., None] * (v - jnp.sum(s * kc, axis=1))
    new = s + kc * u[:, None]
    o = jnp.sum(new * q.transpose(0, 2, 1)[..., None], axis=1)
    return (new.reshape(b, dk, width).astype(state.dtype),
            o.reshape(b, width))


def head_blocks(heads: int, dk: int, dv: int):
    """``(heads a grid step, heads a group)``. A group is the fewest heads
    whose values are whole 128-lane vectors (two of 192): what the kernel
    slices out of a block and spreads ``k`` and ``q`` over. A block is the
    most groups that divide the heads and keep a float32 block under
    ``MAX_BLOCK_BYTES``. Where the heads are not whole groups the whole row
    is one block and one group."""
    group = 1
    while group * dv % 128:
        group += 1
    if heads % group:
        return heads, heads
    block = group
    for n in range(group, heads + 1, group):
        if heads % n == 0 and n * dv * dk * 4 <= MAX_BLOCK_BYTES:
            block = n
    return block, group


def _kernel(group, dv, per_channel, state_ref, q_ref, k_ref, v_ref,
            alpha_ref, beta_ref, new_ref, o_ref):
    heads = q_ref.shape[-1]
    kc, qc = k_ref[0, 0], q_ref[0, 0]  # (dk, heads of the block)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, group * dv), 1)

    def spread(cols, first):
        """A group's columns, each over its own head's ``dv`` lanes."""
        out = cols[:, first: first + 1]
        for i in range(1, group):
            out = jnp.where(lane >= i * dv, cols[:, first + i: first + i + 1],
                            out)
        return out

    for g in range(heads // group):
        at = slice(g * group * dv, (g + 1) * group * dv)
        ks, qs = spread(kc, g * group), spread(qc, g * group)
        s = state_ref[0, :, at].astype(jnp.float32)
        # The decay: a column a head like ``k``, or a row like ``beta``.
        s = s * (spread(alpha_ref[0, 0], g * group) if per_channel
                 else alpha_ref[0, :, at])
        u = beta_ref[0, :, at] * (
            v_ref[0, :, at] - jnp.sum(s * ks, axis=0, keepdims=True))
        new = s + ks * u
        new_ref[0, :, at] = new.astype(new_ref.dtype)
        o_ref[0, :, at] = jnp.sum(new * qs, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def gdn_update_pallas(state, q, k, v, alpha, beta, *,
                      name: str = "cgx_gdn_update", interpret: bool = False):
    """:func:`gdn_update_xla` as one Pallas kernel, ``name`` in the device's
    trace; the new state is written over ``state``'s buffer (donate it). A
    decay a head ``(B, H)`` is a row operand spread over the head's values
    like ``beta``; a decay a key channel ``(B, H, dk)`` a column operand
    like ``k``."""
    b, dk, width = state.shape
    h = q.shape[1]
    dv = width // h
    per_channel = alpha.ndim == 3
    block, group = head_blocks(h, dk, dv)
    n_blocks = h // block

    def cols(x):  # (B, H, dk) -> (B, blocks, dk, heads of a block)
        return x.reshape(b, n_blocks, block, dk).transpose(0, 1, 3, 2)

    def rows(per_head):  # (B, H) -> a row over the heads' values
        return jnp.repeat(per_head, dv, axis=-1)[:, None, :]

    row = pl.BlockSpec((1, 1, block * dv), lambda i, j: (i, 0, j),
                       memory_space=pltpu.VMEM)
    col = pl.BlockSpec((1, 1, dk, block), lambda i, j: (i, j, 0, 0),
                       memory_space=pltpu.VMEM)
    mat = pl.BlockSpec((1, dk, block * dv), lambda i, j: (i, 0, j),
                       memory_space=pltpu.VMEM)
    new, o = pl.pallas_call(
        functools.partial(_kernel, group, dv, per_channel),
        name=name,
        grid=(b, n_blocks),
        in_specs=[mat, col, col, row, col if per_channel else row, row],
        out_specs=[mat, row],
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((b, 1, width), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(state, cols(q), cols(k), v.reshape(b, 1, width),
      cols(alpha) if per_channel else rows(alpha), rows(beta))
    return new, o[:, 0]
