"""The one-step update of a state-space layer's recurrent state, all lanes.

A Mamba-2 layer keeps, a lane, ``h (d_state, d_inner)`` float32 (the state
dimension down the sublanes, the heads' channels along the lanes:
``models/granite_hybrid.py``) and a decode step rewrites it whole::

    h' = decay * h + B (outer) dtx          decay, dtx: rows over d_inner
    y  = sum over d_state of h' * C         B, C: columns over d_state

At 64 lanes and granite-4.0-h-micro's 128 x 4,096 that is 134 MB read and
134 MB written a layer, 36 layers a step: more bytes than the weights, and a
buffer that has to be updated where it lies (two copies of it do not fit
the chip). :func:`ssm_update_pallas` is one kernel, ``cgx_ssm_update``, a
grid step a ``(d_state, tile)`` block of one lane's state, the state
aliased in place; :func:`ssm_update_xla` is the same arithmetic in
``jax.numpy`` (the CPU's path, the fallback, and what XLA fuses by itself
under a donated state). The two agree to float32 rounding: the kernel adds
the ``d_state`` products of ``y`` in another order.
``ops.dispatch.ssm_update`` picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lanes of one grid step's block: (128, 1024) float32 is 512 KiB, in and
# out double-buffered 2 MiB of VMEM.
MAX_TILE = 1024


def ssm_update_xla(state, decay, dtx, bm, cm):
    """``state (B, N, W)``, ``decay``, ``dtx (B, W)``, ``bm``, ``cm (B, N)``
    float32 -> ``(new state (B, N, W), y (B, W))``. A state kept in a
    narrower type is widened, updated in float32 and rounded as it is
    stored; ``y`` is of the unrounded one."""
    new = (state.astype(jnp.float32) * decay[:, None, :]
           + bm[:, :, None] * dtx[:, None, :])
    return new.astype(state.dtype), jnp.sum(new * cm[:, :, None], axis=1)


def lane_tile(width: int) -> int:
    """Lanes a grid step takes of a ``width``-wide state: the whole row
    where it is not whole 128-lane vectors, else the largest halving of it
    that is at most ``MAX_TILE`` and still whole vectors."""
    tile = width
    while tile > MAX_TILE and tile % 256 == 0:
        tile //= 2
    return tile


def _kernel(state_ref, decay_ref, dtx_ref, b_ref, c_ref, new_ref, y_ref):
    new = (state_ref[0].astype(jnp.float32) * decay_ref[0]
           + b_ref[0] * dtx_ref[0])  # (N, tile)
    new_ref[0] = new.astype(new_ref.dtype)
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update_pallas(state, decay, dtx, bm, cm, *, interpret: bool = False):
    """:func:`ssm_update_xla` as one Pallas kernel; the new state is
    written over ``state``'s buffer (donate it)."""
    b, n, width = state.shape
    tile = lane_tile(width)
    row = pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j),
                       memory_space=pltpu.VMEM)
    col = pl.BlockSpec((1, n, 1), lambda i, j: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    block = pl.BlockSpec((1, n, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM)
    new, y = pl.pallas_call(
        _kernel,
        name="cgx_ssm_update",
        grid=(b, width // tile),
        in_specs=[block, row, row, col, col],
        out_specs=[block, row],
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((b, 1, width), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(state, decay[:, None, :], dtx[:, None, :], bm[:, :, None],
      cm[:, :, None])
    return new, y[:, 0]
