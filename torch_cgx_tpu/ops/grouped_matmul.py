"""The grouped product of an expert layer: rows sorted by expert times each
expert's own matrix.

``parallel.moe.dropless_moe`` sorts a step's assignments by expert and
multiplies three times a layer::

    out[r] = lhs[r] @ rhs[g]      for the rows r of group g,
                                  groups laid end to end by ``sizes (E,)``

A decode step hands it two rows an expert, so the work is reading the
touched experts' matrices and nothing else (3.9 MB for 2 x 2560 x 768 x 2
FLOPs). :func:`grouped_matmul_pallas` is one kernel, ``cgx_grouped_matmul``:
a grid step an item of a work list built from ``sizes`` on the device, an
item a (touched expert, row tile) pair, the weight block the expert's whole
``(K, N)`` matrix, fetched by the item's expert id: an expert nobody chose
is never read, and a step's copy is one contiguous run of megabytes behind
which the step's fixed cost disappears. A matrix over ``MAX_BLOCK_BYTES``
(Trinity's 3072 x 3072, 18 MiB) goes in column blocks ``(K, N / blocks)``
(:func:`column_block`): the work list is walked once a block, ``K`` still
whole. :func:`grouped_matmul_xla` is
``jax.lax.ragged_dot`` (the CPU's path, the fallback, and the form for
shapes the kernel was not timed at: :func:`takes_kernel`). Both multiply
``lhs`` and ``rhs`` as they are given, accumulate in float32 and round once
to ``lhs``'s type; on the chip they agree bit for bit (the whole of ``K`` is
in every block, so there is one sum). Rows past the groups' end read zero.
``ops.dispatch.grouped_matmul`` picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The largest weight block a grid step takes, double-buffered beside the
# rows' tile and the float32 product: under the limit below with room.
MAX_BLOCK_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 48 << 20
# The same for a matrix in column blocks. Its buffers need 15 MiB; what a
# custom call asks for is what XLA leaves out of its own plan for VMEM across
# the whole program, and with 48 MiB asked XLA tiled one softmax fusion of
# Trinity's decode step degenerately (3.14 ms for 0.17: the step came out
# slower than under ``ragged_dot``). From 64 MiB on it does not (PERF.md
# section 6, PR 45, has the compiled text's mark to look for).
BLOCKED_VMEM_LIMIT_BYTES = 64 << 20
# Rows a tile. On the chip every tile from 32 to 256 rows read the touched
# matrices at the same rate, at 2 rows a group and at 96 (16 rows 1-6 %
# slower, 512 rows 15-40 %): one tile for every shape, the matrix unit's.
TILE = 128
# The largest mean group the kernel was timed at (and won): past it the
# product is the parent's.
MAX_GROUP_ROWS = 512


def grouped_matmul_xla(lhs, rhs, sizes):
    """``lhs (M, K)``, ``rhs (E, K, N)``, ``sizes (E,)`` int32 -> ``(M, N)``
    of ``lhs``'s type: ``jax.lax.ragged_dot``."""
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def column_block(k: int, n: int, itemsize: int = 2):
    """Columns of the weight block ``(K, bn)`` a grid step takes of a ``(K,
    N)`` matrix: all of ``N`` where the matrix is at most
    ``MAX_BLOCK_BYTES``, else the widest whole-lane (128) divisor of ``N``
    whose block is; None where not even 128 columns fit."""
    if k * n * itemsize <= MAX_BLOCK_BYTES:
        return n
    fits = [bn for bn in range(128, n, 128)
            if n % bn == 0 and k * bn * itemsize <= MAX_BLOCK_BYTES]
    return max(fits, default=None)


def takes_kernel(m: int, e: int, k: int, n: int, itemsize: int = 2) -> bool:
    """Whether the product of ``(M, K)`` rows and ``(E, K, N)`` matrices is
    the kernel's: a group's matrix is one block or whole column blocks
    (:func:`column_block`)
    and the mean group, ``M / E``, is one the kernel was timed at. Measured
    on a v5e at 2560 x 768 and 2048 x 768 bfloat16 (``tools/
    bench_grouped_matmul.py``, PERF.md section 6, PR 40): the kernel reads
    the touched matrices at 670-690 GB/s where ``ragged_dot`` reads them at
    215-340 (2 rows a group), and is 1.8-2.7 times faster at 64-96 rows a
    group, 2.0 at 256 and 1.3-1.8 at 512: no crossover up to there, and
    nothing timed past it."""
    return (column_block(k, n, itemsize) is not None
            and m <= MAX_GROUP_ROWS * e)


def work_list(sizes, m: int, tm: int):
    """The kernel's items from ``sizes (E,)``, each ``(most items,)`` int32:
    ``(group, tile)`` of every item, and beside them the groups' bounds
    ``(E + 1,)`` and the number of items. A group of ``n > 0`` rows is an
    item for every ``tm``-row tile it has a row in, in order; an empty group
    is none. Entries past the last item are not to be read."""
    e = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = jnp.cumsum(tiles)
    # A tile is shared by the groups that meet in it: one item more a group
    # than the tiles there are, at most.
    item = jnp.arange(pl.cdiv(m, tm) + e - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(item_ends, item, side="right",
                         method="compare_all"), e - 1
    ).astype(jnp.int32)
    tile = (first + tiles - item_ends)[group] + item
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, bounds, item_ends[-1]


def _kernel(tm, axis, group_ref, tile_ref, bounds_ref, lhs_ref, rhs_ref,
            out_ref):
    i = pl.program_id(axis)  # the item; axis 0 is the column block, if any
    g, t = group_ref[i], tile_ref[i]
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
    got = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32).astype(out_ref.dtype)
    # The tile's first item owns what the tile holds of no group: zero.
    fresh = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

    @pl.when(fresh)
    def _():
        out_ref[...] = jnp.where(mine, got, jnp.zeros_like(got))

    @pl.when(jnp.logical_not(fresh))
    def _():
        out_ref[...] = jnp.where(mine, got, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul_pallas(lhs, rhs, sizes, *, tm: int = TILE,
                          interpret: bool = False):
    """:func:`grouped_matmul_xla` as one Pallas kernel over tiles of ``tm``
    rows (all of them where there are fewer). The grid is as long as the
    work list, so a tile no group has a row in is never visited: the rows
    past the groups' end are zeroed outside the kernel, where XLA fuses it
    into whatever reads them."""
    m, k = lhs.shape
    e, _, n = rhs.shape
    tm = min(tm, m)
    bn = column_block(k, n, rhs.dtype.itemsize)
    group, tile, bounds, items = work_list(sizes, m, tm)
    # One column block: the grid is the work list. Several: the list is
    # walked once a block (the block outermost, so that the items of a tile
    # follow one another and the tile's output stays where it is).
    grid = (items,) if bn == n else (n // bn, items)

    def block(shape, at):
        """A block fetched at ``at(item, column block, groups, tiles)``."""
        if bn == n:
            return pl.BlockSpec(shape, lambda i, gr, ti, bo: at(i, 0, gr, ti))
        return pl.BlockSpec(shape, lambda j, i, gr, ti, bo: at(i, j, gr, ti))

    out = pl.pallas_call(
        functools.partial(_kernel, tm, len(grid) - 1),
        name="cgx_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                block((tm, k), lambda i, j, gr, ti: (ti[i], 0)),
                block((None, k, bn), lambda i, j, gr, ti: (gr[i], 0, j)),
            ],
            out_specs=block((tm, bn), lambda i, j, gr, ti: (ti[i], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=(VMEM_LIMIT_BYTES if bn == n
                              else BLOCKED_VMEM_LIMIT_BYTES),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(e * k * n + m * k + m * n) * lhs.dtype.itemsize,
        ),
        interpret=interpret,
    )(group, tile, bounds, lhs, rhs)
    live = jnp.arange(m, dtype=jnp.int32)[:, None] < bounds[-1]
    return jnp.where(live, out, jnp.zeros_like(out))
