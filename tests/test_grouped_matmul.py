"""The experts' grouped product: ``ops/grouped_matmul.py``'s kernel
(interpret mode on the CPU) against ``jax.lax.ragged_dot`` and against a
plain loop over the groups, the rule that picks between them, and what the
rule leaves of ``dropless_moe``'s jaxpr where it keeps ``ragged_dot``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu.ops import dispatch, grouped_matmul as gm
from torch_cgx_tpu.parallel import moe
from torch_cgx_tpu.utils.logging import metrics


def _operands(seed, m, sizes, k, n, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)) * k ** -0.5,
                      dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _loop(lhs, rhs, sizes):
    """Group after group in float64; zero past the groups' end."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]))
    at = 0
    for g, size in enumerate(np.asarray(sizes)):
        out[at: at + size] = (np.asarray(lhs[at: at + size], np.float64)
                              @ np.asarray(rhs[g], np.float64))
        at += size
    return out


# name: (rows M, group sizes, row tile). K, N = 64, 32 and 32, 64 are the two
# expert cells' gate/up and down at their rehearsal widths.
CASES = {
    "empty groups between touched ones": (33, [3, 0, 0, 20, 1, 0, 7, 2], 16),
    "every row on one expert": (48, [0, 0, 48, 0, 0, 0], 16),
    "a group over several tiles": (64, [2, 50, 3, 9], 16),
    "starts off the tile": (32, [5, 7, 9, 11], 16),
    "rows past the groups' end": (64, [3, 0, 9, 1, 0, 7], 16),
    "M no multiple of the tile": (70, [3, 0, 0, 20, 1, 0, 40, 6], 16),
    "a tile larger than M": (37, [10, 0, 27], 128),
    "no row in any group": (40, [0, 0, 0, 0], 16),
    "the default tile": (300, [0, 131, 2, 0, 1, 129, 0, 30], gm.TILE),
}


@pytest.mark.parametrize("k,n", [(64, 32), (32, 64)])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_ragged_dot_and_a_loop_over_groups(case, k, n):
    """float32, limit 1e-5 of the largest value (the sums are taken in
    another order); past the groups' end the result is zero whatever those
    rows of ``lhs`` hold, NaN included."""
    m, sizes, tm = CASES[case]
    lhs, rhs, sizes = _operands(len(case), m, sizes, k, n)
    end = int(sizes.sum())
    lhs = lhs.at[end:].set(jnp.nan)
    got = np.asarray(gm.grouped_matmul_pallas(lhs, rhs, sizes, tm=tm,
                                              interpret=True))
    want = _loop(lhs, rhs, sizes)
    limit = 1e-5 * max(1.0, np.abs(want).max())
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < limit
    assert (got[end:] == 0.0).all()
    xla = np.asarray(gm.grouped_matmul_xla(lhs.at[end:].set(0.0), rhs, sizes))
    assert np.abs(got[:end] - xla[:end]).max(initial=0.0) < limit


@pytest.mark.parametrize("case", ["a group over several tiles",
                                  "rows past the groups' end"])
def test_kernel_in_bfloat16_rounds_once_as_ragged_dot_does(case):
    """bfloat16 operands and result, float32 sums: a bfloat16 step of the
    largest value at most from ``ragged_dot``'s."""
    m, sizes, tm = CASES[case]
    lhs, rhs, sizes = _operands(3, m, sizes, 64, 32, jnp.bfloat16)
    got = gm.grouped_matmul_pallas(lhs, rhs, sizes, tm=tm, interpret=True)
    want = gm.grouped_matmul_xla(lhs, rhs, sizes)
    assert got.dtype == jnp.bfloat16
    end = int(sizes.sum())
    gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[:end]
    assert float(gap.max()) <= 2.0 ** -7 * float(jnp.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(CASES))
def test_column_blocks_equal_ragged_dot(case, dtype, monkeypatch):
    """A matrix over ``MAX_BLOCK_BYTES`` (here 64 x 384 against a limit of
    one 64 x 128 float32 block: three column blocks of 128, the work list walked
    once a block) through every case of groups: empty ones, groups that share
    a tile, that end mid-tile, rows past the end. ``K`` is whole in every
    block, so there is one sum an element: float32 within 1e-5 of the largest
    value of ``ragged_dot``'s and the loop's (the order of a sum is the
    backend's), bfloat16 the same bits as ``ragged_dot`` rounds to."""
    monkeypatch.setattr(gm, "MAX_BLOCK_BYTES", 64 * 128 * 4)
    m, sizes, tm = CASES[case]
    k, n = 64, 384
    assert gm.column_block(k, n, jnp.dtype(dtype).itemsize) == 128
    lhs, rhs, sizes = _operands(len(case), m, sizes, k, n, dtype)
    end = int(sizes.sum())
    lhs = lhs.at[end:].set(jnp.nan)
    # The function under the jit: a trace cached at another block limit is
    # not this one.
    got = np.asarray(gm.grouped_matmul_pallas.__wrapped__(
        lhs, rhs, sizes, tm=tm, interpret=True), np.float32)
    xla = np.asarray(gm.grouped_matmul_xla(lhs.at[end:].set(0.0), rhs, sizes),
                     np.float32)
    assert np.isfinite(got).all() and (got[end:] == 0.0).all()
    if dtype == jnp.bfloat16:
        one_block = np.asarray(gm.grouped_matmul_pallas.__wrapped__(
            lhs, rhs[:, :, :128], sizes, tm=tm, interpret=True), np.float32)
        np.testing.assert_array_equal(got[:, :128], one_block)
        step = 2.0 ** -7 * max(1.0, np.abs(xla).max())
        assert np.abs(got[:end] - xla[:end]).max(initial=0.0) <= step
    else:
        want = _loop(lhs, rhs, sizes)
        limit = 1e-5 * max(1.0, np.abs(want).max())
        assert np.abs(got - want).max() < limit
        assert np.abs(got[:end] - xla[:end]).max(initial=0.0) < limit


def test_work_list_names_every_touched_tile_once_and_no_other():
    sizes = jnp.asarray([3, 0, 0, 20, 1, 0, 40, 6], jnp.int32)
    group, tile, bounds, items = gm.work_list(sizes, 70, 16)
    items = int(items)
    got = list(zip(np.asarray(group)[:items], np.asarray(tile)[:items]))
    want, at = [], 0
    for g, size in enumerate(np.asarray(sizes)):
        if size:
            want += [(g, t) for t in range(at // 16, (at + size - 1) // 16 + 1)]
        at += size
    assert got == want
    assert list(np.asarray(bounds)) == [0, 3, 3, 3, 23, 24, 24, 64, 70]
    assert len(group) == 5 + 8 - 1  # tiles + groups - 1: the most items


def test_the_rule_is_the_block_and_the_mean_group():
    # The two cells' four programs, gate/up and down: all the kernel's.
    for m, e, k, n in [(1024, 128, 2560, 768), (1024, 128, 768, 2560),
                       (4096, 128, 2560, 768), (256, 256, 2048, 768),
                       (24576, 256, 768, 2048)]:
        assert gm.takes_kernel(m, e, k, n)
    assert gm.takes_kernel(512 * 256, 256, 2048, 768)
    assert not gm.takes_kernel(512 * 256 + 1, 256, 2048, 768)  # not timed
    # A 16 MiB matrix goes in column blocks of 8 MiB, Trinity's 18 MiB one
    # in three of 6 MiB; a matrix of which 128 columns are no block is refused.
    assert gm.takes_kernel(1024, 128, 4096, 2048)
    assert gm.column_block(4096, 2048) == 1024
    assert gm.takes_kernel(256, 32, 3072, 3072)
    assert gm.column_block(3072, 3072) == 1024
    assert not gm.takes_kernel(512 * 32 + 1, 32, 3072, 3072)
    assert gm.column_block(2560, 768) == 768  # one block, as before
    assert gm.column_block(1 << 16, 256) is None
    assert not gm.takes_kernel(1024, 128, 1 << 16, 256)
    assert gm.takes_kernel(1024, 128, 2048, 1024, itemsize=4)
    assert gm.column_block(2560, 1024, itemsize=4) == 512  # 10 MiB: two


@pytest.mark.parametrize("impl,on_tpu,lowering", [
    ("pallas", False, "pallas"), ("xla", True, "xla"), ("auto", False, "xla"),
    ("auto", True, "pallas"),
])
def test_dispatch_counts_its_lowering(monkeypatch, impl, on_tpu, lowering):
    """``ops.dispatch.grouped_matmul`` is dispatched as the codec is
    (``CGX_CODEC_IMPL``; off the TPU ``auto`` is ``ragged_dot``) and counts
    the call site by lowering. Traced alone: the compiled kernel needs the
    chip."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: on_tpu)
    metrics.reset()
    lhs, rhs, sizes = _operands(0, 48, [10, 0, 30, 8], 64, 32)
    # A function of its own: a trace is cached by the function traced.
    text = str(jax.make_jaxpr(lambda *ops: dispatch.grouped_matmul(*ops))(
        lhs, rhs, sizes))
    assert ("cgx_grouped_matmul" in text) == (lowering == "pallas")
    assert ("ragged_dot" in text) == (lowering == "xla")
    assert metrics.get(f"cgx.codec.lowering.grouped_matmul.{lowering}") == 1
    other = "xla" if lowering == "pallas" else "pallas"
    assert metrics.get(f"cgx.codec.lowering.grouped_matmul.{other}") == 0


def _moe_operands(t, d, e, f):
    rng = np.random.default_rng(e)
    arr = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * 0.3, jnp.float32)
    return arr(t, d), arr(d, e), arr(e), arr(e, d, f), arr(e, d, f), arr(e, f, d)


def test_dropless_moe_is_the_parents_jaxpr_where_the_rule_keeps_ragged_dot(
        monkeypatch):
    """On the chip's path, groups larger than any the kernel was timed at
    stay on ``ragged_dot``: three ``.xla`` call sites, and the jaxpr of the
    layer written with ``jax.lax.ragged_dot`` in the product's place, as the
    parent had it. One row fewer a group and the three products are the
    kernel's."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "auto")
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setattr(gm, "MAX_GROUP_ROWS", 4)
    kw = dict(top_k=2, scale=2.5, dtype=jnp.bfloat16)

    def jaxpr(ops):  # of a function of its own: traces are cached by it
        return str(jax.make_jaxpr(lambda *o: moe.dropless_moe(*o, **kw))(*ops))

    def count(lowering):
        return metrics.get(f"cgx.codec.lowering.grouped_matmul.{lowering}")

    over = _moe_operands(9, 16, 4, 8)  # 18 rows on 4 experts: 4.5 a group
    metrics.reset()
    kept = jaxpr(over)
    assert (count("xla"), count("pallas")) == (3, 0)
    with monkeypatch.context() as mp:
        mp.setattr(dispatch, "grouped_matmul", jax.lax.ragged_dot)
        parents = jaxpr(over)
    assert kept == parents and "cgx_grouped_matmul" not in kept

    under = _moe_operands(8, 16, 4, 8)  # 16 rows on 4 experts: 4 a group
    metrics.reset()
    taken = jaxpr(under)
    assert (count("xla"), count("pallas")) == (0, 3)
    assert taken.count("cgx_grouped_matmul") == 3 and "ragged_dot" not in taken


def test_held_rows_read_zero_through_the_kernel(monkeypatch):
    """A share of the experts under ``CGX_CODEC_IMPL=pallas``: the rows of
    assignments held elsewhere lie past the groups' end, the kernel never
    visits them, and the layer's result there is zero and finite."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    y, router, bias, gate, up, down = _moe_operands(24, 16, 8, 8)
    y = y * 10.0  # scores that differ by more than the bias: rows route apart
    gate, up, down = gate[4:], up[4:], down[4:]
    out, st = moe.dropless_moe(y, router, bias, gate, up, down,
                               top_k=2, scale=1.0, dtype=jnp.float32, held=4)
    idx, _ = moe.sigmoid_topk_route(y, router, bias, top_k=2, scale=1.0)
    untouched = ~np.asarray(idx >= 4).any(axis=1)
    assert untouched.any() and not untouched.all()
    assert bool(jnp.all(out[untouched] == 0.0))
    assert bool(jnp.all(jnp.isfinite(out)))
    monkeypatch.setenv("CGX_CODEC_IMPL", "xla")
    want, st_x = moe.dropless_moe(y, router, bias, gate, up, down, top_k=2,
                                  scale=1.0, dtype=jnp.float32, held=4)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
    assert np.array_equal(np.asarray(st), np.asarray(st_x))


@pytest.mark.tpu  # the compiled Mosaic kernel at the published widths
@pytest.mark.parametrize("m,e,k,n,routed", [
    (1024, 128, 2560, 768, 512), (1024, 128, 768, 2560, 512),
    (256, 256, 2048, 768, 256),
    # Trinity's 18 MiB matrix in three column blocks: a decode step's one
    # row a held group, a 4k prompt's 64.
    (256, 32, 3072, 3072, 256), (16384, 32, 3072, 3072, 256),
])
def test_grouped_matmul_tpu(m, e, k, n, routed):
    """A decode step's product of an expert cell, bit for bit
    ``ragged_dot``'s: the whole of ``K`` is in every block, so both take one
    float32 sum and round it once."""
    rng = np.random.default_rng(40)
    flat = rng.integers(0, routed, size=m)
    sizes = jnp.asarray(np.bincount(np.where(flat < e, flat, e),
                                    minlength=e + 1)[:e], jnp.int32)
    key = jax.random.PRNGKey(40)
    lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(key, (e, k, n), jnp.bfloat16) * jnp.bfloat16(
        k ** -0.5)
    got = gm.grouped_matmul_pallas(lhs, rhs, sizes)
    want = gm.grouped_matmul_xla(lhs, rhs, sizes)
    end = int(sizes.sum())
    assert bool(jnp.all(got[:end] == want[:end]))
    assert bool(jnp.all(got[end:] == 0))
