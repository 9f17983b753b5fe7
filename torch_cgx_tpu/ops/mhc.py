"""The read side of a manifold-constrained hyper-connection, a tile of
tokens at a time.

A hyper-connected model carries ``n`` residual streams a token, ``X (n,
D)``, and every sublayer ``F`` reads one mix of them and writes back into a
mix of them (``models/mhc.py`` has the whole layer). What is computed before
``F`` runs, from the flattened streams ``x^ = vec(X) (nD,)`` and the
sublayer's own ``phi (nD, 2n + n^2)``, ``alpha (3,)`` and ``base (2n +
n^2,)``, all float32::

    m      = (x^ phi) * rsqrt(mean(x^^2) + rms_eps)
    H_pre  = sigmoid(alpha_0 m[0:n]  + base[0:n]) + eps          (n,)
    H_post = 2 sigmoid(alpha_1 m[n:2n] + base[n:2n])             (n,)
    A      = clip(alpha_2 m[2n:] + base[2n:], lo, hi)            (n, n)
    M      = softmax of each row of A
    iters times:  M <- M / (its rows' sums + eps), then
                  M <- M / (its columns' sums + eps)
    H_res  = M
    u      = sum_i H_pre[i] X[i]                                 (D,)

:func:`mhc_pre_xla` is that in ``jax.numpy`` (the CPU's path and the form
for shapes the kernel does not take: :func:`token_tile`), the product with
``phi`` at full precision. XLA reads the streams three times for it (the
product, the sum of squares, ``u``). :func:`mhc_pre_pallas` is one kernel
that reads a tile of tokens' streams once. The product is one pass of the
matrix unit and still float32: ``phi`` is handed over as three bfloat16
pieces that add up to it (:func:`split_bf16`), laid as rows of ``phi^T``
(:func:`kernel_phi`: made once, where the parameters are taken, not in the
call: a weight is a program's argument, so XLA would make the pieces again
in every call), so that the tokens come out on the lane axis; streams in
bfloat16 are one piece, exact as they are, others three. From there every one of the ``2n +
n^2`` mixes is a ``(1, tokens)`` row and a Sinkhorn iteration is arithmetic
written out over the ``n^2`` rows: no ``(..., n, n)`` array ever lies in a
tiled layout. ``u`` wants ``H_pre`` a token a sublane; a row is turned
into a column by a select against the identity and a sum
(:func:`_turned`), which is some hundredths of the tile's work.

A hyper-connection with no ``H_post`` and ``H_res`` (the read-out in front
of the final norm: ``phi (nD, n)``, ``alpha (1,)``, ``base (n,)``) is the
same call with ``mixes=False``.

``H_post`` and ``H_res`` are returned tokens last, ``(n, T)`` and ``(n^2,
T)`` (row ``i n + j`` is ``H_res[i, j]``): the layout the kernel writes
without a transpose; ``models/mhc.mix`` takes them so. The two forms agree to
float32 rounding (the kernel's product adds in another order).
``ops.dispatch.mhc_pre`` picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a grid step takes. A tile of four 3,584-wide bfloat16 streams is
# 7 MiB, double-buffered beside ``phi``'s pieces (2 MiB) and ``u``.
TILE = 256
VMEM_LIMIT_BYTES = 64 << 20
PIECES = 3  # bfloat16 pieces of a float32: 24 bits of mantissa


def split_bf16(v, pieces: int = PIECES):
    """``v`` float32 as ``pieces`` bfloat16 arrays that add up to it (to 8
    bits of mantissa a piece), stacked on a new leading axis."""
    out, rest = [], v.astype(jnp.float32)
    for _ in range(pieces):
        piece = rest.astype(jnp.bfloat16)
        out.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return jnp.stack(out)


def kernel_phi(phi):
    """``phi (n D, K)`` float32 as the kernel reads it: its transpose in
    :data:`PIECES` bfloat16 pieces, each padded to whole sublanes of rows,
    one under the other: ``(PIECES * rows, n D)``."""
    k = phi.shape[1]
    rows = -(-k // 8) * 8
    phi_t = jnp.pad(split_bf16(phi.T), ((0, 0), (0, rows - k), (0, 0)))
    return phi_t.reshape(PIECES * rows, phi.shape[0])


def token_tile(tokens: int):
    """Tokens a grid step of the kernel takes of ``tokens``: ``TILE`` where
    it divides them, all of them where they are whole sublanes and no more
    than a tile (a decode step's lanes); None for a shape that is the
    ``jax.numpy`` form's (a single token: the prefill's read-out)."""
    if tokens % TILE == 0:
        return TILE
    if tokens % 8 == 0 and tokens < TILE:
        return tokens
    return None


def _coefficients(m, alpha, base, n, iters, eps, clamp, mixes, row):
    """The mixes from ``m``, whose entry ``k`` is ``row(m, k)`` (any shape
    the arithmetic broadcasts over): ``(H_pre [n], H_post [n], H_res
    [n][n])`` as lists of such entries, the last two None without
    ``mixes``. An iteration of the Sinkhorn is written out over the ``n^2``
    entries; the iterations are a loop (twenty of them unrolled thirteen
    times a program are most of its text, and no faster)."""
    def mix(k, scale):
        return scale * row(m, k) + base[k]

    h_pre = [jax.nn.sigmoid(mix(i, alpha[0])) + eps for i in range(n)]
    if not mixes:
        return h_pre, None, None
    h_post = [2.0 * jax.nn.sigmoid(mix(n + i, alpha[1])) for i in range(n)]
    a = [[jnp.clip(mix(2 * n + i * n + j, alpha[2]), *clamp)
          for j in range(n)] for i in range(n)]
    # Softmax of each row, its largest taken off first.
    top = [functools.reduce(jnp.maximum, r) for r in a]
    mat = [[jnp.exp(v - t) for v in r] for r, t in zip(a, top)]
    mat = [[v / sum(r) for v in r] for r in mat]

    def sinkhorn(_, mat):
        mat = [[v / (sum(r) + eps) for v in r] for r in mat]
        cols = [sum(r[j] for r in mat) + eps for j in range(n)]
        return [[v / c for v, c in zip(r, cols)] for r in mat]

    return h_pre, h_post, jax.lax.fori_loop(0, iters, sinkhorn, mat)


def mhc_pre_xla(x, phi, alpha, base, *, n: int, iters: int, eps: float,
                clamp, rms_eps: float, mixes: bool = True):
    """``x (T, n * D)`` (the streams of a token flattened, stream ``i`` its
    columns ``i D`` to ``(i + 1) D``), ``phi (n D, K)``, ``alpha``, ``base
    (K,)`` float32 -> ``(u (T, D)`` of ``x``'s type, ``H_post (n, T)``,
    ``H_res (n * n, T))`` float32, the last two None without ``mixes``."""
    t = x.shape[0]
    xf = x.astype(jnp.float32)
    m = jnp.matmul(xf, phi, precision=jax.lax.Precision.HIGHEST)
    m = m * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + rms_eps)
    h_pre, h_post, h_res = _coefficients(
        m, alpha, base, n, iters, eps, clamp, mixes, lambda m, k: m[:, k])
    streams = xf.reshape(t, n, -1)
    u = sum(h[:, None] * streams[:, i] for i, h in enumerate(h_pre))
    u = u.astype(x.dtype)
    if not mixes:
        return u, None, None
    return (u, jnp.stack(h_post),
            jnp.stack([v for r in h_res for v in r]))


def _turned(v, eye, to_column: bool):
    """A ``(1, T)`` row as a ``(T, 1)`` column, or back: what lies on the
    diagonal ``eye (T, T)`` of its broadcast, summed the other way."""
    return jnp.sum(jnp.where(eye, v, 0.0), axis=1 if to_column else 0,
                   keepdims=True)


def _kernel(n, rows, iters, eps, clamp, rms_eps, mixes, x_ref, phi_ref,
            alpha_ref, base_ref, u_ref, *mix_refs):
    t, d = u_ref.shape
    exact = x_ref.dtype == jnp.bfloat16
    eye = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
    m, squares = None, None
    for i in range(n):  # a stream at a time: the float32 copy stays small
        xi = x_ref[:, i * d:(i + 1) * d]
        xf = xi.astype(jnp.float32)
        ss = jnp.sum(xf * xf, axis=-1, keepdims=True)
        squares = ss if squares is None else squares + ss
        pieces = [xi] if exact else list(split_bf16(xf))
        for piece in pieces:
            # phi^T's rows times the tokens' rows: (pieces * rows, T).
            part = jax.lax.dot_general(
                phi_ref[:, i * d:(i + 1) * d], piece,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = part if m is None else m + part
    m = sum(m[p * rows:(p + 1) * rows] for p in range(PIECES))  # (rows, T)
    scale = jax.lax.rsqrt(_turned(squares, eye, False) / (n * d) + rms_eps)
    m = m * scale
    alpha = [alpha_ref[k] for k in range(alpha_ref.shape[0])]
    base = [base_ref[k] for k in range(base_ref.shape[0])]
    h_pre, h_post, h_res = _coefficients(
        m, alpha, base, n, iters, eps, clamp, mixes,
        lambda m, k: m[k:k + 1])
    u = None
    for i, h in enumerate(h_pre):
        term = _turned(h, eye, True) * x_ref[:, i * d:(i + 1) * d].astype(
            jnp.float32)
        u = term if u is None else u + term
    u_ref[...] = u.astype(u_ref.dtype)
    if mixes:
        post_ref, res_ref = mix_refs
        for i, h in enumerate(h_post):
            post_ref[i:i + 1] = h
        for k, v in enumerate(v for r in h_res for v in r):
            res_ref[k:k + 1] = v


@functools.partial(
    jax.jit,
    static_argnames=("n", "iters", "eps", "clamp", "rms_eps", "mixes",
                     "name", "interpret"),
)
def mhc_pre_pallas(x, phi_t, alpha, base, *, n: int, iters: int, eps: float,
                   clamp, rms_eps: float, mixes: bool = True,
                   name: str = "cgx_mhc_pre", interpret: bool = False):
    """:func:`mhc_pre_xla` as one Pallas kernel, ``name`` in the device's
    trace, over tiles of :func:`token_tile` tokens; ``phi_t`` is
    :func:`kernel_phi` of ``phi``."""
    t, width = x.shape
    d = width // n
    tile = token_tile(t)
    rows = phi_t.shape[0] // PIECES
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs = [pl.BlockSpec((tile, d), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((t, d), x.dtype)]
    if mixes:
        out_specs += [pl.BlockSpec((n, tile), lambda i: (0, i)),
                      pl.BlockSpec((n * n, tile), lambda i: (0, i))]
        out_shape += [jax.ShapeDtypeStruct((n, t), jnp.float32),
                      jax.ShapeDtypeStruct((n * n, t), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kernel, n, rows, iters, eps, clamp, rms_eps,
                          mixes),
        name=name,
        grid=(t // tile,),
        in_specs=[
            pl.BlockSpec((tile, width), lambda i: (i, 0)),
            pl.BlockSpec((PIECES * rows, width), lambda i: (0, 0)),
            smem, smem,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * width * (PIECES * rows + 2),
            transcendentals=t * (2 * n + n * n),
            bytes_accessed=(t * (width + d) * x.dtype.itemsize
                            + PIECES * rows * width * 2
                            + t * (n + n * n) * 4 * int(mixes)),
        ),
        interpret=interpret,
    )(x, phi_t, alpha, base)
    return tuple(out) if mixes else (out[0], None, None)
