"""Manifold-constrained hyper-connections: ``n`` residual streams a token
in place of one, mixed around every sublayer by coefficients computed from
the streams themselves, the stream-to-stream mix held to the doubly
stochastic matrices by Sinkhorn's iterations (DeepSeek's "mHC", over
Hyper-Connections, arXiv:2409.19606).

The embedding is repeated into the streams, ``X_0[i] = e``
(:func:`spread`). A sublayer ``F`` with its RMSNorm, where a plain block
computes ``x + F(norm(x))``, has a set ``hc = {phi (nD, 2n + n^2), alpha
(3,), base (2n + n^2,)}`` of its own, float32, and computes::

    u, H_post, H_res = pre(X)        ops/mhc.py has the equations
    y  = F(RMSNorm_w(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y            (:func:`mix`)

In front of the final norm the streams are read out by a fourth kind of
set, ``{phi (nD, n), alpha (1,), base (n,)}``: ``x = sum_i (sigmoid(alpha
m + base) + eps)[i] X[i]`` (:func:`read_out`), the ``pre`` half alone.

Coefficients are float32 whatever the streams' type. :func:`pre` and
:func:`read_out` go through ``ops.dispatch.mhc_pre`` (one kernel that reads
the streams once, where the kernels run); :func:`mix` is one elementwise
pass that XLA fuses: it reads ``X`` and ``y`` and writes ``X'``.

The kernel reads ``phi`` as bfloat16 pieces of its transpose; an adapter
makes them once, when it takes the parameters (:func:`with_kernel_phi`: a
leaf ``phi_t`` beside every ``phi``), so that no call makes them again.

A config that carries hyper-connections has ``hc_mult`` (``n``),
``hc_sinkhorn_iters``, ``hc_eps``, ``hc_clamp (lo, hi)`` and ``eps`` (the
RMSNorm's).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops import dispatch
from ..ops.mhc import kernel_phi

DECODE_KERNEL = "cgx_mhc_pre_decode"
PREFILL_KERNEL = "cgx_mhc_pre_prefill"


def spread(x, n: int):
    """``x (B, S, D)`` repeated into ``n`` streams ``(B, S, n, D)``."""
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (n,) + x.shape[2:])


def with_kernel_phi(params):
    """``params`` with, in every set that has a ``phi``, ``phi_t`` as the
    kernel reads it (``ops.mhc.kernel_phi``) beside it; every other leaf,
    and a set that has its ``phi_t`` already, as it is (a program is handed
    the adapter's tree and builds an adapter around it again: nothing is
    made a second time, inside the program)."""
    if not isinstance(params, dict):
        return params
    out = {k: with_kernel_phi(v) for k, v in params.items()}
    if "phi" in out and "phi_t" not in out:
        out["phi_t"] = kernel_phi(out["phi"])
    return out


def _pre(cfg, streams, hc, kernel, mixes):
    b, s, n, d = streams.shape
    u, h_post, h_res = dispatch.mhc_pre(
        streams.reshape(b * s, n * d), hc["phi"], hc["alpha"], hc["base"],
        kernel=kernel, phi_t=hc.get("phi_t"), n=n,
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        clamp=tuple(cfg.hc_clamp), rms_eps=cfg.eps, mixes=mixes,
    )
    return u.reshape(b, s, d), h_post, h_res


def pre(cfg, streams, hc, kernel: str):
    """``streams (B, S, n, D)`` -> what the sublayer reads, ``u (B, S,
    D)``, and the mixes for :func:`mix`, tokens last: ``H_post (n, B S)``,
    ``H_res (n n, B S)`` float32."""
    return _pre(cfg, streams, hc, kernel, True)


def read_out(cfg, streams, hc, kernel: str):
    """``streams (B, S, n, D)`` -> the one stream ``(B, S, D)`` the final
    norm takes."""
    return _pre(cfg, streams, hc, kernel, False)[0]


def mix(streams, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in float32, rounded
    once to the streams' type: ``streams (B, S, n, D)``, ``y (B, S, D)``,
    ``h_post``, ``h_res`` as :func:`pre` returns them."""
    b, s, n, d = streams.shape
    xf = streams.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    post = h_post.reshape(n, b, s, 1)
    res = h_res.reshape(n, n, b, s, 1)
    out = [
        sum(res[i, j] * xf[:, :, j] for j in range(n)) + post[i] * yf
        for i in range(n)
    ]
    return jnp.stack(out, axis=2).astype(streams.dtype)


def res_error(h_res, n: int, count_mask=None):
    """The largest distance of a row's or a column's sum of ``H_res (n n,
    T)`` from 1 over the tokens ``count_mask (T,)`` keeps (all of them
    without one): a float32 scalar."""
    mat = h_res.reshape(n, n, -1)
    off = jnp.maximum(jnp.max(jnp.abs(jnp.sum(mat, axis=1) - 1.0), axis=0),
                      jnp.max(jnp.abs(jnp.sum(mat, axis=0) - 1.0), axis=0))
    if count_mask is not None:
        off = jnp.where(count_mask, off, 0.0)
    return jnp.max(off)
