"""Latent-attention (MLA) decoder with a dropless sigmoid-routed expert
layer: the DeepSeek-V3 family's block, as JoyAI-LLM-Flash publishes it and,
with YaRN positions and hyper-connected residual streams, Xing4.0-29B-A4B.

Plain functions over a plain parameter tree (no flax module: the serving
plane needs each layer's cache streams in and out, which a module hides).
Block ``l``, one residual stream::

    h  = x + Attn(RMSNorm(x))
    x' = h + FFN_l(RMSNorm(h))          FFN_0 dense SwiGLU, FFN_l>0 experts

With ``cfg.hc_mult = n`` streams a token (``models/mhc.py`` has the
equations) the embedding is repeated into ``X (n, D)`` and each of the two
sublayers reads one mix of the streams and writes into a mix of them::

    u, H_post, H_res = pre(X; hc)       hc = hc_attn, then hc_ffn
    y     = F(RMSNorm(u))               F = Attn, then FFN_l
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

and the final norm takes ``read_out(X; hc_head)``. The block is written
once, in ``serving/latent.py`` (``_enter`` / ``_leave``); the functions
here take what a sublayer reads and return what it writes, whichever it is.

**MLA.** ``c_q = RMSNorm(W_qa x)``; ``q = W_qb c_q`` (``q = W_q x`` for a
layer whose tree holds a full-rank ``q``) split per head into
``[q_nope | q_rope]``; ``[c_kv | k_r] = W_kva x``; ``c = RMSNorm(c_kv)``;
``q_rope`` and ``k_r`` rotated at the token's position (interleaved pairs),
``k_r`` one per token for all heads; ``[k_nope_h | v_h] = W_kvb c``; scores
``(q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(d_nope + d_rope)``. What a
cache has to hold of a token is ``c`` and the rotated ``k_r``, nothing
else. :func:`attend_expanded` rebuilds every head's key and value from
``c`` (prefill, ``ops/prefill_attention.py``: no ``(H, S, S)`` tensor is
held);
:func:`attend_absorbed` folds ``W_kvb`` into the query and the output
(decode: no per-head key or value of a cached token is ever rebuilt). Both
are the same mathematics.

**Positions.** Pair ``i`` of the ``d_rope / 2`` turns by ``position *
theta**(-2i / d_rope)``. Under ``cfg.yarn`` (:class:`Yarn`, a published
``rope_scaling`` of type ``yarn``, as the family computes it) the pairs that
turn fewer than ``beta_slow`` times over the original positions turn
``factor`` times slower, those that turn more than ``beta_fast`` times are
kept, a linear ramp between; cosines and sines times ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)`` and the scores' scale times
``mscale(factor, mscale_all_dim)**2``, ``mscale(f, m) = 0.1 m ln f + 1``.

**Experts.** ``s = sigmoid(W_g y)`` in float32; the ``top_k`` experts of
largest ``s + b`` (``b`` the selection bias); weights ``s_i / sum_chosen s``
times ``routed_scale``; ``sum w_i E_i(y) + E_shared(y)`` through
``parallel.moe.dropless_moe``. No token is dropped.

Parameter tree (weights in ``cfg.dtype``; norms, router, bias and the
hyper-connections' leaves float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/attn_norm, ffn_norm (D,)
    layer_<i>/attn/{q_a (D, Rq), q_a_norm (Rq,), q_b (Rq, H*(dn+dr)),
                    kv_a (D, Rkv+dr), kv_a_norm (Rkv,),
                    kv_b (Rkv, H*(dn+dv)), o (H*dv, D)}
    layer_0/mlp/{gate (D, F), up (D, F), down (F, D)}
    layer_<i>/moe/{router (D, E), bias (E,), gate (E, D, Fe), up (E, D, Fe),
                   down (E, Fe, D), shared/{gate, up, down}}
    with hc_mult = n:
    layer_<i>/hc_attn, hc_ffn/{phi (nD, 2n+n*n), alpha (3,), base (2n+n*n,)}
    hc_head/{phi (nD, n), alpha (1,), base (n,)}
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch
from ..parallel import moe
from .attention import joined_softmax


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's numbers, as a published ``rope_scaling`` of type ``yarn``
    gives them."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def frequency_scale(self, d: int, theta: float):
        """What each of the ``d / 2`` pairs' frequency ``theta**(-2i/d)`` is
        multiplied by, float64: 1 for the pairs that turn more than
        ``beta_fast`` times over the original positions (below the first
        correction dim), ``1 / factor`` for those that turn less than
        ``beta_slow`` times (above the second), a linear ramp between."""
        def correction_dim(turns):
            return (d * math.log(self.original_positions
                                 / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                       / max(high - low, 0.001), 0.0, 1.0)
        return 1.0 - ramp * (1.0 - 1.0 / self.factor)

    @property
    def rotation_scale(self) -> float:
        """What the cosines and sines are multiplied by."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        """What the scores' ``1 / sqrt(d)`` is multiplied by."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int
    n_layer: int
    d_model: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    d_ff: int  # the leading dense layers' SwiGLU width
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 1
    n_dense_layers: int = 1
    routed_scale: float = 1.0
    rope_theta: float = 10000.0
    eps: float = 1e-6
    q_block: int = 512  # queries a block of the prefill's attention
    dtype: Any = jnp.bfloat16
    yarn: Optional[Yarn] = None  # position scaling; None: plain rotation
    # Hyper-connections (``models/mhc.py``); 0 streams: one residual.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "MlaMoeConfig":
        """From the keys of a published ``config.json``. A ``rope_scaling``
        of a type that is not computed here is refused, not ignored."""
        found = {}
        scaling = c.get("rope_scaling")
        if scaling is not None:
            if scaling.get("type") != "yarn":
                raise ValueError(
                    f"rope_scaling type {scaling.get('type')!r} is not "
                    "computed here (yarn is)"
                )
            found["yarn"] = Yarn(
                factor=float(scaling["factor"]),
                original_positions=int(
                    scaling["original_max_position_embeddings"]),
                **{k: float(scaling[k])
                   for k in ("beta_fast", "beta_slow", "mscale",
                             "mscale_all_dim") if k in scaling},
            )
        if c.get("hc_mult"):
            found.update(
                hc_mult=c["hc_mult"],
                hc_sinkhorn_iters=c["hc_sinkhorn_iters"], hc_eps=c["hc_eps"],
                hc_clamp=(float(c["mhc_h_res_clamp_min"]),
                          float(c["mhc_h_res_clamp_max"])),
            )
        kw = {**found, **kw}
        return cls(
            vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_head=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            d_nope=c["qk_nope_head_dim"], d_rope=c["qk_rope_head_dim"],
            d_v=c["v_head_dim"], d_ff=c["intermediate_size"],
            n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            d_expert=c["moe_intermediate_size"],
            n_shared=c["n_shared_experts"],
            n_dense_layers=c["first_k_dense_replace"],
            routed_scale=c["routed_scaling_factor"],
            rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], **kw,
        )

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's cache over all layers: the latent
        and the rotated key."""
        return self.n_layer * (self.kv_lora_rank + self.d_rope) * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    ) * w.astype(jnp.float32)


def rope(x, positions, theta, yarn: Optional[Yarn] = None):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``positions * theta**(-2i/d)``, the frequencies and the rotation scaled
    as ``yarn`` says (:class:`Yarn`; a factor of 1 is the plain rotation to
    the bit). ``x (..., S, [H,] d)``, ``positions`` broadcastable to ``x``'s
    leading axes up to ``S``; float32 out."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if yarn is not None:
        inv = inv * yarn.frequency_scale(d, theta)
    ang = positions[..., None].astype(jnp.float32) * inv.astype(np.float32)
    if x.ndim == ang.ndim + 1:  # a head axis between S and d
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None and yarn.rotation_scale != 1.0:
        cos, sin = cos * yarn.rotation_scale, sin * yarn.rotation_scale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).reshape(x.shape)


def _mm(x, w, dtype):
    return x.astype(dtype) @ w.astype(dtype)


def swiglu(y, p, dtype):
    g = _mm(y, p["gate"], dtype)
    return _mm(jax.nn.silu(g) * _mm(y, p["up"], dtype), p["down"], dtype)


def mla_project(cfg: MlaMoeConfig, x, pa, positions):
    """``x (B, S, D)`` (already normed) at ``positions (B, S)`` -> ``q_nope
    (B, S, H, dn)``, rotated ``q_rope (B, S, H, dr)`` (``cfg.dtype``), and
    the token's cache entry: the normalised latent ``c (B, S, Rkv)`` and
    the rotated key ``k_r (B, S, dr)``, float32."""
    dt = cfg.dtype
    b, s, _ = x.shape
    if "q" in pa:  # no low rank (``q_lora_rank`` null)
        q = _mm(x, pa["q"], dt)
    else:
        c_q = rms_norm(_mm(x, pa["q_a"], dt), pa["q_a_norm"], cfg.eps)
        q = _mm(c_q, pa["q_b"], dt)
    q = q.reshape(b, s, cfg.n_head, cfg.d_nope + cfg.d_rope)
    q_nope, q_rope = q[..., : cfg.d_nope], q[..., cfg.d_nope:]
    kv = _mm(x, pa["kv_a"], dt)
    c = rms_norm(kv[..., : cfg.kv_lora_rank], pa["kv_a_norm"], cfg.eps)
    yarn = getattr(cfg, "yarn", None)  # a config without the field: none
    k_r = rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta, yarn)
    q_rope = rope(q_rope, positions, cfg.rope_theta, yarn).astype(dt)
    return q_nope, q_rope, c, k_r


def _kv_b_heads(cfg: MlaMoeConfig, pa):
    """``W_kvb`` as ``(Rkv, H, dn)`` for the keys and ``(Rkv, H, dv)`` for
    the values."""
    w = pa["kv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.d_nope + cfg.d_v
    )
    return w[..., : cfg.d_nope], w[..., cfg.d_nope:]


def _softmax_scale(cfg: MlaMoeConfig):
    """``1 / sqrt(d_nope + d_rope)``, times YaRN's ``mscale`` squared where
    the config scales positions."""
    yarn = getattr(cfg, "yarn", None)
    scale = 1.0 / np.sqrt(cfg.d_nope + cfg.d_rope)
    return np.float32(scale if yarn is None else scale * yarn.softmax_scale)


def attend_expanded(cfg: MlaMoeConfig, pa, q_nope, q_rope, c, k_r):
    """Causal attention of a whole prompt, every head's key and value
    rebuilt from ``c``: ``(B, S, H*dv)``. ``ops.dispatch.prefill_attention``
    over the two-part keys: a prompt of more than ``cfg.q_block`` positions
    is one pass of the ``cgx_prefill_attention`` kernel where the kernels
    run; else queries go ``cfg.q_block`` at a time, so the scores held are
    ``(B, H, q_block, S)``."""
    dt = cfg.dtype
    w_k, w_v = _kv_b_heads(cfg, pa)
    c_dt, kr_dt = c.astype(dt), k_r.astype(dt)
    k_nope = jnp.einsum("bsl,lhn->bshn", c_dt, w_k.astype(dt))
    v = jnp.einsum("bsl,lhv->bshv", c_dt, w_v.astype(dt))
    return dispatch.prefill_attention(
        q_nope, k_nope, v, q_rope, kr_dt, scale=_softmax_scale(cfg),
        q_block=cfg.q_block, dtype=dt,
    )


def attend_absorbed(cfg: MlaMoeConfig, pa, q_nope, q_rope, c_all, kr_all,
                    kv_mask, tail=None):
    """One query a lane against cached latents: ``q_nope (B, H, dn)``,
    ``q_rope (B, H, dr)``, ``c_all (B, T, Rkv)``, ``kr_all (B, T, dr)``,
    ``kv_mask (B, T)`` (True = a live position) -> ``(B, H*dv)``. ``W_kvb``'s
    key half is folded into the query and its value half applied to the
    weighted sum of latents. ``tail``: the lane's tail rows ``(c (B, Tt,
    Rkv), kr (B, Tt, dr), mask (B, Tt))``, attended apart from the pages
    under one softmax (``attention.joined_softmax``), the two weighted
    sums of latents added in float32."""
    dt = cfg.dtype
    w_k, w_v = _kv_b_heads(cfg, pa)
    q_abs = jnp.einsum("bhn,lhn->bhl", q_nope, w_k.astype(dt))

    def scores(c, kr):
        return (
            jnp.einsum("bhl,btl->bht", q_abs, c,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhr,btr->bht", q_rope, kr,
                         preferred_element_type=jnp.float32)
        ) * _softmax_scale(cfg)

    def weighted(probs, c):
        return jnp.einsum("bht,btl->bhl", probs, c,
                          preferred_element_type=jnp.float32)

    c_tail, kr_tail, tail_mask = tail or (None, None, None)
    probs, tail_probs = joined_softmax(
        scores(c_all, kr_all), kv_mask,
        None if tail is None else scores(c_tail, kr_tail), tail_mask,
        dtype=dt,
    )
    o_lat = weighted(probs, c_all)
    if tail is not None:
        o_lat = o_lat + weighted(tail_probs, c_tail)
    o = jnp.einsum("bhl,lhv->bhv", o_lat.astype(dt), w_v.astype(dt))
    return o.reshape(o.shape[0], -1)


def ffn(cfg: MlaMoeConfig, pl, y, count_mask=None):
    """The layer's feed-forward half over ``y (..., D)`` (already normed):
    ``(out, stats)``; ``stats`` is None for a dense layer, else
    ``moe.STATS`` as an int32 vector."""
    if "mlp" in pl:
        return swiglu(y, pl["mlp"], cfg.dtype), None
    pm = pl["moe"]
    flat = y.reshape(-1, y.shape[-1])
    out, stats = moe.dropless_moe(
        flat, pm["router"], pm["bias"], pm["gate"], pm["up"], pm["down"],
        top_k=cfg.top_k, scale=cfg.routed_scale, dtype=cfg.dtype,
        count_mask=count_mask,
    )
    out = out + swiglu(flat, pm["shared"], cfg.dtype)
    return out.reshape(y.shape), stats


def embed(cfg: MlaMoeConfig, params, tokens):
    return params["embed"][tokens].astype(cfg.dtype)


def logits(cfg: MlaMoeConfig, params, x):
    """Final norm and the untied head; float32."""
    y = rms_norm(x, params["norm_f"], cfg.eps)
    return jnp.matmul(
        y.astype(cfg.dtype), params["head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
