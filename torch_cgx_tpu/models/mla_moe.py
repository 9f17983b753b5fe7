"""Latent-attention (MLA) decoder with a dropless sigmoid-routed expert
layer: the DeepSeek-V3 family's block, as JoyAI-LLM-Flash publishes it.

Plain functions over a plain parameter tree (no flax module: the serving
plane needs each layer's cache streams in and out, which a module hides).
Block ``l``::

    h  = x + Attn(RMSNorm(x))
    x' = h + FFN_l(RMSNorm(h))          FFN_0 dense SwiGLU, FFN_l>0 experts

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

**Experts.** ``s = sigmoid(W_g y)`` in float32; the ``top_k`` experts of
largest ``s + b`` (``b`` the selection bias); weights ``s_i / sum_chosen s``
times ``routed_scale``; ``sum w_i E_i(y) + E_shared(y)`` through
``parallel.moe.dropless_moe``. No token is dropped.

Parameter tree (weights in ``cfg.dtype``, norms, router and bias float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/attn_norm, ffn_norm (D,)
    layer_<i>/attn/{q_a (D, Rq), q_a_norm (Rq,), q_b (Rq, H*(dn+dr)),
                    kv_a (D, Rkv+dr), kv_a_norm (Rkv,),
                    kv_b (Rkv, H*(dn+dv)), o (H*dv, D)}
    layer_0/mlp/{gate (D, F), up (D, F), down (F, D)}
    layer_<i>/moe/{router (D, E), bias (E,), gate (E, D, Fe), up (E, D, Fe),
                   down (E, Fe, D), shared/{gate, up, down}}
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch
from ..parallel import moe
from .attention import joined_softmax


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

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "MlaMoeConfig":
        """From the keys of a published ``config.json``."""
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


def rope(x, positions, theta):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``positions * theta**(-2i/d)``. ``x (..., S, [H,] d)``, ``positions``
    broadcastable to ``x``'s leading axes up to ``S``; float32 out."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions[..., None].astype(jnp.float32) * inv.astype(np.float32)
    if x.ndim == ang.ndim + 1:  # a head axis between S and d
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
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
    k_r = rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    q_rope = rope(q_rope, positions, cfg.rope_theta).astype(dt)
    return q_nope, q_rope, c, k_r


def _kv_b_heads(cfg: MlaMoeConfig, pa):
    """``W_kvb`` as ``(Rkv, H, dn)`` for the keys and ``(Rkv, H, dv)`` for
    the values."""
    w = pa["kv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.d_nope + cfg.d_v
    )
    return w[..., : cfg.d_nope], w[..., cfg.d_nope:]


def _softmax_scale(cfg: MlaMoeConfig):
    return np.float32(1.0 / np.sqrt(cfg.d_nope + cfg.d_rope))


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
