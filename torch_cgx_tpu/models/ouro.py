"""Looped decoder: one stack of sandwich-normed multi-head attention and
dense SwiGLU layers run ``passes`` times a token, every pass with a cache of
its own, and an exit gate after each pass: the block Ouro-2.6B publishes
(``model_type: ouro``, ``total_ut_steps`` passes).

Plain functions over a plain parameter tree, like ``afmoe.py`` (whose
sandwich this is, without its gate, QK norm, windows or experts) and
``window_moe.py`` (whose ``rope_half`` and ``attend_blocks`` this block
shares). A position::

    x = embed[token]
    for t in 0..T-1:                     the same layers' weights every pass
      for l in 0..L-1:
        a = RMSNorm(x; in_norm_l)
        q, k, v = a W_q, a W_k, a W_v    q, k rotated at the token's position
        cache slot (t, l) <- k, v        pass t reads what pass t wrote
        x = x + RMSNorm(Attn(q, K_(t,l), V_(t,l)) W_o; post_attn_norm_l)
        m = RMSNorm(x; pre_mlp_norm_l)
        x = x + RMSNorm(SwiGLU_l(m); post_mlp_norm_l)
      x = RMSNorm(x; norm_f)             closes every pass, enters the next
      lam_t = sigmoid(x . w_exit + b_exit)
    logits = x W_head                    pass T's

``n_head`` heads of ``d_head`` for queries, keys and values alike (plain
multi-head), the whole head rotated in half-split pairs, scores over
``sqrt(d_head)``, no bias but the gate's. The gate is float32; a token's
exit distribution is ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass
taking what is left (:func:`exit_mass`). At the published threshold of 1.0
the running sum reaches it at pass T alone, so every token takes every pass
and is served pass T's logits; a lower threshold would let a token leave
early, which is a different result and is refused (:class:`OuroConfig`).

Parameter tree (weights in ``cfg.dtype``, norms and the gate float32)::

    embed (V, D)   head (D, V)   norm_f (D,)   exit/{w (D,), b ()}
    layer_<i>/in_norm, post_attn_norm, pre_mlp_norm, post_mlp_norm (D,)
    layer_<i>/attn/{q, k, v (D, H*dh), o (H*dh, D)}
    layer_<i>/mlp/{gate (D, F), up (D, F), down (F, D)}
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .mla_moe import _mm, rms_norm, swiglu
from .window_moe import rope_half


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int
    n_layer: int  # the weight layers; a token's cache holds passes x n_layer
    d_model: int
    n_head: int
    d_head: int
    d_ff: int
    passes: int  # times a token goes through the n_layer layers
    exit_threshold: float = 1.0
    rope_theta: float = 1e6
    eps: float = 1e-6
    q_block: int = 512  # queries a block of the prefill's attention
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "OuroConfig":
        """From the keys of a published ``config.json``. A config this block
        is not is refused."""
        n = c["num_hidden_layers"]
        types = c.get("layer_types")
        if types is not None and (len(types) != n
                                  or set(types) != {"full_attention"}):
            raise ValueError(
                f"ouro: layer_types holds {sorted(set(types))} over "
                f"{len(types)} entries; this block is full_attention on "
                f"all {n} layers")
        for key, want in (("rope_scaling", None), ("use_sliding_window", False),
                          ("hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if c.get(key, want) != want:
                raise ValueError(
                    f"ouro: {key} = {c[key]!r}; this block is {want!r}")
        if c["num_key_value_heads"] != c["num_attention_heads"]:
            raise ValueError(
                f"ouro: {c['num_key_value_heads']} K/V heads under "
                f"{c['num_attention_heads']} query heads; this block is "
                "plain multi-head")
        return cls(
            vocab_size=c["vocab_size"], n_layer=n, d_model=c["hidden_size"],
            n_head=c["num_attention_heads"], d_head=c["head_dim"],
            d_ff=c["intermediate_size"], passes=c["total_ut_steps"],
            exit_threshold=float(c["early_exit_threshold"]),
            rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        if self.passes < 1:
            raise ValueError(f"ouro: {self.passes} passes")
        if self.exit_threshold < 1.0:
            raise ValueError(
                f"ouro: early_exit_threshold {self.exit_threshold} is under "
                "1: a token would leave the loop at the first pass whose "
                "running exit mass reaches it and be served that pass's "
                "logits, which is a different result from the model's at "
                "its published threshold of 1.0, and another PR's (the "
                "decode step runs every pass for every lane)")

    @property
    def n_cache_layers(self) -> int:
        """Layers' worth of K and V a token leaves: one a pass a layer."""
        return self.passes * self.n_layer

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over every pass's layers."""
        return 2 * self.n_cache_layers * self.n_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0


def attn_project(cfg: OuroConfig, a, pa, positions):
    """``a (B, S, D)`` (already normed) at ``positions (B, S)`` -> ``q (B,
    S, H, dh)`` in ``cfg.dtype`` and the token's cache entry ``k``, ``v (B,
    S, H, dh)`` float32; ``q`` and ``k`` rotated."""
    b, s, _ = a.shape
    dt = cfg.dtype
    q = _mm(a, pa["q"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    k = _mm(a, pa["k"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    v = _mm(a, pa["v"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    q = rope_half(q, positions, cfg.rope_theta).astype(dt)
    return q, rope_half(k, positions, cfg.rope_theta), v.astype(jnp.float32)


def attn_out(cfg: OuroConfig, pl, x, o):
    """The output projection, the sandwich norm and the residual: ``x +
    RMSNorm(o W_o)``; ``o (..., H*dh)``."""
    return x + rms_norm(_mm(o, pl["attn"]["o"], cfg.dtype),
                        pl["post_attn_norm"], cfg.eps)


def ffn_half(cfg: OuroConfig, pl, h):
    """The block's second half over the residual stream ``h (..., D)``: ``h
    + RMSNorm(SwiGLU(RMSNorm(h)))``."""
    m = rms_norm(h, pl["pre_mlp_norm"], cfg.eps)
    return h + rms_norm(swiglu(m, pl["mlp"], cfg.dtype),
                        pl["post_mlp_norm"], cfg.eps)


def embed(cfg: OuroConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def close_pass(cfg: OuroConfig, params, x):
    """What ends a pass: the final norm, whose output is the next pass's
    input (and the head's, after the last), and the exit gate on it.
    ``(x (..., D) float32, lam (...) float32)``."""
    x = rms_norm(x, params["norm_f"], cfg.eps)
    gate = params["exit"]
    lam = jax.nn.sigmoid(
        jnp.sum(x * gate["w"].astype(jnp.float32), axis=-1)
        + gate["b"].astype(jnp.float32))
    return x, lam


def exit_mass(lams):
    """The exit distribution over the passes from their gates ``lams (T,
    ...)``: ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass taking
    what is left, so that the ``T`` sum to 1."""
    stay = jnp.cumprod(1.0 - lams, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = lams * before
    return jnp.concatenate([p[:-1], before[-1:]])


def logits(cfg: OuroConfig, params, x):
    """The untied head over the last pass's closed stream; float32."""
    return jnp.matmul(
        x.astype(cfg.dtype), params["head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
