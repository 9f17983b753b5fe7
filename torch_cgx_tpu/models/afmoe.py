"""Grouped-query decoder with sandwich norms, a gated and QK-normed attention
over sliding-window and full layers, and a sigmoid-routed expert layer
beside a shared expert after a few leading dense layers: the block
Trinity-Large-Preview publishes (``model_type: afmoe``).

Plain functions over a plain parameter tree, like ``window_moe.py`` (whose
``rope_half``, ``attend_blocks`` and ``logits`` this block shares) and
``mla_moe.py`` (``rms_norm``, ``swiglu``). Block ``l``::

    a  = RMSNorm(x; in_norm)
    h  = x + RMSNorm(Attn_l(a); post_attn_norm)
    m  = RMSNorm(h; pre_mlp_norm)
    x' = h + RMSNorm(FFN_l(m); post_mlp_norm)

Four norms a block: what a sub-layer returns is normed before it is added
(the sandwich), so the residual stream is a sum of unit-scale terms. The
stream starts at ``embed[tokens] * sqrt(d_model)`` (``mup_enabled``).

**Attention.** ``q, k, v = a W_q, a W_k, a W_v`` (``n_head`` query heads,
``n_kv_head`` K/V heads, query head ``h`` reads K/V head ``h // (n_head /
n_kv_head)``); ``q`` and ``k`` are RMS-normed over the ``d_head`` of a head
(one weight vector for all heads: ``q_norm``, ``k_norm``), then rotated on a
*window* layer (``cfg.windows[l] = W``: ``window_moe.rope_half`` at the
token's position, query ``i`` reads keys ``i - W < j <= i``) and left bare on
a *full* layer (no positional embedding, every ``j <= i``); scores over
``sqrt(d_head)``. The heads' output is gated element by element before the
output projection: ``(o * sigmoid(a W_g)) W_o``, ``W_g`` as wide as ``W_q``.
What a cache holds of a token is ``k`` as the scores contract it (normed,
rotated on a window layer) and ``v``.

**FFN.** Layer ``l`` is dense SwiGLU of width ``d_ff`` where ``cfg.dense[l]``
(the published ``num_dense_layers`` leading layers), else ``shared SwiGLU(m)
+ sum_i w_i E_i(m)``: ``s = sigmoid(m W_r)`` in float32 over all ``n_experts``
published experts, the ``top_k`` of largest ``s + bias``, ``w`` the chosen
``s`` normalised to sum to 1 times ``route_scale``
(``parallel.moe.sigmoid_topk_route``; one group). The chip may hold a share
of a layer's experts (``n_held`` from ``first_expert`` on): the router keeps
its whole width, the held experts' part is computed, the others add nothing
(``moe.dropless_moe(held=)``).

Parameter tree (weights in ``cfg.dtype``, norms, router and bias float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/in_norm, post_attn_norm, pre_mlp_norm, post_mlp_norm (D,)
    layer_<i>/attn/{q (D, H*dh), k (D, Hk*dh), v (D, Hk*dh), g (D, H*dh),
                    o (H*dh, D), q_norm (dh,), k_norm (dh,)}
    layer_<i>/mlp/{gate (D, F), up (D, F), down (F, D)}        a dense layer
    layer_<i>/moe/{router (D, E), bias (E,), gate (Eh, D, Fe), up (Eh, D, Fe),
                   down (Eh, Fe, D), shared/{gate, up, down}}  otherwise
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import moe
from .mla_moe import _mm, rms_norm, swiglu
from .window_moe import rope_half


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int
    n_layer: int
    d_model: int
    n_head: int
    n_kv_head: int
    d_head: int
    d_ff: int  # a dense layer's SwiGLU width
    n_experts: int  # the router's width: the published experts of a layer
    top_k: int
    d_expert: int
    d_shared: int  # the shared expert's width
    windows: Tuple[int, ...]  # a layer's window, 0 for a full layer
    dense: Tuple[bool, ...]  # whether a layer's FFN is the dense one
    experts_held: Optional[int] = None  # None: all of them
    first_expert: int = 0
    route_scale: float = 1.0
    embed_scale: float = 1.0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    q_block: int = 512  # queries a block of the prefill's attention
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "AfmoeConfig":
        """From the keys of a published ``config.json`` and, where the file
        is a cut, ``layers_kept`` (which published layer each of the
        ``num_hidden_layers`` layers is: its ``layer_types`` entry, dense
        where under ``num_dense_layers``), ``num_experts_published`` (the
        router's width where ``num_experts`` counts the experts held) and
        ``first_expert``. A config this block is not is refused."""
        for key, want in (("score_func", "sigmoid"), ("route_norm", True),
                          ("rope_scaling", None), ("n_group", 1),
                          ("topk_group", 1), ("num_expert_groups", 1),
                          ("num_limited_groups", 1)):
            if c.get(key, want) != want:
                raise ValueError(
                    f"afmoe: {key} = {c[key]!r}; this block is {want!r}")
        n = c["num_hidden_layers"]
        kept = tuple(c.get("layers_kept", range(n)))
        if len(kept) != n:
            raise ValueError(f"layers_kept names {len(kept)} layers, "
                             f"num_hidden_layers is {n}")
        held = c["num_experts"]
        published = c.get("num_experts_published", held)
        return cls(
            vocab_size=c["vocab_size"], n_layer=n, d_model=c["hidden_size"],
            n_head=c["num_attention_heads"],
            n_kv_head=c["num_key_value_heads"], d_head=c["head_dim"],
            d_ff=c["intermediate_size"], n_experts=published,
            top_k=c["num_experts_per_tok"],
            d_expert=c["moe_intermediate_size"],
            d_shared=c["moe_intermediate_size"] * c["num_shared_experts"],
            windows=tuple(
                c["sliding_window"]
                if c["layer_types"][i] == "sliding_attention" else 0
                for i in kept),
            dense=tuple(i < c["num_dense_layers"] for i in kept),
            experts_held=None if held == published else held,
            first_expert=c.get("first_expert", 0),
            route_scale=c["route_scale"],
            embed_scale=(float(np.sqrt(c["hidden_size"]))
                         if c["mup_enabled"] else 1.0),
            rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        if self.first_expert + self.n_held > self.n_experts:
            raise ValueError(
                f"experts {self.first_expert} to {self.first_expert} + "
                f"{self.n_held} of {self.n_experts}")

    @property
    def n_held(self) -> int:
        """Experts of a layer this chip holds."""
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over all layers (a window
        layer stops growing at its window: ``serving/adapter.py``)."""
        return 2 * self.n_layer * self.n_kv_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0


def attn_project(cfg: AfmoeConfig, layer: int, a, pa, positions):
    """``a (B, S, D)`` (already normed) at ``positions (B, S)`` -> ``q (B,
    S, H, dh)`` in ``cfg.dtype`` and the token's cache entry ``k``, ``v (B,
    S, Hk, dh)`` float32; ``q`` and ``k`` normed a head, then rotated on a
    window layer."""
    b, s, _ = a.shape
    dt = cfg.dtype
    q = _mm(a, pa["q"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    k = _mm(a, pa["k"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    v = _mm(a, pa["v"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    q = rms_norm(q, pa["q_norm"], cfg.eps)
    k = rms_norm(k, pa["k_norm"], cfg.eps)
    if cfg.windows[layer]:
        q = rope_half(q, positions, cfg.rope_theta)
        k = rope_half(k, positions, cfg.rope_theta)
    return q.astype(dt), k, v.astype(jnp.float32)


def attn_out(cfg: AfmoeConfig, pl, x, a, o):
    """The gate, the output projection, the sandwich norm and the residual:
    ``x + RMSNorm((o * sigmoid(a W_g)) W_o)``; ``o (..., H*dh)``."""
    gate = jax.nn.sigmoid(_mm(a, pl["attn"]["g"], cfg.dtype).astype(
        jnp.float32))
    out = _mm(o.astype(jnp.float32) * gate, pl["attn"]["o"], cfg.dtype)
    return x + rms_norm(out, pl["post_attn_norm"], cfg.eps)


def ffn_half(cfg: AfmoeConfig, pl, h, count_mask=None):
    """The block's second half over the residual stream ``h (..., D)``:
    ``(h + RMSNorm(FFN(RMSNorm(h))), stats)``; ``stats`` is None for a dense
    layer, else ``moe.STATS`` (``moe.HELD_STATS`` of a held share) as an
    int32 vector."""
    m = rms_norm(h, pl["pre_mlp_norm"], cfg.eps)
    if "mlp" in pl:
        f, stats = swiglu(m, pl["mlp"], cfg.dtype), None
    else:
        pm = pl["moe"]
        flat = m.reshape(-1, m.shape[-1])
        f, stats = moe.dropless_moe(
            flat, pm["router"], pm["bias"], pm["gate"], pm["up"], pm["down"],
            top_k=cfg.top_k, scale=cfg.route_scale, dtype=cfg.dtype,
            count_mask=count_mask,
            held=None if cfg.experts_held is None else cfg.first_expert,
        )
        f = (f + swiglu(flat, pm["shared"], cfg.dtype)).reshape(m.shape)
    return h + rms_norm(f, pl["post_mlp_norm"], cfg.eps), stats


def embed(cfg: AfmoeConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.float32) * np.float32(
        cfg.embed_scale)
