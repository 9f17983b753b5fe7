"""Grouped-query decoder whose layers mix sliding-window and full attention,
with a dropless expert layer routed from the block's input: the block
SmallThinker-21BA3B-Instruct publishes.

Plain functions over a plain parameter tree, like ``mla_moe.py`` (the
serving plane needs each layer's cache streams in and out). Block ``l``::

    y  = RMSNorm(x; in_norm)
    r  = y W_r                              float32; the router reads y
    x1 = x + Attn_l(y)
    z  = RMSNorm(x1; post_norm)
    x2 = x1 + sum_i w_i E_idx_i(z)          idx = top-k of r, w = softmax(r[idx])

**Attention.** ``q, k, v = y W_q, y W_k, y W_v`` (``n_head`` query heads,
``n_kv_head`` K/V heads, query head ``h`` reads K/V head ``h // (n_head /
n_kv_head)``), scores over ``sqrt(d_head)``. A layer is *global* or a
*window* layer (``cfg.windows[l]``: 0 or ``W``). A window layer rotates
``q`` and ``k`` at the token's position (:func:`rope_half`: all ``d_head``
dims, pairs ``(i, i + d_head / 2)``) and lets query ``i`` read keys ``i - W <
j <= i``; a global layer rotates nothing (no positional embedding at all)
and reads every ``j <= i``. What a cache holds of a token is ``k`` as the
scores contract it (rotated on a window layer) and ``v``.
:func:`attend_blocks` is the prefill (``ops/prefill_attention.py``): a tile
of queries against the blocks of keys it can see under a running softmax, so
no ``(H, S, S)`` tensor is held and a window layer does a window's work.

**Experts.** ``E_e(z) = W_down,e (relu(W_gate,e z) * W_up,e z)`` through
``parallel.moe.dropless_moe`` with the routing given from outside
(``moe.softmax_topk_route`` over ``y``). No shared expert, no token dropped.

Parameter tree (weights in ``cfg.dtype``, norms and router float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/in_norm, post_norm (D,)
    layer_<i>/attn/{q (D, H*dh), k (D, Hk*dh), v (D, Hk*dh), o (H*dh, D)}
    layer_<i>/moe/{router (D, E), gate (E, D, Fe), up (E, D, Fe),
                   down (E, Fe, D)}
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch
from ..parallel import moe
from .mla_moe import _mm, rms_norm


@dataclasses.dataclass(frozen=True)
class WindowMoeConfig:
    vocab_size: int
    n_layer: int
    d_model: int
    n_head: int
    n_kv_head: int
    d_head: int
    n_experts: int
    top_k: int
    d_expert: int
    windows: Tuple[int, ...]  # a layer's window, 0 for a global layer
    rotated: Tuple[bool, ...]  # whether a layer rotates q and k
    rope_theta: float = 10000.0
    eps: float = 1e-6
    q_block: int = 512  # queries a block of the prefill's attention
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "WindowMoeConfig":
        """From the keys of a published ``config.json``; the layouts are cut
        to ``num_hidden_layers``."""
        n = c["num_hidden_layers"]
        return cls(
            vocab_size=c["vocab_size"], n_layer=n, d_model=c["hidden_size"],
            n_head=c["num_attention_heads"],
            n_kv_head=c["num_key_value_heads"], d_head=c["head_dim"],
            n_experts=c["moe_num_primary_experts"],
            top_k=c["moe_num_active_primary_experts"],
            d_expert=c["moe_ffn_hidden_size"],
            windows=tuple(c["sliding_window_size"] if w else 0
                          for w in c["sliding_window_layout"][:n]),
            rotated=tuple(bool(r) for r in c["rope_layout"][:n]),
            rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], **kw,
        )

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over all layers (a window
        layer stops growing at its window: ``serving/adapter.py``)."""
        return 2 * self.n_layer * self.n_kv_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0


def rope_half(x, positions, theta):
    """Rotate the half-split pairs ``(x[i], x[i + d/2])`` of the last axis
    by ``positions * theta**(-2i/d)``. ``x (B, S, H, d)``, ``positions (B,
    S)``; float32 out."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions[..., None, None].astype(jnp.float32) * inv.astype(
        np.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def attn_project(cfg: WindowMoeConfig, layer: int, y, pa, positions):
    """``y (B, S, D)`` (already normed) at ``positions (B, S)`` -> ``q (B,
    S, H, dh)`` in ``cfg.dtype`` and the token's cache entry ``k``, ``v (B,
    S, Hk, dh)`` float32; ``q`` and ``k`` rotated where the layer rotates."""
    b, s, _ = y.shape
    dt = cfg.dtype
    q = _mm(y, pa["q"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    k = _mm(y, pa["k"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    v = _mm(y, pa["v"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    if cfg.rotated[layer]:
        q = rope_half(q, positions, cfg.rope_theta).astype(dt)
        k = rope_half(k, positions, cfg.rope_theta)
    return q, k.astype(jnp.float32), v.astype(jnp.float32)


def attend_blocks(cfg: WindowMoeConfig, q, k, v, window: int):
    """Causal attention of a whole prompt from position 0, each K/V head
    read by its group of query heads: ``(B, S, H*dh)``, under the band
    ``q_pos - window < key_pos <= q_pos`` (every ``key_pos <= q_pos``
    without a window). ``ops.dispatch.prefill_attention``: a prompt of more
    than ``cfg.q_block`` positions is one pass of the ``cgx_prefill_
    attention`` kernel over the key blocks a tile of queries can see, where
    the kernels run; else queries go ``cfg.q_block`` at a time, and a block
    reads the ``window + q_block`` keys that end with its own last query
    (every key without a window)."""
    return dispatch.prefill_attention(
        q, k, v, window=window, scale=1.0 / np.sqrt(q.shape[-1]),
        q_block=cfg.q_block, dtype=cfg.dtype,
    )


def experts(cfg: WindowMoeConfig, pm, y, z, count_mask=None):
    """The layer's expert half: routed from ``y`` (the attention's input,
    normed), computed on ``z`` (the post-attention norm's output), both
    ``(..., D)``. Returns ``(out, moe.STATS as an int32 vector)``."""
    flat_y = y.reshape(-1, y.shape[-1])
    flat_z = z.reshape(-1, z.shape[-1])
    out, stats = moe.dropless_moe(
        flat_z, None, None, pm["gate"], pm["up"], pm["down"],
        top_k=cfg.top_k, dtype=cfg.dtype, count_mask=count_mask,
        routing=moe.softmax_topk_route(flat_y, pm["router"],
                                       top_k=cfg.top_k),
        act="relu",
    )
    return out.reshape(z.shape), stats


def block_tail(cfg: WindowMoeConfig, pl, x, y, attn_out, count_mask=None):
    """Output projection and residual, then the expert half and its
    residual; the residual stream is float32. ``(x2, stats)``."""
    x = x + _mm(attn_out, pl["attn"]["o"], cfg.dtype).astype(jnp.float32)
    z = rms_norm(x, pl["post_norm"], cfg.eps)
    out, stats = experts(cfg, pl["moe"], y, z, count_mask)
    return x + out.astype(jnp.float32), stats


def embed(cfg: WindowMoeConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def logits(cfg: WindowMoeConfig, params, x):
    """Final norm and the untied head; float32."""
    y = rms_norm(x, params["norm_f"], cfg.eps)
    return jnp.matmul(
        y.astype(cfg.dtype), params["head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
