"""Grouped-query decoder with QK-norm, rotary positions on every layer and a
dropless softmax-routed expert layer in every block, which generates by
DIFFUSION OVER BLOCKS: the block SDAR-30B-A3B-Chat publishes (``model_type:
sdar_moe``).

Plain functions over a plain parameter tree, like ``window_moe.py`` (whose
``rope_half``, ``attend_blocks``' dispatch and ``logits`` this block shares)
and ``afmoe.py`` (the QK-norm). Block ``l``::

    a  = RMSNorm(x; in_norm)
    x1 = x + Attn(a)
    h  = RMSNorm(x1; post_norm)
    x2 = x1 + sum_i w_i E_idx_i(h)     idx = top-k of h W_r, w = softmax(.)[idx]
                                       divided by its sum (``norm_topk_prob``)

**Attention.** ``q, k, v = a W_q, a W_k, a W_v`` (``n_head`` query heads,
``n_kv_head`` K/V heads, query head ``h`` reads K/V head ``h // (n_head /
n_kv_head)``), no bias; ``q`` and ``k`` are RMS-normed over the ``d_head`` of
a head (one gain vector for all heads), then rotated at the token's position
(``window_moe.rope_half``: all ``d_head`` dims, pairs ``(i, i + d_head / 2)``);
scores over ``sqrt(d_head)``. What a cache holds of a token is ``k`` as the
scores contract it (normed, rotated) and ``v``.

**The mask.** Positions come in blocks of ``cfg.block_tokens = L``: key ``j``
is visible to query ``i`` iff ``j // L <= i // L``, causal over blocks and
both ways inside one. :func:`attend_blocks` is the prefill
(``ops/prefill_attention.py`` with its ``block`` argument).

**Experts.** ``E_e(h) = W_down,e (silu(W_gate,e h) * W_up,e h)`` through
``parallel.moe.dropless_moe`` with the routing given from outside
(``moe.softmax_topk_route`` over ``h``: a softmax over the chosen logits is
the full softmax's chosen values over their sum). No shared expert, no token
dropped; every layer has experts.

**Generation** (``serving/block.py``, docs/SERVING.md "Blocks"): position
``i``'s logits predict position ``i``'s token; a position that is not known
yet holds ``cfg.mask_token``. A block is denoised in at most
``cfg.denoise_steps`` forwards that store nothing, then run once more and
stored. The schedule, the threshold and the mask id are no keys of the
published ``config.json``: the configuration's ``assumed`` lists them, and
:meth:`SdarMoeConfig.from_hf` takes them as ``block_length``,
``denoising_steps``, ``confidence_threshold`` and ``mask_token_id``.

Parameter tree (weights in ``cfg.dtype``, norms and router float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/in_norm, post_norm (D,)
    layer_<i>/attn/{q (D, H*dh), k (D, Hk*dh), v (D, Hk*dh), o (H*dh, D),
                    q_norm (dh,), k_norm (dh,)}
    layer_<i>/moe/{router (D, E), gate (E, D, Fe), up (E, D, Fe),
                   down (E, Fe, D)}
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np

from ..ops import dispatch
from ..parallel import moe
from .mla_moe import _mm, rms_norm
from .window_moe import rope_half


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int
    n_layer: int
    d_model: int
    n_head: int
    n_kv_head: int
    d_head: int
    n_experts: int
    top_k: int
    d_expert: int
    block_tokens: int  # L: positions a block
    denoise_steps: int  # T: forwards a block is denoised in, at most
    mask_token: int
    # A masked position whose confidence passes it is unmasked whatever the
    # schedule says (``low_confidence_dynamic``); 1.0 never is: the static
    # rule.
    unmask_threshold: float = 1.0
    rope_theta: float = 1000000.0
    eps: float = 1e-6
    q_block: int = 512  # queries a block of the prefill's attention
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "SdarMoeConfig":
        """From the keys of a published ``config.json`` and the generation
        procedure's parameters, which it does not hold (``block_length``,
        ``denoising_steps``, ``mask_token_id``, ``confidence_threshold``). A
        config this block is not is refused."""
        for key, want in (("use_sliding_window", False),
                          ("rope_scaling", None), ("mlp_only_layers", []),
                          ("decoder_sparse_step", 1),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False),
                          ("norm_topk_prob", True), ("hidden_act", "silu")):
            if c.get(key, want) != want:
                raise ValueError(
                    f"sdar_moe: {key} = {c[key]!r}; this block is {want!r}")
        return cls(
            vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_head=c["num_attention_heads"],
            n_kv_head=c["num_key_value_heads"], d_head=c["head_dim"],
            n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
            d_expert=c["moe_intermediate_size"],
            block_tokens=c["block_length"],
            denoise_steps=c["denoising_steps"],
            mask_token=c["mask_token_id"],
            unmask_threshold=float(c.get("confidence_threshold", 1.0)),
            rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        if not 1 <= self.denoise_steps <= self.block_tokens:
            raise ValueError(
                f"sdar_moe: {self.denoise_steps} denoising steps for a block "
                f"of {self.block_tokens}: a step unmasks at least a position")
        if not 0 <= self.mask_token < self.vocab_size:
            raise ValueError(
                f"sdar_moe: mask token {self.mask_token} is no row of a "
                f"vocabulary of {self.vocab_size}")

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over all layers."""
        return 2 * self.n_layer * self.n_kv_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0


def attn_project(cfg: SdarMoeConfig, a, pa, positions):
    """``a (B, S, D)`` (already normed) at ``positions (B, S)`` -> ``q (B,
    S, H, dh)`` in ``cfg.dtype`` and the token's cache entry ``k``, ``v (B,
    S, Hk, dh)`` float32; ``q`` and ``k`` normed a head, then rotated."""
    b, s, _ = a.shape
    dt = cfg.dtype
    q = _mm(a, pa["q"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    k = _mm(a, pa["k"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    v = _mm(a, pa["v"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    q = rope_half(rms_norm(q, pa["q_norm"], cfg.eps), positions,
                  cfg.rope_theta)
    k = rope_half(rms_norm(k, pa["k_norm"], cfg.eps), positions,
                  cfg.rope_theta)
    return q.astype(dt), k, v.astype(jnp.float32)


def attend_blocks(cfg: SdarMoeConfig, q, k, v):
    """The attention of a whole prompt from position 0 under the block mask
    (``ops.dispatch.prefill_attention(block=)``): ``(B, S, H*dh)``."""
    return dispatch.prefill_attention(
        q, k, v, scale=1.0 / np.sqrt(q.shape[-1]), q_block=cfg.q_block,
        dtype=cfg.dtype, block=cfg.block_tokens,
    )


def block_tail(cfg: SdarMoeConfig, pl, x, attn_out, count_mask=None):
    """Output projection and residual, then the expert half and its
    residual; the residual stream is float32. ``x (..., D)``; ``(x2,
    moe.STATS as an int32 vector)``."""
    x = x + _mm(attn_out, pl["attn"]["o"], cfg.dtype).astype(jnp.float32)
    h = rms_norm(x, pl["post_norm"], cfg.eps)
    flat = h.reshape(-1, h.shape[-1])
    pm = pl["moe"]
    out, stats = moe.dropless_moe(
        flat, None, None, pm["gate"], pm["up"], pm["down"],
        top_k=cfg.top_k, dtype=cfg.dtype, count_mask=count_mask,
        routing=moe.softmax_topk_route(flat, pm["router"], top_k=cfg.top_k),
    )
    return x + out.reshape(h.shape).astype(jnp.float32), stats


def embed(cfg: SdarMoeConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def logits(cfg: SdarMoeConfig, params, x):
    """Final norm and the untied head; float32. Position ``i``'s row
    predicts position ``i``'s token: no shift."""
    y = rms_norm(x, params["norm_f"], cfg.eps)
    return jnp.matmul(
        y.astype(cfg.dtype), params["head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
