"""GPT-2 style decoder-only transformer (flax.linen) — the flagship model.

BASELINE.md's "GPT-2 medium pretrain DDP, 2-bit QSGD" config needs a real
decoder; the reference itself ships no models (SURVEY.md §0). TPU-first
choices: bf16 activations with f32 params/logits, fused qkv projection,
einsum attention shaped for the MXU, and tensor-parallel-ready parameter
layouts (column-parallel qkv/mlp-in, row-parallel proj/mlp-out — apply
:func:`tp_param_spec` with jit in_shardings and GSPMD inserts the TP
collectives). ``attn_fn`` plugs in ring-attention sequence parallelism
(parallel/ring_attention.py) for long contexts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .attention import Mlp, MultiHeadAttention, dense_attention


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    max_seq: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    # Mixture-of-experts (0 = dense MLP). Experts shard over ``ep_axis``
    # when set (parallel/moe.py).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    ep_axis: Optional[str] = None

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over all layers."""
        return 2 * self.n_layer * self.d_model * 4

    def state_bytes_per_lane(self) -> int:
        """No recurrent state beside the pages."""
        return 0

    @staticmethod
    def small(**kw):
        return GPT2Config(**kw)

    @staticmethod
    def medium(**kw):
        return GPT2Config(n_layer=24, n_head=16, d_model=1024, **kw)

    @staticmethod
    def tiny(**kw):
        """Test/dryrun config."""
        defaults = dict(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                        max_seq=128)
        defaults.update(kw)
        return GPT2Config(**defaults)


class Block(nn.Module):
    cfg: GPT2Config
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask=None, train: bool = True):
        cfg = self.cfg
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x).astype(cfg.dtype)
        x = x + MultiHeadAttention(
            cfg.d_model, cfg.n_head, dtype=cfg.dtype, causal=True,
            attn_fn=self.attn_fn, dropout=cfg.dropout, name="attn",
        )(y, mask=mask, train=train)
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x).astype(cfg.dtype)
        if cfg.n_experts > 0:
            from ..parallel.moe import MoEMlp

            return x + MoEMlp(
                cfg.d_model,
                n_experts=cfg.n_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dtype=cfg.dtype,
                ep_axis=cfg.ep_axis,
                name="moe_mlp",
            )(y, train=train)
        return x + Mlp(
            cfg.d_model, dtype=cfg.dtype, dropout=cfg.dropout, name="mlp"
        )(y, train=train)


class GPT2(nn.Module):
    cfg: GPT2Config
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, positions=None, attn_mask=None,
                 train: bool = True):
        """``positions``: optional global token positions (B, S) or (S,) —
        required under sequence parallelism, where the local shard's
        positions are not ``arange(s_local)``.

        ``attn_mask``: optional bool (B, S) key-padding mask (True =
        attend), passed to every block's attention; under sequence
        parallelism pass the LOCAL (B, S_local) slice, sharded like the
        tokens."""
        cfg = self.cfg
        b, s = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="wte")
        pos = nn.Embed(cfg.max_seq, cfg.d_model, dtype=cfg.dtype, name="wpe")
        if positions is None:
            positions = jnp.arange(s)[None, :]
        elif positions.ndim == 1:
            positions = positions[None, :]
        x = wte(tokens) + pos(positions)
        if cfg.dropout:
            x = nn.Dropout(cfg.dropout, deterministic=not train)(x)
        for i in range(cfg.n_layer):
            x = Block(cfg, attn_fn=self.attn_fn, name=f"h_{i}")(
                x, mask=attn_mask, train=train
            )
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        # tied embedding head, f32 logits
        logits = x.astype(jnp.float32) @ wte.embedding.astype(jnp.float32).T
        return logits


def lm_loss(logits, tokens):
    """Next-token cross entropy (shifted)."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def sp_lm_loss(logits, tokens, axis_name: str):
    """Next-token cross entropy when the SEQUENCE dim is sharded over
    ``axis_name`` (ring-attention training). The target of a local block's
    last token is the *next shard's first token* — fetched with one
    single-column ``ppermute`` — and only the global final position has no
    target. Returns the global mean (identical to :func:`lm_loss` on the
    unsharded sequence), replicated across the axis."""
    ws = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # dst i receives from src i+1: the right neighbor's first column.
    perm = [((i + 1) % ws, i) for i in range(ws)]
    first_right = jax.lax.ppermute(tokens[:, :1], axis_name, perm)
    tgt = jnp.concatenate([tokens[:, 1:], first_right], axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    # Mask the global last position (its "target" wrapped around the ring).
    s_local = tokens.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, ll.shape, 1)
    mask = jnp.where(
        jnp.logical_and(idx == ws - 1, col == s_local - 1), 0.0, 1.0
    )
    total = jax.lax.psum(jnp.sum(ll * mask), axis_name)
    count = jax.lax.psum(jnp.sum(mask), axis_name)
    return -total / count


def tp_param_spec(path: str, leaf) -> P:
    """Tensor-parallel PartitionSpec for a GPT-2 param by tree path.

    Megatron-style: qkv and mlp_in are column-parallel (shard output dim over
    'tp'), attn_proj and mlp_out row-parallel (shard input dim), embeddings
    sharded on the feature dim. Biases of row-parallel layers stay
    replicated. GSPMD derives the matching collectives.
    """
    if leaf.ndim < 1:
        return P()
    if "attn_qkv" in path or "mlp_in" in path:
        return P(None, "tp") if leaf.ndim == 2 else P("tp")
    if "attn_proj" in path or "mlp_out" in path:
        return P("tp", None) if leaf.ndim == 2 else P()
    if "wte" in path or "wpe" in path:
        return P(None, "tp")
    return P()
