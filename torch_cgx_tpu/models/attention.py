"""Shared multi-head attention + MLP blocks for the transformer model zoo.

One implementation of the qkv-projection / head-split / attention /
head-merge / output-projection plumbing, reused by GPT-2, BERT, and ViT.
Parameter names (``attn_qkv``, ``attn_proj``, ``mlp_in``, ``mlp_out``) are
the contract :func:`torch_cgx_tpu.models.gpt2.tp_param_spec` matches on for
tensor-parallel sharding — keep them stable.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import CgxDense


def dense_attention(q, k, v, *, causal: bool = True, mask=None):
    """(B, H, S, D) einsum attention on the MXU; f32 softmax.

    ``mask``: optional key-side padding mask, bool (B, S) or broadcastable to
    (B, H, Sq, Sk); True = attend.
    """
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.float32(np.sqrt(d))
    if causal:
        s = q.shape[2]
        cm = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(cm, scores, np.float32(-1e30))
    if mask is not None:
        if mask.ndim == 2:  # (B, Sk) key padding
            mask = mask[:, None, None, :]
        scores = jnp.where(mask, scores, np.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def joined_softmax(scores, mask, tail_scores=None, tail_mask=None, *, dtype):
    """One float32 softmax over a lane's committed pages and its tail,
    which are read apart: ``scores (B, H, T)`` over the page table's
    positions and ``tail_scores (B, H, Tt)`` over the tail's, ``mask (B,
    T)`` / ``tail_mask (B, Tt)`` True at a live position. Joined here (an
    array of scores, never of keys or values), then split again:
    ``(page probabilities, tail probabilities)`` in ``dtype``; the second
    is None without a tail. Both decode attentions go through this, so a
    cache read never has to be concatenated with its tail."""
    t = scores.shape[-1]
    if tail_scores is not None:
        scores = jnp.concatenate([scores, tail_scores], axis=-1)
        mask = jnp.concatenate([mask, tail_mask], axis=-1)
    scores = jnp.where(mask[:, None, :], scores, np.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if tail_scores is None:
        return probs, None
    return probs[..., :t], probs[..., t:]


def decode_attention(q, k, v, k_tail, v_tail, *, mask, tail_mask,
                     score_divisor=None):
    """Single-position decode attention over a lane's cache as it lies.

    ``q``: (B, H, D) — the lane's current token. ``k``/``v``: (B, T, Hk*D)
    — the page table's positions as ``ops.paged_kv.gather_dequant_pages``
    returns them: one row a position, the K/V heads side by side, garbage
    beyond each lane's committed length. ``k_tail``/``v_tail``: (B, Tt,
    Hk*D), the tail rows the same way. ``mask`` (B, T) / ``tail_mask`` (B,
    Tt): True = a live position. Returns (B, H*D) in ``q.dtype``.
    Causality is implied: every live cached position precedes (or is) the
    query token, so the masks ARE the causal mask. ``Hk`` is the rows'
    width over ``D``: with fewer K/V heads than query heads (grouped
    queries) K/V head ``g`` is read by the ``H / Hk`` query heads ``g * H /
    Hk`` onward. The scores are divided by ``score_divisor`` (``sqrt(D)``
    unless given).

    The rows are contracted where they lie. Each head's query is laid
    into its own D columns of an H*D row (zeros elsewhere), so the scores
    are one dot of the rows against H such rows, and the weighted sum is
    one dot of the probabilities against the rows, of which a head keeps
    its own D columns: no ``(B, H, T, D)`` copy of the table is asked for
    (PR 27's trace: transposing, concatenating and casting that copy took
    104.8 ms of a 169.6 ms GPT-2 large step; XLA did not fuse them into
    the read). The added products are exact zeros. float32 scores and
    softmax, ``q.dtype`` probabilities, the pages' and the tail's weighted
    sums accumulated and added in float32 and cast once."""
    b, h, d = q.shape
    hk = k.shape[-1] // d
    own = jnp.eye(hk, dtype=q.dtype)
    if h != hk:  # (H, Hk): which K/V head a query head reads
        own = jnp.repeat(own, h // hk, axis=0)
    q_rows = (q[:, :, None, :] * own[:, :, None]).reshape(b, h, hk * d)
    if score_divisor is None:
        score_divisor = np.sqrt(d)

    def scores(rows):
        return jnp.einsum("bhw,btw->bht", q_rows, rows,
                          preferred_element_type=jnp.float32
                          ) / np.float32(score_divisor)

    def weighted(probs, rows):
        return jnp.einsum("bht,btw->bhw", probs, rows,
                          preferred_element_type=jnp.float32)

    probs, tail_probs = joined_softmax(
        scores(k), mask, scores(k_tail), tail_mask, dtype=q.dtype
    )
    o = weighted(probs, v) + weighted(tail_probs, v_tail)  # (B, H, Hk*D) f32
    # A query head keeps its own K/V head's D columns (one product of each
    # sum is not an exact zero). With as many K/V heads as query heads
    # ``own`` is symmetric and the sum over either axis is the same array.
    o = jnp.sum(
        o.reshape(b, h, hk, d) * own.astype(jnp.float32)[:, :, None],
        axis=1 if h == hk else 2,
    )
    return o.reshape(b, h * d).astype(q.dtype)


class MultiHeadAttention(nn.Module):
    """qkv projection -> heads -> ``attn_fn`` -> merge -> output projection.

    ``attn_fn(q, k, v, causal=...)`` defaults to :func:`dense_attention`;
    ring-attention sequence parallelism plugs in here.
    """

    d_model: int
    n_head: int
    dtype: Any = jnp.bfloat16
    causal: bool = True
    attn_fn: Optional[Callable] = None
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, mask=None, train: bool = True):
        h = self.n_head
        d_head = self.d_model // h
        qkv = CgxDense(3 * self.d_model, dtype=self.dtype, name="attn_qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # (B, S, D) -> (B, H, S, d)
            b, s, _ = t.shape
            return t.reshape(b, s, h, d_head).transpose(0, 2, 1, 3)

        attn = self.attn_fn or dense_attention
        kw = {} if mask is None else {"mask": mask}
        o = attn(heads(q), heads(k), heads(v), causal=self.causal, **kw)
        b, _, s, _ = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.d_model)
        o = CgxDense(self.d_model, dtype=self.dtype, name="attn_proj")(o)
        if self.dropout:
            o = nn.Dropout(self.dropout, deterministic=not train)(o)
        return o


class Mlp(nn.Module):
    """Dense -> gelu -> Dense feed-forward block."""

    d_model: int
    ratio: int = 4
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True):
        y = CgxDense(self.ratio * self.d_model, dtype=self.dtype, name="mlp_in")(x)
        y = nn.gelu(y)
        y = CgxDense(self.d_model, dtype=self.dtype, name="mlp_out")(y)
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return y
