"""The GPT-2 adapter: explicit-parameter forward passes over the module's
own parameter tree (``models/gpt2.py``). Decode against the paged cache
needs per-layer K/V in and out, which the flax module does not expose.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..models.attention import decode_attention, dense_attention
from ..models.gpt2 import GPT2Config
from .adapter import (
    Adapter,
    ServeConfig,
    lane_masks,
    layer_cache_rows,
    page_specs,
)


def _ln(x, scale, bias, eps=1e-6):
    """flax.linen.LayerNorm numerics (f32 stats, rsqrt, mean2 variance)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    mean2 = (xf * xf).mean(-1, keepdims=True)
    var = jnp.maximum(0.0, mean2 - mean * mean)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)


def _dense(x, w, b, dtype):
    y = x.astype(dtype) @ w.astype(dtype)
    return y + b.astype(dtype) if b is not None else y


class GPT2Server(Adapter):
    """The GPT-2 adapter: prefill/decode forwards + serving geometry for
    one (GPT2Config, params) pair, cache streams ``k`` and ``v``. It is
    the dense-MLP GPT-2 block and nothing else; a model with experts is
    served by an adapter that has them (``serving/latent.py``)."""

    kind = "gpt2"

    def __init__(self, model_cfg: GPT2Config, params,
                 serve: Optional[ServeConfig] = None):
        if model_cfg.n_experts:
            raise ValueError(
                "GPT2Server is the dense-MLP GPT-2 adapter: it has no "
                f"expert layer for n_experts={model_cfg.n_experts} (the "
                "serving plane serves experts through "
                "serving.latent.LatentMoEServer)"
            )
        super().__init__(model_cfg, params.get("params", params), serve)
        self.n_head = model_cfg.n_head
        self.d_head = model_cfg.d_model // model_cfg.n_head

    def cache_streams(self, layer: int):
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(self.n_head, self.d_head)])
        return (("k", spec), ("v", spec))

    # -- forwards ----------------------------------------------------------

    def _embed(self, tokens, positions):
        wte = self.p["wte"]["embedding"]
        wpe = self.p["wpe"]["embedding"]
        x = wte[tokens] + wpe[positions]
        return x.astype(self.cfg.dtype)

    def _logits(self, x):
        x = _ln(x, self.p["ln_f"]["scale"], self.p["ln_f"]["bias"])
        wte = self.p["wte"]["embedding"].astype(jnp.float32)
        return x.astype(jnp.float32) @ wte.T

    def _block_tail(self, x, pl, attn_out):
        """Shared post-attention half of a block: proj residual + MLP."""
        dtype = self.cfg.dtype
        ap = pl["attn"]["attn_proj"]
        x = x + _dense(attn_out, ap["kernel"], ap.get("bias"), dtype)
        y = _ln(x, pl["ln_2"]["scale"], pl["ln_2"]["bias"]).astype(dtype)
        mi, mo = pl["mlp"]["mlp_in"], pl["mlp"]["mlp_out"]
        h = jax.nn.gelu(_dense(y, mi["kernel"], mi.get("bias"), dtype))
        return x + _dense(h, mo["kernel"], mo.get("bias"), dtype)

    def _qkv(self, x, pl):
        dtype = self.cfg.dtype
        aq = pl["attn"]["attn_qkv"]
        y = _ln(x, pl["ln_1"]["scale"], pl["ln_1"]["bias"]).astype(dtype)
        qkv = _dense(y, aq["kernel"], aq.get("bias"), dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # (B, S, Dm) -> (B, H, S, Dh)
            b, s, _ = t.shape
            return t.reshape(b, s, self.n_head, self.d_head).transpose(
                0, 2, 1, 3
            )

        return heads(q), heads(k), heads(v)

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt, returning
        the logits at ``last_idx`` and every layer's K/V.

        tokens/positions: (B, S) int32 — S is the PADDED length
        (prompts pad to a page multiple so distinct prompt lengths share
        one compiled program; under causal attention right-padding
        cannot perturb any earlier position's K/V or the ``last_idx``
        logits). Returns (logits (B, vocab), ks, vs): each a list per
        layer of (B, S, H, Dh) f32 — the cache payload the pages
        quantize (callers slice off the pad)."""
        x = self._embed(tokens, positions)
        ks: List[jax.Array] = []
        vs: List[jax.Array] = []
        for layer in range(self.cfg.n_layer):
            pl = self.p[f"h_{layer}"]
            q, k, v = self._qkv(x, pl)
            ks.append(k.transpose(0, 2, 1, 3).astype(jnp.float32))
            vs.append(v.transpose(0, 2, 1, 3).astype(jnp.float32))
            o = dense_attention(q, k, v, causal=True)
            b, _, s, _ = o.shape
            o = o.transpose(0, 2, 1, 3).reshape(b, s, self.cfg.d_model)
            x = self._block_tail(x, pl, o)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return self._logits(x_last)[:, -1], ks, vs

    def decode_forward(self, state, streams):
        """One decode position against the paged cache: current tokens at
        their positions, KV read = gathered committed pages (decoded to
        ``cfg.dtype`` rows, contracted where they lie) and, apart, the raw
        tail with this token's K/V appended; one softmax over both.
        Returns (logits (B, vocab), the new tails by stream, None)."""
        cfg = self.cfg
        x = self._embed(state["tokens"][:, None], state["pos"][:, None])
        tail_idx, mask_c, mask_t = lane_masks(self.serve, state)
        new: Dict[str, List[jax.Array]] = {"k": [], "v": []}
        for layer in range(cfg.n_layer):
            pl = self.p[f"h_{layer}"]
            q, k, v = self._qkv(x, pl)  # (B, H, 1, Dh)
            pages, tails, written = layer_cache_rows(
                state, layer, streams[layer], tail_idx,
                (k[:, :, 0], v[:, :, 0]), cfg.dtype,
            )
            for name, tail in written.items():
                new[name].append(tail)
            o = decode_attention(
                q[:, :, 0], pages["k"], pages["v"], tails["k"], tails["v"],
                mask=mask_c, tail_mask=mask_t,
            )
            x = self._block_tail(x, pl, o[:, None])
        return self._logits(x)[:, -1], new, None

