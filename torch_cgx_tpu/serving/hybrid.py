"""The hybrid state-space adapter: a decoder whose layers are mostly
Mamba-2 with a few grouped-query attention layers among them
(``models/granite_hybrid.py``) behind the one scheduler.

Its layers name different streams. An attention layer leaves pages: ``k``
and ``v``, rows of ``n_kv_head * d_head`` (no positional embedding, so
what is cached is the projection itself). A Mamba layer leaves none: what
a lane keeps of it is a fixed-size recurrent state, ``conv (d_conv - 1,
d_inner + 2 d_state)`` (the convolution's last inputs) and ``ssm (d_state,
d_inner)``, float32, which every decode step rewrites whole
(``ops.dispatch.ssm_update``: one kernel a layer over all lanes, in place)
and an admission overwrites with what the prefill left at the prompt's last
token. ``state_streams`` states them; the scheduler carries them in the
donated state beside pools and tails.

Page geometry is the streams' arithmetic (``serving/latent.py`` says the
same of its own): at 256 tokens a page and bucket 512, a ``k`` or ``v``
page of eight heads of 64 is 256 buckets, eight whole 32-bucket chunks,
one bucket a token, rows of 512: the flat Mosaic kernels at commit and the
paged read at decode.

The disaggregated path ships K and V frames of every layer and no state;
it refuses this adapter (``transport.require_kv_streams``), which is
served with local prefill.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..models import granite_hybrid as gh
from ..models.attention import decode_attention
from ..models.granite_hybrid import HybridConfig
from ..models.mla_moe import _mm, rms_norm
from ..ops import paged_kv
from .scheduler import ServeConfig, page_specs


class HybridSSMServer:
    """Model adapter (the protocol is in ``scheduler.py``) for one
    ``(HybridConfig, params)`` pair: cache streams ``k`` and ``v`` on the
    attention layers, state streams ``conv`` and ``ssm`` (``state_dtype``,
    float32 unless a control asks for less) on the Mamba layers."""

    kind = "hybrid_ssm"
    step_counters = ()

    def __init__(self, model_cfg: HybridConfig, params,
                 serve: Optional[ServeConfig] = None,
                 state_dtype: Any = jnp.float32):
        self.cfg = model_cfg
        self.p = params
        self.serve = serve or ServeConfig.from_env(model_cfg)
        self.state_dtype = jnp.dtype(state_dtype)
        self.n_layer = model_cfg.n_layer
        self.geometry = tuple(
            (f.name, str(getattr(model_cfg, f.name)))
            for f in dataclasses.fields(model_cfg)
        )

    def layer_name(self, layer: int) -> str:
        return f"layer_{layer}"

    def cache_streams(self, layer: int):
        cfg = self.cfg
        if cfg.layer_types[layer] != "attention":
            return ()
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(cfg.n_kv_head, cfg.d_head)])
        return (("k", spec), ("v", spec))

    def state_streams(self, layer: int):
        cfg = self.cfg
        if cfg.layer_types[layer] != "mamba":
            return ()
        return (
            ("conv", (cfg.d_conv - 1, cfg.d_xbc), self.state_dtype),
            ("ssm", (cfg.d_state, cfg.d_inner), self.state_dtype),
        )

    def with_params(self, params) -> "HybridSSMServer":
        return HybridSSMServer(self.cfg, params, self.serve,
                               self.state_dtype)

    def kv_bytes_per_token(self) -> int:
        return self.cfg.kv_bytes_per_token()

    def state_bytes_per_lane(self) -> int:
        return (self.cfg.state_bytes_per_lane() // 4
                * self.state_dtype.itemsize)

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``; each attention layer's ``k`` and ``v`` ``(B, S, Hk,
        dh)`` f32 (right-padding is inert under the causal mask); each
        Mamba layer's ``conv`` and ``ssm`` state after position
        ``last_idx``, which the pad does not reach
        (``granite_hybrid.mamba_prefill``). One list a stream, None for a
        layer without it. ``positions`` is not used: nothing is rotated."""
        cfg = self.cfg
        x = gh.embed(cfg, self.p, tokens)
        out = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "ssm")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "mamba":
                mixed, conv, state = gh.mamba_prefill(
                    cfg, pl["mamba"], y, last_idx
                )
                out["conv"][layer] = conv.astype(self.state_dtype)
                out["ssm"][layer] = state.astype(self.state_dtype)
            else:
                q, k, v = gh.attn_project(cfg, y, pl["attn"])
                out["k"][layer] = k.astype(jnp.float32)
                out["v"][layer] = v.astype(jnp.float32)
                mixed = _mm(gh.attend_grouped(cfg, q, k, v),
                            pl["attn"]["o"], cfg.dtype)
            x = gh.mlp_half(cfg, pl, gh.residual(cfg, x, mixed))
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return (gh.logits(cfg, self.p, x_last)[:, -1], out["k"], out["v"],
                out["conv"], out["ssm"])

    def decode_forward(self, state, streams):
        """One decode position: an attention layer reads its committed
        pages (``cfg.dtype`` rows, contracted where they lie) and, apart,
        its raw tail with this token's K and V appended; a Mamba layer
        takes one step of its recurrence and hands back its state,
        rewritten. Returns (logits (B, V), the new tails and states by
        stream, None)."""
        cfg, dt = self.cfg, self.cfg.dtype
        pt = self.serve.page_tokens
        p_dim = self.serve.pages_per_seq
        x = gh.embed(cfg, self.p, state["tokens"][:, None])[:, 0]  # (B, D)
        b = x.shape[0]
        tail_idx = jnp.minimum(state["tail_len"], pt - 1)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1)
            == tail_idx[:, None]
        )[:, :, None, None]
        committed = state["n_pages"] * pt
        pos_c = jax.lax.broadcasted_iota(jnp.int32, (b, p_dim * pt), 1)
        pos_t = jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1)
        mask_c = pos_c < committed[:, None]
        mask_t = pos_t <= tail_idx[:, None]
        width = cfg.n_kv_head * cfg.d_head
        new = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "ssm")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "mamba":
                mixed, new["conv"][layer], new["ssm"][layer] = gh.mamba_step(
                    cfg, pl["mamba"], y, state["state_conv"][layer],
                    state["state_ssm"][layer],
                )
            else:
                q, k, v = gh.attn_project(cfg, y[:, None], pl["attn"])
                pages, tails = {}, {}
                for (name, spec), fresh in zip(streams[layer], (k, v)):
                    tail = jnp.where(
                        onehot, fresh.astype(jnp.float32),
                        state[f"tail_{name}"][layer],
                    )
                    new[name][layer] = tail
                    tails[name] = tail.reshape(b, pt, width).astype(dt)
                    pages[name] = paged_kv.gather_dequant_pages(
                        state["pools"][layer][name], state["page_table"],
                        spec, dt,
                    )
                o = decode_attention(
                    q[:, 0], pages["k"], pages["v"], tails["k"], tails["v"],
                    mask=mask_c, tail_mask=mask_t,
                    score_divisor=1.0 / cfg.attention_multiplier,
                )
                mixed = _mm(o, pl["attn"]["o"], dt)
            x = gh.mlp_half(cfg, pl, gh.residual(cfg, x, mixed))
        return gh.logits(cfg, self.p, x), new, None
