"""Hybrid KDA / latent-attention decoder with group-limited routed experts:
five delta-rule layers whose decay is a number a key channel (KDA), then one
latent-attention (MLA) layer, over and over, a dense SwiGLU in the leading
layers and routed experts in the rest, as ``bailing_hybrid`` publishes it
(Ling-3.0-flash and its -VL sibling's language model).

Plain functions over a plain parameter tree, like ``models/olmo_hybrid.py``
(whose ``unit_lower_inverse``, ``carry_chunks``, ``_mm`` and L2 norm these
layers use, with ``granite_hybrid``'s convolution and ``mla_moe``'s
``rms_norm``, rotary, projections, attentions and untied head). ``x0 =
E[token]``; the block is pre-norm::

    h  = x + mixer_l(RMSNorm(x))
    x' = h + FFN_l(RMSNorm(h))        dense SwiGLU or experts, by the layer

and the logits are ``RMSNorm(x) W_head`` (untied). ``cfg.dtype`` (bfloat16)
is the type of every matrix product's operands; the residual stream and the
KDA layers' activations between products stay float32 (``olmo_hybrid._mm``;
PERF.md section 6, PR 33, says what a bfloat16 stream costs a state that
is rewritten every token).

**KDA** (``H`` heads, keys and values of ``d_head``, depthwise causal
convolution of width ``d_conv``, no bias)::

    [q | k | v] = W_qkv y                       (H d_head each)
    f = W_f y  (H d_head)     [b | g] = W_bg y  (H, H)
    [q | k | v]_t = silu(sum_j w_j * [q | k | v]_{t-(d_conv-1)+j})
    q = q / |q| / sqrt(d_head),  k = k / |k|       a head; L2, eps 1e-6
    log alpha = L sigmoid(exp(A_log) (f + dt_bias))     a key channel a head
    beta = sigmoid(b)                                   one a head
    S~ = Diag(alpha) S_{t-1},  u = beta (v - S~^T k),  S_t = S~ + k u^T
    o = S_t^T q
    out = W_out (RMSNorm(o) * sigmoid(g))    the norm a head, one weight
                                             (d_head,); g one number a head

``L = kda_lower_bound`` (-5: the safe gate), so ``log alpha`` lies in ``(L,
0)``. What a lane keeps of such a layer is no page: the convolution's last
``d_conv - 1`` inputs (``conv``) and ``S`` (``kda``), float32, laid ``(d_head,
H * d_head)`` as ``olmo_hybrid``'s is; the one-step update is
``ops.dispatch.kda_update``. Prefill computes the same recurrence in chunks
(:func:`kda_chunk_scan`).

**MLA** as ``models/mla_moe.py`` has it, with ``q = W_q y`` at full rank and
one output gate a head: ``out = W_o (o_h * sigmoid(g_h))``, ``g = W_g y``.
What is cached of a token is the normed latent ``c`` and the rotated key
``k_r``.

**Experts** (``parallel.moe.dropless_moe``): sigmoid scores over all
``n_experts`` in float32, selection limited to the best ``topk_group`` of
``n_group`` groups, ``top_k`` a token, weights normalised and times
``routed_scale``, plus a shared expert. This tree may hold a share of a
layer's experts (``experts_held``, from ``first_expert`` on): the router
keeps its whole width, the held experts' part of the result is computed and
nothing stands in for the rest.

Parameter tree (weights ``cfg.dtype``; norms, router, bias, the convolution,
``A_log`` and ``dt_bias`` float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/mixer_norm, ffn_norm (D,)
    layer_<i>/kda/{qkv (D, 3 H dh), f (D, H dh), bg (D, 2 H),
                   conv_w (d_conv, 3 H dh), A_log (H,), dt_bias (H dh,),
                   norm (dh,), out (H dh, D)}
    layer_<i>/attn/{q (D, H (dn + dr)), kv_a (D, Rkv + dr), kv_a_norm (Rkv,),
                    kv_b (Rkv, H (dn + dv)), g (D, H), o (H dv, D)}
    layer_<i>/mlp/{gate (D, F), up (D, F), down (F, D)}
    layer_<i>/moe/{router (D, E), bias (E,), gate (Eh, D, Fe), up (Eh, D, Fe),
                   down (Eh, Fe, D), shared/{gate, up, down}}

What the published ``config.json`` does not settle is listed in the
benchmark's configuration file under ``assumed``. A clamped SwiGLU (a
non-zero entry of the ``*_swiglu_limit_list`` on a layer that is kept), a
bias, a tied head, key heads other than value heads and a head norm over
less than a head are refused; a layer whose tree holds a low-rank query
(``q_a``, ``q_b``: a non-null ``q_lora_rank``) takes ``mla_moe``'s path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch as ops_dispatch
from ..parallel import moe
from . import granite_hybrid as gh
from . import mla_moe
from .mla_moe import logits, rms_norm  # noqa: F401 (logits: the untied head)
from .olmo_hybrid import (  # noqa: F401 (embed: float32 rows, as Olmo's)
    HI, _l2norm, _mm, carry_chunks, embed, unit_lower_inverse,
)

KINDS = ("kda", "mla")
# Positions a sub-chunk spans: the one stretch over which exp(-G) is formed.
# 16 x 5 = 80 < 88, float32's largest exponent, at the safe gate's bound.
SUB_CHUNK = 16


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]  # of KINDS, one a layer
    dense_layers: Tuple[int, ...]  # layers whose FFN is the dense SwiGLU
    n_head: int
    d_head: int  # KDA's keys and values
    kv_lora_rank: int
    d_nope: int
    d_rope: int
    d_v: int  # MLA's values
    d_ff: int
    n_experts: int  # the router's width: the whole layer's experts
    top_k: int
    n_group: int
    topk_group: int
    d_expert: int
    d_shared: int
    experts_held: Optional[int] = None  # None: all of them
    first_expert: int = 0
    routed_scale: float = 1.0
    rope_theta: float = 10000.0
    d_conv: int = 4
    log_alpha_floor: float = -5.0
    chunk: int = 64
    q_block: int = 512
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "LingHybridConfig":
        """From the keys of a published ``config.json``, and three of the
        cut's: ``layers_kept`` (which of the published layers these are;
        all of them by default), ``num_experts_published`` (the router's
        width where ``num_experts`` counts the experts held) and
        ``first_expert``."""
        kept = tuple(c.get("layers_kept", range(c["num_hidden_layers"])))
        if len(kept) != c["num_hidden_layers"]:
            raise ValueError(
                f"layers_kept names {len(kept)} layers, num_hidden_layers "
                f"{c['num_hidden_layers']}")
        served_only = {
            "kda_safe_gate": True, "use_kda_lora": False, "no_kda_lora": True,
            "gated_attention_proj_granularity_type": "head_wise",
            "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
            "use_mla_nope": False, "linear_silu": True, "use_qk_norm": True,
            "score_function": "sigmoid", "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "num_shared_experts": 1,
            "tie_word_embeddings": False, "use_bias": False,
            "use_qkv_bias": False, "use_nGPT": False, "value_norm": False,
            "up_proj_norm": False, "scale_router_input": False,
            "rope_scaling": None,
            "rotary_dim": c["qk_rope_head_dim"], "hidden_act": "silu",
        }
        for key, only in served_only.items():
            if c.get(key, only) != only:
                raise ValueError(
                    f"LingHybridConfig: {key}={c[key]!r} is not served (only "
                    f"{only!r}: the layer equations in models/ling_hybrid.py)"
                )
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = c.get(key) or ()
            clamped = [i for i in kept if i < len(limits) and limits[i]]
            if clamped:
                raise ValueError(
                    f"LingHybridConfig: {key} is non-zero on layers "
                    f"{clamped}: a clamped SwiGLU is not served")
        published = c.get("num_experts_published", c["num_experts"])
        held = c["num_experts"]
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            layer_types=tuple(
                "mla" if (i + 1) % c["layer_group_size"] == 0 else "kda"
                for i in kept),
            dense_layers=tuple(n for n, i in enumerate(kept)
                               if i < c["first_k_dense_replace"]),
            n_head=c["num_attention_heads"], d_head=c["head_dim"],
            kv_lora_rank=c["kv_lora_rank"], d_nope=c["qk_nope_head_dim"],
            d_rope=c["qk_rope_head_dim"], d_v=c["v_head_dim"],
            d_ff=c["intermediate_size"], n_experts=published,
            top_k=c["num_experts_per_tok"], n_group=c["n_group"],
            topk_group=c["topk_group"], d_expert=c["moe_intermediate_size"],
            d_shared=c["moe_shared_expert_intermediate_size"],
            experts_held=None if held == published else held,
            first_expert=c.get("first_expert", 0),
            routed_scale=c["routed_scaling_factor"],
            rope_theta=float(c["rope_theta"]),
            d_conv=c["short_conv_kernel_size"],
            log_alpha_floor=float(c["kda_lower_bound"]),
            eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        odd = set(self.layer_types) - set(KINDS)
        if odd:
            raise ValueError(f"layer_types holds {sorted(odd)}")
        if self.chunk % SUB_CHUNK:
            raise ValueError(f"chunk {self.chunk} is not whole sub-chunks of "
                             f"{SUB_CHUNK}")
        if -self.log_alpha_floor * SUB_CHUNK >= 88:
            raise ValueError(
                f"log alpha down to {self.log_alpha_floor} over {SUB_CHUNK} "
                "positions overflows float32's exponent")
        if self.n_experts % self.n_group:
            raise ValueError(f"{self.n_experts} experts in {self.n_group} "
                             "groups")
        if self.first_expert + self.n_held > self.n_experts:
            raise ValueError(
                f"experts {self.first_expert} to {self.first_expert} + "
                f"{self.n_held} of {self.n_experts}")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_held(self) -> int:
        """Experts of a layer this tree holds."""
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    @property
    def d_inner(self) -> int:
        """A KDA layer's ``q``, ``k``, ``v`` or ``f``: every head's."""
        return self.n_head * self.d_head

    @property
    def d_qkv(self) -> int:
        """Channels of the convolution: ``q``, ``k`` and ``v``."""
        return 3 * self.d_inner

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "mla")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer)
                     if i not in self.dense_layers)

    @property
    def n_cache_layers(self) -> int:
        """Layers that leave pages behind (the serve plan's frames)."""
        return len(self.attention_layers)

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's cache over the MLA layers: the
        latent and the rotated key (a KDA layer's cache does not grow with
        the tokens)."""
        return self.n_cache_layers * (self.kv_lora_rank + self.d_rope) * 4

    def state_bytes_per_lane(self) -> int:
        """float32 bytes of the recurrent state a lane holds over the KDA
        layers, whatever its length."""
        per_layer = ((self.d_conv - 1) * self.d_qkv
                     + self.d_head * self.d_inner)
        return (self.n_layer - self.n_cache_layers) * per_layer * 4


# ---------------------------------------------------------------------------
# KDA.
# ---------------------------------------------------------------------------


def kda_project(cfg: LingHybridConfig, y, pk):
    """``y (..., D)`` (already normed) -> the convolution's input ``qkv
    (..., 3 H dh)``, the decay's ``f (..., H dh)`` and the raw ``b``, ``g
    (..., H)``, float32 (what the float32 ``conv`` state holds is not
    rounded first)."""
    bg = _mm(y, pk["bg"], cfg.dtype)
    h = cfg.n_head
    return (_mm(y, pk["qkv"], cfg.dtype), _mm(y, pk["f"], cfg.dtype),
            bg[..., :h], bg[..., h:])


def _step_operands(cfg: LingHybridConfig, pk, qkv, f, b, live=None):
    """What the recurrence takes of a token: ``qkv (..., 3 H dh)`` after the
    convolution, ``f (..., H dh)`` and the raw ``b (..., H)`` -> ``q``, ``k
    (..., H, dh)`` (normalised, ``q`` over ``sqrt(dh)`` too), ``v (..., H,
    dh)``, ``log alpha (..., H, dh)`` and ``beta (..., H)`` (both 0 where
    ``live`` is False), float32."""
    h, dh = cfg.n_head, cfg.d_head
    lead = qkv.shape[:-1]
    q, k, v = (qkv[..., i * h * dh: (i + 1) * h * dh].reshape(*lead, h, dh)
               for i in range(3))
    q = _l2norm(q) * np.float32(dh ** -0.5)
    k = _l2norm(k)
    gate = jnp.exp(pk["A_log"])[:, None] * (f + pk["dt_bias"]).reshape(
        *lead, h, dh)
    log_alpha = np.float32(cfg.log_alpha_floor) * jax.nn.sigmoid(gate)
    beta = jax.nn.sigmoid(b)
    if live is not None:
        beta = jnp.where(live, beta, 0.0)
        log_alpha = jnp.where(live[..., None], log_alpha, 0.0)
    return q, k, v, log_alpha, beta


def _gated_out(cfg: LingHybridConfig, pk, o, g):
    """``W_out (RMSNorm(o) * sigmoid(g))``: ``o (..., H, dh)`` float32, the
    norm a head, ``g (..., H)`` one gate a head."""
    gated = rms_norm(o, pk["norm"], cfg.eps) * jax.nn.sigmoid(g)[..., None]
    return _mm(gated.reshape(*o.shape[:-2], cfg.d_inner), pk["out"],
               cfg.dtype)


def kda_chunk_scan(q, k, v, log_alpha, beta, chunk: int, state0=None):
    """The recurrence ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`` with ``u_t
    = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)``, ``o_t = S_t^T q_t``
    over ``S`` positions in chunks: ``olmo_hybrid.gdn_chunk_scan``'s WY form
    with a decay a key channel.

    ``q``, ``k``, ``log_alpha (B, S, H, dk)`` (``log_alpha <= 0``), ``v (B,
    S, H, dv)``, ``beta (B, S, H)`` (``beta = 0`` with ``log_alpha = 0``
    makes a position the identity), ``state0 (B, H, dk, dv)`` or None for
    zeros; float32. Returns ``o (B, S, H, dv)`` and the state after position
    ``S - 1``. Within a chunk, with ``G`` the running sum of ``log alpha``
    and ``P(a, b)_ij = sum_c a_ic b_jc exp(G_ic - G_jc)`` for ``j <= i``:
    solve ``(I + strict_tril(diag(beta) P(k, k))) [W, U] = diag(beta) [K *
    exp(G), V]``, then with the state ``S`` entering the chunk ``V' = U - W
    S``, ``O = (Q * exp(G)) S + tril(P(q, k)) V'`` and ``S' = Diag(exp(G_end))
    S + (K * exp(G_end - G))^T V'`` (``olmo_hybrid.carry_chunks``).

    ``P`` is never formed pairwise (a ``(L, L, dk)`` array of exponents a
    chunk a head). The rows of a sub-chunk of :data:`SUB_CHUNK` positions
    share a reference ``R``, ``G`` at the sub-chunk's first position: ``P_ij
    = (a_i * exp(G_i - R)) . (b_j * exp(R - G_j))``, a matrix product. The
    first exponent is at most 0; the second is at most 0 for a column before
    the sub-chunk, spans at most the sub-chunk for a column inside it
    (``-log alpha`` x 15, which the configuration's bound keeps under
    float32's range), and a column after it takes no part (its factor is set
    to 0). The products run at full float32 precision, as ``gdn_chunk_scan``'s
    do."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_alpha, beta)
        )
    nc, ns = (s + pad) // chunk, chunk // SUB_CHUNK

    def chunks(t):  # (B, S, H, ...) -> (B, nc, H, L, ...)
        t = t.reshape(b, nc, chunk, *t.shape[2:])
        return jnp.moveaxis(t, 3, 2)

    with jax.named_scope("cgx_kda_chunk_scan"):
        q, k, v, g, beta = (chunks(t) for t in (q, k, v, log_alpha, beta))
        g = jnp.cumsum(g, axis=-2)  # (b, nc, h, L, dk): log decay up to i
        pos = np.arange(chunk)
        # (b, nc, h, ns, 1, dk): G at each sub-chunk's first position.
        ref = g[..., ::SUB_CHUNK, :][..., None, :]
        rows = jnp.exp(
            g.reshape(b, nc, h, ns, SUB_CHUNK, dk) - ref)  # exponents <= 0
        # Columns up to a sub-chunk's last position, for each sub-chunk.
        reach = pos[None, :] < (np.arange(ns)[:, None] + 1) * SUB_CHUNK
        cols = k[..., None, :, :] * jnp.exp(jnp.where(
            reach[..., None], ref - g[..., None, :, :], -jnp.inf))

        def pairs(a):  # P(a, k): (b, nc, h, L, L)
            a = a.reshape(b, nc, h, ns, SUB_CHUNK, dk) * rows
            return jnp.einsum("bchnid,bchnjd->bchnij", a, cols,
                              precision=HI).reshape(b, nc, h, chunk, chunk)

        lower = np.tril(np.ones((chunk, chunk), bool))
        inv = unit_lower_inverse(jnp.where(
            np.tril(lower, -1), beta[..., None] * pairs(k), 0.0))
        eg = jnp.exp(g)
        w = jnp.matmul(inv, beta[..., None] * k * eg, precision=HI)
        u = jnp.matmul(inv, beta[..., None] * v, precision=HI)
        qk = jnp.where(lower, pairs(q), 0.0)
        k_end = k * jnp.exp(g[..., -1:, :] - g)
        whole = eg[..., -1, :]  # (b, nc, h, dk): decay over a chunk

        if state0 is None:
            state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        final, o = carry_chunks(state0, w, u, q * eg, qk, k_end, whole)
    # (nc, b, h, L, dv) -> (b, S, h, dv)
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, nc * chunk, h, dv)
    return o[:, :s], final


def kda_prefill(cfg: LingHybridConfig, pk, y, last_idx):
    """The mixer over a whole (right-padded, already normed) prompt ``y (B,
    S, D)``: ``(out (B, S, D), conv (B, d_conv - 1, 3 H dh), kda (B, dh, H
    dh))``, the state a lane holds after position ``last_idx``: positions
    past it are identity steps and the convolution's state is its ``d_conv
    - 1`` inputs ending at ``last_idx`` (zeros before the prompt's start)."""
    b, s, _ = y.shape
    qkv_in, f, b_raw, g_raw = kda_project(cfg, y, pk)
    conv, padded = gh.conv_prefill(pk["conv_w"], None, qkv_in)
    live = (jnp.arange(s) <= last_idx)[None, :, None]
    q, k, v, log_alpha, beta = _step_operands(
        cfg, pk, jax.nn.silu(conv), f, b_raw, live)
    o, state = kda_chunk_scan(q, k, v, log_alpha, beta, cfg.chunk)
    # (B, H, dk, dv) -> the lanes' layout (B, dk, H*dv).
    state = state.transpose(0, 2, 1, 3).reshape(b, cfg.d_head, cfg.d_inner)
    return (_gated_out(cfg, pk, o, g_raw),
            gh.conv_state_at(padded, last_idx, cfg.d_conv), state)


def kda_step(cfg: LingHybridConfig, pk, y, conv_state, state):
    """One token a lane: ``y (B, D)`` (already normed), ``conv_state (B,
    d_conv - 1, 3 H dh)``, ``state (B, dh, H dh)`` -> ``(out (B, D), the new
    conv state, the new state)``. The state update is
    ``ops.dispatch.kda_update`` (one kernel over all lanes on the chip)."""
    qkv_in, f, b_raw, g_raw = kda_project(cfg, y, pk)
    conv, window = gh.conv_step(pk["conv_w"], None, conv_state, qkv_in)
    q, k, v, log_alpha, beta = _step_operands(
        cfg, pk, jax.nn.silu(conv), f, b_raw)
    new_state, o = ops_dispatch.kda_update(
        state, q, k, v, jnp.exp(log_alpha), beta)
    o = o.reshape(-1, cfg.n_head, cfg.d_head)
    return (_gated_out(cfg, pk, o, g_raw),
            window[:, 1:].astype(conv_state.dtype), new_state)


# ---------------------------------------------------------------------------
# MLA: ``mla_moe``'s, with a gate a head before the output projection.
# ---------------------------------------------------------------------------


def mla_out(cfg: LingHybridConfig, pa, o, y):
    """``W_o (o_h * sigmoid(g_h))``, ``g = W_g y``: ``o (..., H * dv)`` as
    the attention returns it, ``y (..., D)`` the layer's normed input;
    float32."""
    g = jax.nn.sigmoid(_mm(y, pa["g"], cfg.dtype))[..., None]
    gated = o.astype(jnp.float32).reshape(*g.shape[:-1], cfg.d_v) * g
    return _mm(gated.reshape(o.shape), pa["o"], cfg.dtype)


# ---------------------------------------------------------------------------
# The model.
# ---------------------------------------------------------------------------


def swiglu(cfg: LingHybridConfig, y, p):
    dt = cfg.dtype
    return _mm(jax.nn.silu(_mm(y, p["gate"], dt)) * _mm(y, p["up"], dt),
               p["down"], dt)


def ffn(cfg: LingHybridConfig, pl, y, count_mask=None):
    """The layer's feed-forward half over ``y (..., D)`` (already normed):
    ``(out float32, stats)``; ``stats`` is None for a dense layer, else
    ``moe.HELD_STATS`` (``moe.STATS`` for a tree that holds every expert) as
    an int32 vector."""
    if "mlp" in pl:
        return swiglu(cfg, y, pl["mlp"]), None
    pm = pl["moe"]
    flat = y.reshape(-1, y.shape[-1])
    out, stats = moe.dropless_moe(
        flat, pm["router"], pm["bias"], pm["gate"], pm["up"], pm["down"],
        top_k=cfg.top_k, scale=cfg.routed_scale, dtype=cfg.dtype,
        count_mask=count_mask, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        held=None if cfg.experts_held is None else cfg.first_expert,
    )
    out = out.astype(jnp.float32) + swiglu(cfg, flat, pm["shared"])
    return out.reshape(y.shape), stats


def ffn_half(cfg: LingHybridConfig, pl, h, count_mask=None):
    """``h + FFN(RMSNorm(h))`` and the expert layer's counts (or None)."""
    out, stats = ffn(cfg, pl, rms_norm(h, pl["ffn_norm"], cfg.eps),
                     count_mask)
    return h + out, stats


def forward(cfg: LingHybridConfig, params, tokens):
    """Logits ``(B, S, V)`` of whole sequences, no cache: the chunked delta
    rule and the expanded attention as prefill runs them."""
    x = embed(cfg, params, tokens)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    for layer, kind in enumerate(cfg.layer_types):
        pl = params[f"layer_{layer}"]
        y = rms_norm(x, pl["mixer_norm"], cfg.eps)
        if kind == "kda":
            out, _, _ = kda_prefill(cfg, pl["kda"], y, s - 1)
        else:
            pa = pl["attn"]
            q_nope, q_rope, c, k_r = mla_moe.mla_project(cfg, y, pa, positions)
            out = mla_out(cfg, pa, mla_moe.attend_expanded(
                cfg, pa, q_nope, q_rope, c, k_r), y)
        x, _ = ffn_half(cfg, pl, x + out)
    return logits(cfg, params, x)
