"""Hybrid gated delta-rule / attention decoder: three gated delta-rule
layers, then one full-attention layer, over and over, a SwiGLU MLP in every
layer, as ``olmo_hybrid`` publishes it (Olmo-Hybrid-7B).

Plain functions over a plain parameter tree, like ``models/granite_hybrid.py``
(whose convolution, grouped attention and ``last_idx`` rule these layers
use, with ``models/mla_moe.py``'s ``rms_norm`` and untied head).
``x0 = E[token]``; layer ``l`` puts its norms AFTER the mixer and the MLP
(the family's order, as Olmo 2 and 3)::

    h  = x + RMSNorm(mixer_l(x))
    x' = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

and the logits are ``RMSNorm(x) W_head`` (untied). ``cfg.dtype`` (bfloat16) is
the type of every matrix product's operands; between products the
activations and the residual stream stay float32 (:func:`_mm`: the product's
own accumulator is kept, not rounded). At a serving batch that costs nothing
(``x`` is 1.5 MB at 96 lanes beside 7 GB of weights a step), and the block's
residual grows with depth, so a bfloat16 ``x`` would round most where it is
largest.

**Full attention** (``layer_types[l] == "full_attention"``): ``q =
RMSNorm(W_q x)`` and ``k = RMSNorm(W_k x)``, each norm over the whole
projected width before the split into heads, ``v = W_v x``; ``n_head`` query
heads and ``n_kv_head`` key/value heads of ``d_head``, **no rotary**
(``rope_theta`` null: a configuration that states a base is refused, not
guessed), scores over ``sqrt(d_head)``, causal softmax. What a cache holds
of a token is its normed ``k`` and its ``v``, rows of ``n_kv_head * d_head``.

**Gated delta rule** (``"linear_attention"``; ``H`` heads, keys of ``d_k``,
values of ``d_v``, depthwise causal convolution of width ``d_conv``, no
bias)::

    [q | k | v | z] = W_in x            (H d_k, H d_k, H d_v, H d_v)
    [b | a] = W_ba x                                          (H, H)
    [q | k | v]_t = silu(sum_j w_j * [q | k | v]_{t-(d_conv-1)+j})
    q = q / |q| / sqrt(d_k),  k = k / |k|          a head; L2, eps 1e-6
    beta = 2 sigmoid(b)      (``allow_neg_eigval``; without it sigmoid(b))
    alpha = exp(-exp(A_log) softplus(a + dt_bias))            one a head
    S~ = alpha S_{t-1},  u = beta (v - S~^T k),  S_t = S~ + k u^T
                                                   a head: S (d_k, d_v)
    o = S_t^T q
    out = W_out (RMSNorm(o) * silu(z))   the norm a head, one weight (d_v,)

What a lane keeps of such a layer is no page: the last ``d_conv - 1`` inputs
of the convolution (``conv``) and ``S`` (``gdn``), both float32 and rewritten
whole every token. ``S`` is laid ``(d_k, H * d_v)``: the key dimension down
the sublanes, the heads' values side by side along the lanes (``ops/gdn.py``
says why), and the one-step update is ``ops.dispatch.gdn_update``.

Prefill computes the same recurrence in chunks of ``chunk`` positions
(:func:`gdn_chunk_scan`, the WY / UT form: within a chunk a unit
lower-triangular system a head, between chunks the carried state); decode
takes one step (:func:`gdn_step`). A position past ``last_idx`` of a
right-padded prompt takes ``beta = 0`` and ``log alpha = 0``, which makes
its step the identity, so the state a prefill returns is the state at
``last_idx`` whatever the padded length.

Parameter tree (weights ``cfg.dtype``; norms, the convolution and the
per-head ``A_log``, ``dt_bias`` float32)::

    embed (V, D)   head (D, V)   norm_f (D,)
    layer_<i>/mixer_norm, mlp_norm (D,)       applied after the mixer / MLP
    layer_<i>/mlp/{gate (D, F), up (D, F), down (F, D)}
    layer_<i>/attn/{q (D, H*dh), k, v (D, Hk*dh), o (H*dh, D),
                    q_norm (H*dh,), k_norm (Hk*dh,)}
    layer_<i>/gdn/{in_proj (D, 2*H*dk + 2*H*dv), ba_proj (D, 2*H),
                   conv_w (d_conv, 2*H*dk + H*dv), A_log, dt_bias (H,),
                   norm (dv,), out_proj (H*dv, D)}

What the published ``config.json`` does not settle, and is the family's
convention here: the block's norm order, the q/k norm over the whole width,
no rotary, and the chunk of 64. Key heads other than value heads, biases, a
tied head and an activation other than SiLU are refused.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch as ops_dispatch
from . import granite_hybrid as gh
from .mla_moe import logits, rms_norm  # noqa: F401 (logits: the untied head)

HI = jax.lax.Precision.HIGHEST
KINDS = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]  # of KINDS, one a layer
    n_head: int
    n_kv_head: int
    d_ff: int
    g_heads: int  # gated delta-rule heads (keys and values alike)
    d_k: int
    d_v: int
    d_conv: int = 4
    allow_neg_eigval: bool = True
    chunk: int = 64
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "OlmoHybridConfig":
        """From the keys of a published ``config.json``."""
        theta = c.get("rope_theta", (c.get("rope_parameters") or {}).get(
            "rope_theta"))
        stated = dict(c, rope_theta=theta)
        unsupported = {
            "rope_theta": None, "attention_bias": False,
            "tie_word_embeddings": False, "hidden_act": "silu",
            "linear_num_key_heads": c["linear_num_value_heads"],
        }
        for key, only in unsupported.items():
            if stated.get(key, only) != only:
                raise ValueError(
                    f"OlmoHybridConfig: {key}={stated[key]!r} is not served "
                    f"(only {only!r}: the layer equations in models/"
                    "olmo_hybrid.py)"
                )
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            layer_types=tuple(c["layer_types"]),
            n_head=c["num_attention_heads"],
            n_kv_head=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], g_heads=c["linear_num_value_heads"],
            d_k=c["linear_key_head_dim"], d_v=c["linear_value_head_dim"],
            d_conv=c["linear_conv_kernel_dim"],
            allow_neg_eigval=c["linear_allow_neg_eigval"],
            eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        odd = set(self.layer_types) - set(KINDS)
        if odd:
            raise ValueError(f"layer_types holds {sorted(odd)}")
        if self.n_head % self.n_kv_head or self.d_model % self.n_head:
            raise ValueError(
                f"{self.n_head} query heads over {self.n_kv_head} K/V heads "
                f"at width {self.d_model}"
            )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_head

    @property
    def attention_multiplier(self) -> float:
        """What the scores are multiplied by (``gh.attend_grouped``)."""
        return 1.0 / np.sqrt(self.d_head)

    @property
    def d_qkv(self) -> int:
        """Channels of the convolution: ``q``, ``k`` and ``v``."""
        return self.g_heads * (2 * self.d_k + self.d_v)

    @property
    def d_value(self) -> int:
        return self.g_heads * self.d_v

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "full_attention")

    @property
    def n_cache_layers(self) -> int:
        """Layers that leave pages behind (the serve plan's frames)."""
        return len(self.attention_layers)

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over the full-attention
        layers (a delta-rule layer's cache does not grow with the tokens)."""
        return 2 * self.n_cache_layers * self.n_kv_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """float32 bytes of the recurrent state a lane holds over the
        delta-rule layers, whatever its length."""
        per_layer = (self.d_conv - 1) * self.d_qkv + self.d_k * self.d_value
        return (self.n_layer - self.n_cache_layers) * per_layer * 4


def _mm(x, w, dtype):
    """``x @ w`` with ``dtype`` operands and the float32 accumulator out
    (``mla_moe._mm`` rounds its result to ``dtype``)."""
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The gated delta rule.
# ---------------------------------------------------------------------------


def gdn_project(cfg: OlmoHybridConfig, y, pg):
    """``y (..., D)`` -> the output gate ``z (..., H dv)``, the
    convolution's input ``qkv (..., H (2 dk + dv))`` and the raw ``b``, ``a
    (..., H)``, float32 (what the float32 ``conv`` state holds is not
    rounded first)."""
    proj = _mm(y, pg["in_proj"], cfg.dtype)
    ba = _mm(y, pg["ba_proj"], cfg.dtype)
    c, h = cfg.d_qkv, cfg.g_heads
    return proj[..., c:], proj[..., :c], ba[..., :h], ba[..., h:]


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _step_operands(cfg: OlmoHybridConfig, pg, qkv, b, a, live=None):
    """What the recurrence takes of a token: ``qkv (..., H (2 dk + dv))``
    after the convolution and the raw ``b``, ``a (..., H)`` -> ``q``, ``k
    (..., H, dk)`` (normalised, ``q`` over ``sqrt(dk)`` too), ``v (..., H,
    dv)``, ``log alpha`` and ``beta (..., H)`` (both 0 where ``live`` is
    False), float32."""
    h, dk, dv = cfg.g_heads, cfg.d_k, cfg.d_v
    lead = qkv.shape[:-1]
    q = _l2norm(qkv[..., : h * dk].reshape(*lead, h, dk)) * np.float32(
        dk ** -0.5)
    k = _l2norm(qkv[..., h * dk: 2 * h * dk].reshape(*lead, h, dk))
    v = qkv[..., 2 * h * dk:].reshape(*lead, h, dv)
    beta = jax.nn.sigmoid(b)
    if cfg.allow_neg_eigval:
        beta = 2.0 * beta
    log_alpha = -jnp.exp(pg["A_log"]) * jax.nn.softplus(a + pg["dt_bias"])
    if live is not None:
        beta, log_alpha = (jnp.where(live, t, 0.0) for t in (beta, log_alpha))
    return q, k, v, log_alpha, beta


def _gated_out(cfg: OlmoHybridConfig, pg, o, z):
    """``W_out (RMSNorm(o) * silu(z))``: ``o (..., H, dv)`` float32, the norm
    a head."""
    g = rms_norm(o, pg["norm"], cfg.eps) * jax.nn.silu(z.reshape(o.shape))
    return _mm(g.reshape(*o.shape[:-2], cfg.d_value), pg["out_proj"],
               cfg.dtype)


def unit_lower_inverse(a):
    """``(I + A)^-1`` of a strictly lower-triangular ``A (..., n, n)``, by
    doubling: the inverse of the diagonal blocks of size ``s`` (the identity
    at ``s = 1``) gives that of the blocks of ``2 s``, ``[[T1, 0], [-T2 A21
    T1, T2]]``, which over the whole matrix is ``T - T (A * mask_s) T`` with
    ``mask_s`` the lower-left quarters of the ``2 s`` blocks: two matrix
    products a level, ``log2 n`` levels, no loop over the rows (a forward
    substitution is ``n`` dependent steps, which the chip runs badly), and
    the arithmetic of a blocked substitution, not of the alternating series
    ``(I - A)(I + A^2)(I + A^4)...``, whose terms cancel."""
    n = a.shape[-1]
    i, j = np.indices((n, n))
    eye = jnp.eye(n, dtype=a.dtype)
    t, s = eye, 1
    while s < n:
        mask = (i // (2 * s) == j // (2 * s)) & (i % (2 * s) >= s) & (
            j % (2 * s) < s)
        m = a * mask
        t = t - (m if s == 1 else jnp.matmul(
            jnp.matmul(t, m, precision=HI), t, precision=HI))
        s *= 2
    return t


def carry_chunks(state0, w, u, qg, qk, k_end, whole):
    """The chunks one after another, the state carried: with ``S`` entering
    a chunk, ``V' = U - W S``, ``O = Qg S + QK V'``, ``S' = whole S + K_end^T
    V'``. ``w``, ``qg``, ``k_end (B, nc, H, L, dk)``, ``u (B, nc, H, L,
    dv)``, ``qk (B, nc, H, L, L)``; ``whole`` the decay over a whole chunk,
    ``(B, nc, H)`` a head or ``(B, nc, H, dk)`` a key channel; ``state0 (B,
    H, dk, dv)``. Returns the last state and ``o (nc, B, H, L, dv)``."""
    a_head = whole.ndim == 3

    def carry(state, c):
        w_c, u_c, qg_c, qk_c, k_end_c, whole_c = c
        vp = u_c - jnp.matmul(w_c, state, precision=HI)
        o_c = (jnp.matmul(qg_c, state, precision=HI)
               + jnp.matmul(qk_c, vp, precision=HI))
        rows = whole_c[..., None, None] if a_head else whole_c[..., None]
        new = rows * state + jnp.einsum(
            "bhld,bhlv->bhdv", k_end_c, vp, precision=HI)
        return new, o_c

    return jax.lax.scan(
        carry, state0,
        tuple(t.swapaxes(0, 1) for t in (w, u, qg, qk, k_end, whole)),
    )


def gdn_chunk_scan(q, k, v, log_alpha, beta, chunk: int, state0=None):
    """The recurrence ``S_t = alpha_t S_{t-1} + k_t u_t^T`` with ``u_t =
    beta_t (v_t - alpha_t S_{t-1}^T k_t)``, ``o_t = S_t^T q_t`` over ``S``
    positions in chunks (the WY / UT form).

    ``q``, ``k (B, S, H, dk)``, ``v (B, S, H, dv)``, ``log_alpha (B, S, H)``
    (<= 0), ``beta (B, S, H)`` (``beta = 0`` with ``log_alpha = 0`` makes a
    position the identity), ``state0 (B, H, dk, dv)`` or None for zeros;
    float32. Returns ``o (B, S, H, dv)`` and the state after position ``S -
    1``, ``(B, H, dk, dv)``. Within a chunk, with ``G`` the running sum of
    ``log alpha`` and ``Gamma_ij = exp(G_i - G_j)``: solve ``(I +
    strict_tril(diag(beta) (K K^T * Gamma))) [W, U] = diag(beta) [K *
    exp(G), V]`` (:func:`unit_lower_inverse`), then with the state ``S``
    entering the chunk ``V' = U - W S``, ``O = (Q * exp(G)) S + (Q K^T *
    Gamma * tril) V'`` and ``S' = exp(G_end) S + (K * exp(G_end - G))^T
    V'``. ``S`` is padded to whole chunks with identity positions. The
    products run at full float32 precision, as ``ssd_scan``'s do: the state
    they leave is read by every later token of the request."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_alpha, beta)
        )
    nc = (s + pad) // chunk

    def chunks(t):  # (B, S, H, ...) -> (B, nc, H, L, ...)
        t = t.reshape(b, nc, chunk, *t.shape[2:])
        return jnp.moveaxis(t, 3, 2)

    with jax.named_scope("cgx_gdn_chunk_scan"):
        q, k, v, g, beta = (chunks(t) for t in (q, k, v, log_alpha, beta))
        g = jnp.cumsum(g, axis=-1)  # (b, nc, h, L): log decay up to i
        lower = np.tril(np.ones((chunk, chunk), bool))
        gamma = jnp.exp(jnp.where(
            lower, g[..., :, None] - g[..., None, :], -jnp.inf))
        kk = jnp.einsum("bchid,bchjd->bchij", k, k, precision=HI)
        inv = unit_lower_inverse(
            beta[..., None] * kk * gamma * np.tril(lower, -1))
        eg = jnp.exp(g)[..., None]
        w = jnp.matmul(inv, beta[..., None] * k * eg, precision=HI)
        u = jnp.matmul(inv, beta[..., None] * v, precision=HI)
        qk = jnp.einsum("bchid,bchjd->bchij", q, k, precision=HI) * gamma
        k_end = k * jnp.exp(g[..., -1:] - g)[..., None]
        whole = jnp.exp(g[..., -1])  # (b, nc, h): decay over a chunk

        if state0 is None:
            state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        final, o = carry_chunks(state0, w, u, q * eg, qk, k_end, whole)
    # (nc, b, h, L, dv) -> (b, S, h, dv)
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, nc * chunk, h, dv)
    return o[:, :s], final


def gdn_prefill(cfg: OlmoHybridConfig, pg, y, last_idx):
    """The mixer over a whole (right-padded) prompt ``y (B, S, D)``: ``(out
    (B, S, D), conv (B, d_conv - 1, H (2 dk + dv)), gdn (B, dk, H dv))``, the
    state a lane holds after position ``last_idx``: positions past it are
    identity steps and the convolution's state is its ``d_conv - 1`` inputs
    ending at ``last_idx`` (zeros before the prompt's start)."""
    b, s, _ = y.shape
    z, qkv_in, b_raw, a_raw = gdn_project(cfg, y, pg)
    conv, padded = gh.conv_prefill(pg["conv_w"], None, qkv_in)
    live = (jnp.arange(s) <= last_idx)[None, :, None]
    q, k, v, log_alpha, beta = _step_operands(
        cfg, pg, jax.nn.silu(conv), b_raw, a_raw, live)
    o, state = gdn_chunk_scan(q, k, v, log_alpha, beta, cfg.chunk)
    # (B, H, dk, dv) -> the lanes' layout (B, dk, H*dv).
    state = state.transpose(0, 2, 1, 3).reshape(b, cfg.d_k, cfg.d_value)
    return (_gated_out(cfg, pg, o, z),
            gh.conv_state_at(padded, last_idx, cfg.d_conv), state)


def gdn_step(cfg: OlmoHybridConfig, pg, y, conv_state, state):
    """One token a lane: ``y (B, D)``, ``conv_state (B, d_conv - 1, H (2 dk
    + dv))``, ``state (B, dk, H dv)`` -> ``(out (B, D), the new conv state,
    the new state)``. The state update is ``ops.dispatch.gdn_update`` (one
    kernel over all lanes on the chip)."""
    z, qkv_in, b_raw, a_raw = gdn_project(cfg, y, pg)
    conv, window = gh.conv_step(pg["conv_w"], None, conv_state, qkv_in)
    q, k, v, log_alpha, beta = _step_operands(
        cfg, pg, jax.nn.silu(conv), b_raw, a_raw)
    new_state, o = ops_dispatch.gdn_update(
        state, q, k, v, jnp.exp(log_alpha), beta)
    o = o.reshape(-1, cfg.g_heads, cfg.d_v)
    return (_gated_out(cfg, pg, o, z),
            window[:, 1:].astype(conv_state.dtype), new_state)


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------


def attn_project(cfg: OlmoHybridConfig, y, pa):
    """``y (B, S, D)`` -> ``q (B, S, H, dh)`` in ``cfg.dtype`` (a dot's
    operand) and ``k``, ``v (B, S, Hk, dh)`` float32 (what the cache is
    given): ``q`` and ``k`` normed over their whole width before the split
    into heads; nothing is rotated."""
    b, s, _ = y.shape
    dt = cfg.dtype
    q = rms_norm(_mm(y, pa["q"], dt), pa["q_norm"], cfg.eps).astype(dt)
    k = rms_norm(_mm(y, pa["k"], dt), pa["k_norm"], cfg.eps)
    v = _mm(y, pa["v"], dt)
    return (q.reshape(b, s, cfg.n_head, cfg.d_head),
            k.reshape(b, s, cfg.n_kv_head, cfg.d_head),
            v.reshape(b, s, cfg.n_kv_head, cfg.d_head))


def attn_out(cfg: OlmoHybridConfig, o, pa):
    """The output projection of ``o (..., H * dh)``: float32."""
    return _mm(o, pa["o"], cfg.dtype)


def attend(cfg: OlmoHybridConfig, q, k, v, pa):
    """Causal attention of a whole prompt and its output projection: ``(B,
    S, D)`` float32."""
    return attn_out(cfg, gh.attend_grouped(
        cfg, q, k.astype(cfg.dtype), v.astype(cfg.dtype)), pa)


# ---------------------------------------------------------------------------
# The model.
# ---------------------------------------------------------------------------


def embed(cfg: OlmoHybridConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def post_norm_residual(cfg: OlmoHybridConfig, x, out, w):
    """``x + RMSNorm(out)``: the family's block, the norm after."""
    return x + rms_norm(out, w, cfg.eps)


def mlp_half(cfg: OlmoHybridConfig, pl, h):
    """The layer's second half: ``h + RMSNorm(SwiGLU(h))``."""
    pm, dt = pl["mlp"], cfg.dtype
    out = _mm(jax.nn.silu(_mm(h, pm["gate"], dt)) * _mm(h, pm["up"], dt),
              pm["down"], dt)
    return post_norm_residual(cfg, h, out, pl["mlp_norm"])


def forward(cfg: OlmoHybridConfig, params, tokens):
    """Logits ``(B, S, V)`` of whole sequences, no cache: the chunked delta
    rule and the grouped attention as prefill runs them."""
    x = embed(cfg, params, tokens)
    last = tokens.shape[1] - 1
    for layer, kind in enumerate(cfg.layer_types):
        pl = params[f"layer_{layer}"]
        if kind == "linear_attention":
            out, _, _ = gdn_prefill(cfg, pl["gdn"], x, last)
        else:
            out = attend(cfg, *attn_project(cfg, x, pl["attn"]), pl["attn"])
        x = mlp_half(cfg, pl, post_norm_residual(cfg, x, out,
                                                 pl["mixer_norm"]))
    return logits(cfg, params, x)
