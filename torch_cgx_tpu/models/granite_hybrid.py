"""Hybrid state-space / attention decoder: Mamba-2 layers with a few
grouped-query attention layers among them and one SwiGLU MLP in every
layer, as ``granitemoehybrid`` publishes it (granite-4.0-h-micro:
``num_local_experts`` 0, so the shared MLP is the only feed-forward).

Plain functions over a plain parameter tree, like ``models/mla_moe.py``
(whose ``rms_norm`` and ``swiglu`` these layers use): the serving plane
needs each layer's cache streams and recurrent state in and out.
``x0 = E[token] * embedding_multiplier``; layer ``l``::

    x = x + residual_multiplier * mixer_l(RMSNorm(x))
    x = x + residual_multiplier * W_down(silu(W_gate y) * W_up y)
                                                      with y = RMSNorm(x)

and the logits are ``RMSNorm(x) E^T / logits_scaling`` (tied embedding).

**Attention** (``layer_types[l] == "attention"``): ``n_head`` query heads
and ``n_kv_head`` key/value heads of ``d_head``, no bias, no positional
embedding (``position_embedding_type`` "nope": nothing is rotated), scores
times ``attention_multiplier`` (not ``1/sqrt(d_head)``), causal softmax;
each K/V head serves ``n_head / n_kv_head`` query heads. What a cache holds
of a token is its ``k`` and ``v`` rows of ``n_kv_head * d_head``.

**Mamba-2** (``d_inner = m_heads * m_head`` channels, one group of
``d_state``, depthwise causal convolution of width ``d_conv``)::

    [z | xBC | dt] = W_in y           (d_inner, d_inner + 2 d_state, m_heads)
    xBC_t = silu(sum_j w_j * xBC_{t-(d_conv-1)+j} + b)
    [x | B | C] = xBC_t                          (d_inner, d_state, d_state)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)          one a head
    h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t
                                               a head: (m_head, d_state)
    y_t = h_t C_t + D * x_t
    out = W_out RMSNorm(y_t * silu(z_t))       over d_inner, with a weight

What a lane keeps of a Mamba layer is no page: the last ``d_conv - 1``
inputs of the convolution (``conv``) and ``h`` (``ssm``), both float32 and
rewritten whole every token. ``h`` is laid ``(d_state, d_inner)``: the
state dimension down the sublanes, the heads' channels side by side along
the lanes, so that a token's ``x`` and ``dt`` are rows, its ``B`` and ``C``
columns, ``y`` is a row, and the one-step update
(``ops.dispatch.ssm_update``) is full-width vector work with nothing relaid.

Prefill computes the same recurrence in chunks of ``chunk`` positions
(:func:`ssd_scan`, the SSD form: within a chunk by matrix products, between
chunks by the carried state); decode takes one step (:func:`mamba_step`).
A position past ``last_idx`` of a right-padded prompt takes ``dt = 0``,
which makes its update the identity, so the state a prefill returns is the
state at ``last_idx`` whatever the padded length.

Parameter tree (weights ``cfg.dtype``; norms, the convolution and the
per-head ``A_log``, ``D``, ``dt_bias`` float32)::

    embed (V, D)   norm_f (D,)
    layer_<i>/mixer_norm, mlp_norm (D,)
    layer_<i>/mlp/{gate (D, F), up (D, F), down (F, D)}
    layer_<i>/attn/{q (D, H*dh), k (D, Hk*dh), v (D, Hk*dh), o (H*dh, D)}
    layer_<i>/mamba/{in_proj (D, 2*di + 2*N + Hm), conv_w (d_conv, di + 2*N),
                     conv_b (di + 2*N,), dt_bias, A_log, D (Hm,), norm (di,),
                     out_proj (di, D)}

Departures from the published module, none of them in the mathematics: the
MLP's ``input_linear`` is held as its two halves ``gate`` and ``up`` (the
shared ``swiglu``); ``n_groups`` other than 1, projection or attention
biases and a mixture of experts are refused, not guessed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dispatch as ops_dispatch
from .mla_moe import _mm, rms_norm, swiglu

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]  # "mamba" | "attention", one a layer
    n_head: int
    n_kv_head: int
    d_head: int
    d_ff: int
    m_heads: int
    m_head: int
    d_state: int
    d_conv: int = 4
    chunk: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    logits_scaling: float = 1.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, **kw) -> "HybridConfig":
        """From the keys of a published ``config.json``."""
        unsupported = {
            "mamba_n_groups": 1, "mamba_proj_bias": False,
            "attention_bias": False, "num_local_experts": 0,
            "position_embedding_type": "nope", "tie_word_embeddings": True,
        }
        for key, only in unsupported.items():
            if c.get(key, only) != only:
                raise ValueError(
                    f"HybridConfig: {key}={c[key]!r} is not served (only "
                    f"{only!r}: the layer equations in models/"
                    "granite_hybrid.py)"
                )
        return cls(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            layer_types=tuple(c["layer_types"]),
            n_head=c["num_attention_heads"],
            n_kv_head=c["num_key_value_heads"],
            d_head=c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["shared_intermediate_size"], m_heads=c["mamba_n_heads"],
            m_head=c["mamba_d_head"], d_state=c["mamba_d_state"],
            d_conv=c["mamba_d_conv"], chunk=c["mamba_chunk_size"],
            embedding_multiplier=c["embedding_multiplier"],
            residual_multiplier=c["residual_multiplier"],
            attention_multiplier=c["attention_multiplier"],
            logits_scaling=c["logits_scaling"], eps=c["rms_norm_eps"], **kw,
        )

    def __post_init__(self):
        odd = set(self.layer_types) - {"mamba", "attention"}
        if odd:
            raise ValueError(f"layer_types holds {sorted(odd)}")
        if self.n_head % self.n_kv_head:
            raise ValueError(
                f"{self.n_head} query heads over {self.n_kv_head} K/V heads"
            )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head

    @property
    def d_xbc(self) -> int:
        """Channels of the convolution: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.d_state

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    @property
    def n_cache_layers(self) -> int:
        """Layers that leave pages behind (the serve plan's frames)."""
        return len(self.attention_layers)

    def kv_bytes_per_token(self) -> int:
        """float32 bytes of one token's K and V over the attention layers
        (a Mamba layer's cache does not grow with the tokens)."""
        return 2 * self.n_cache_layers * self.n_kv_head * self.d_head * 4

    def state_bytes_per_lane(self) -> int:
        """float32 bytes of the recurrent state a lane holds over the Mamba
        layers, whatever its length."""
        per_layer = ((self.d_conv - 1) * self.d_xbc
                     + self.d_state * self.d_inner)
        return (self.n_layer - self.n_cache_layers) * per_layer * 4


# ---------------------------------------------------------------------------
# Mamba-2.
# ---------------------------------------------------------------------------


def mamba_project(cfg: HybridConfig, y, pm):
    """``y (..., D)`` (already normed) -> gate ``z (..., di)`` in
    ``cfg.dtype``, the convolution's input ``xBC (..., di + 2N)`` and the
    raw ``dt (..., Hm)``, both float32 (the product's own accumulator:
    what the float32 ``conv`` state holds is not rounded first)."""
    proj = jnp.matmul(y.astype(cfg.dtype), pm["in_proj"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
    di, dx = cfg.d_inner, cfg.d_xbc
    return (proj[..., :di].astype(cfg.dtype), proj[..., di: di + dx],
            proj[..., di + dx:])


def _head_rows(cfg: HybridConfig, per_head):
    """A per-head value ``(..., Hm)`` as a row over the heads' channels
    ``(..., di)``."""
    return jnp.repeat(per_head, cfg.m_head, axis=-1)


def _step_operands(cfg: HybridConfig, pm, xbc, dt_raw, live=None):
    """What the recurrence takes of a token: ``xbc (..., di + 2N)`` after
    the convolution and ``dt_raw (..., Hm)`` -> ``x (..., di)``, ``dt (...,
    Hm)`` (0 where ``live`` is False), ``B``, ``C (..., N)``, float32."""
    di, n = cfg.d_inner, cfg.d_state
    x, bm, cm = xbc[..., :di], xbc[..., di: di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt_raw + pm["dt_bias"])
    if live is not None:
        dt = jnp.where(live, dt, 0.0)
    return x, dt, bm, cm


def _gated_out(cfg: HybridConfig, pm, y, z):
    """``W_out RMSNorm(y * silu(z))``: ``y (..., di)`` float32."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    return _mm(rms_norm(g, pm["norm"], cfg.eps), pm["out_proj"], cfg.dtype)


def conv_prefill(w, b, x):
    """The depthwise causal convolution over a whole prompt, before its
    activation: ``w (d_conv, C)``, ``b (C,)`` or None, ``x (B, S, C)``
    float32 -> ``(conv (B, S, C), x with d_conv - 1 zero positions in
    front)``; :func:`conv_state_at` cuts a lane's state from the latter."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(w[j] * padded[:, j: j + s] for j in range(k))
    return (conv if b is None else conv + b), padded


def conv_state_at(padded, last_idx, k: int):
    """The convolution's state after position ``last_idx``: its ``k - 1``
    inputs ending there (zeros before the prompt's start)."""
    return jax.lax.dynamic_slice_in_dim(padded, last_idx + 1, k - 1, 1)


def conv_step(w, b, conv_state, x):
    """One token of the same convolution: ``conv_state (B, d_conv - 1,
    C)``, ``x (B, C)`` float32 -> ``(conv (B, C)``, the window ``(B, d_conv,
    C))``, whose last ``d_conv - 1`` rows are the next state."""
    window = jnp.concatenate(
        [conv_state.astype(jnp.float32), x[:, None]], axis=1
    )
    conv = jnp.sum(w * window, axis=1)
    return (conv if b is None else conv + b), window


def ssd_scan(x, dt, a, bm, cm, chunk: int, state0=None):
    """The recurrence ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (outer) B_t``,
    ``y_t = h_t C_t`` over ``S`` positions in chunks (the SSD form).

    ``x (B, S, H, P)``, ``dt (B, S, H)`` (>= 0; 0 makes a position the
    identity), ``a (H,)`` (< 0), ``bm``, ``cm (B, S, N)``, ``state0 (B, H,
    P, N)`` or None for zeros; float32. Returns ``y (B, S, H, P)`` and the
    state after position ``S - 1``, ``(B, H, P, N)``. Within a chunk
    position ``i`` takes position ``j <= i`` with the weight ``C_i . B_j *
    exp(sum_{j<k<=i} dt_k a)``: two matrix products; a chunk hands the
    next its state decayed over the whole chunk. ``S`` is padded to whole
    chunks with ``dt = 0``. The products run at full float32 precision:
    they are a thirtieth of a prefill's work, and the state they leave is
    read by every later token of the request."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    pad = -s % chunk
    if pad:
        x, dt, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, bm, cm)
        )
    nc = (s + pad) // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    bc = bm.reshape(b, nc, chunk, n)
    cc = cm.reshape(b, nc, chunk, n)
    cs = jnp.cumsum(dtc * a, axis=2)  # (b, nc, L, h): log decay up to i
    xdt = xc * dtc[..., None]
    # Within the chunk.
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (b, nc, Li, Lj, h)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", cc, bc, precision=HI)
    y = jnp.einsum("bcijh,bcjhp->bcihp", cb[..., None] * decay, xdt,
                   precision=HI)
    # What each chunk adds to the state, decayed to the chunk's end.
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (b, nc, L, h)
    adds = jnp.einsum("bcjn,bcjhp->bchpn", bc, xdt * to_end[..., None],
                      precision=HI)
    whole = jnp.exp(cs[:, :, -1, :])  # (b, nc, h): decay over a chunk

    def carry(state, chunk_in):
        add, dec = chunk_in
        return dec[..., None, None] * state + add, state

    if state0 is None:
        state0 = jnp.zeros((b, h, p, n), jnp.float32)
    final, before = jax.lax.scan(
        carry, state0, (adds.swapaxes(0, 1), whole.swapaxes(0, 1))
    )
    before = before.swapaxes(0, 1)  # (b, nc, h, p, n): state entering a chunk
    y = y + jnp.einsum("bcin,bchpn->bcihp", cc, before, precision=HI
                       ) * jnp.exp(cs)[..., None]
    return y.reshape(b, nc * chunk, h, p)[:, :s], final


def mamba_prefill(cfg: HybridConfig, pm, y, last_idx):
    """The mixer over a whole (right-padded) prompt ``y (B, S, D)``
    (already normed): ``(out (B, S, D), conv (B, d_conv - 1, di + 2N), ssm
    (B, N, di))``, the state a lane holds after position ``last_idx``:
    positions past it take ``dt = 0`` and the convolution's state is its
    ``d_conv - 1`` inputs ending at ``last_idx`` (zeros before the
    prompt's start)."""
    b, s, _ = y.shape
    k = cfg.d_conv
    z, xbc_in, dt_raw = mamba_project(cfg, y, pm)
    conv, padded = conv_prefill(pm["conv_w"], pm["conv_b"], xbc_in)
    live = (jnp.arange(s) <= last_idx)[None, :, None]
    x, dt, bm, cm = _step_operands(cfg, pm, jax.nn.silu(conv), dt_raw, live)
    xh = x.reshape(b, s, cfg.m_heads, cfg.m_head)
    yh, state = ssd_scan(xh, dt, -jnp.exp(pm["A_log"]), bm, cm, cfg.chunk)
    yh = yh + pm["D"][:, None] * xh
    conv_state = conv_state_at(padded, last_idx, k)
    # (B, H, P, N) -> the lanes' layout (B, N, H*P).
    ssm_state = state.transpose(0, 3, 1, 2).reshape(b, cfg.d_state,
                                                    cfg.d_inner)
    return (_gated_out(cfg, pm, yh.reshape(b, s, cfg.d_inner), z),
            conv_state, ssm_state)


def mamba_step(cfg: HybridConfig, pm, y, conv_state, ssm_state):
    """One token a lane: ``y (B, D)`` (already normed), ``conv_state (B,
    d_conv - 1, di + 2N)``, ``ssm_state (B, N, di)`` -> ``(out (B, D), the
    new conv state, the new ssm state)``. The state update is
    ``ops.dispatch.ssm_update`` (one kernel over all lanes on the chip)."""
    z, xbc_in, dt_raw = mamba_project(cfg, y, pm)
    conv, window = conv_step(pm["conv_w"], pm["conv_b"], conv_state, xbc_in)
    x, dt, bm, cm = _step_operands(cfg, pm, jax.nn.silu(conv), dt_raw)
    decay = _head_rows(cfg, jnp.exp(dt * -jnp.exp(pm["A_log"])))
    new_ssm, yv = ops_dispatch.ssm_update(
        ssm_state, decay, _head_rows(cfg, dt) * x, bm, cm
    )
    yv = yv + _head_rows(cfg, pm["D"]) * x
    return (_gated_out(cfg, pm, yv, z),
            window[:, 1:].astype(conv_state.dtype), new_ssm)


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------


def attn_project(cfg: HybridConfig, y, pa):
    """``y (B, S, D)`` (already normed) -> ``q (B, S, H, dh)``, ``k``, ``v
    (B, S, Hk, dh)`` in ``cfg.dtype``; nothing is rotated."""
    b, s, _ = y.shape
    dt = cfg.dtype
    q = _mm(y, pa["q"], dt).reshape(b, s, cfg.n_head, cfg.d_head)
    k = _mm(y, pa["k"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    v = _mm(y, pa["v"], dt).reshape(b, s, cfg.n_kv_head, cfg.d_head)
    return q, k, v


def attend_grouped(cfg: HybridConfig, q, k, v):
    """Causal attention of a whole prompt, each K/V head read by its group
    of query heads: ``(B, S, H*dh)``."""
    b, s, h, d = q.shape
    g = h // cfg.n_kv_head
    qg = q.reshape(b, s, cfg.n_kv_head, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32
                        ) * np.float32(cfg.attention_multiplier)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, np.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return o.reshape(b, s, h * d)


# ---------------------------------------------------------------------------
# The model.
# ---------------------------------------------------------------------------


def embed(cfg: HybridConfig, params, tokens):
    x = params["embed"][tokens].astype(jnp.float32)
    return (x * np.float32(cfg.embedding_multiplier)).astype(cfg.dtype)


def residual(cfg: HybridConfig, x, out):
    return x + (out.astype(jnp.float32)
                * np.float32(cfg.residual_multiplier)).astype(cfg.dtype)


def mlp_half(cfg: HybridConfig, pl, x):
    """The layer's second half: ``x + r * SwiGLU(RMSNorm(x))``."""
    y = rms_norm(x, pl["mlp_norm"], cfg.eps)
    return residual(cfg, x, swiglu(y, pl["mlp"], cfg.dtype))


def logits(cfg: HybridConfig, params, x):
    """Final norm, the tied head, ``/ logits_scaling``; float32."""
    y = rms_norm(x, params["norm_f"], cfg.eps)
    out = jnp.matmul(
        y.astype(cfg.dtype), params["embed"].astype(cfg.dtype).T,
        preferred_element_type=jnp.float32,
    )
    return out / np.float32(cfg.logits_scaling)


def forward(cfg: HybridConfig, params, tokens):
    """Logits ``(B, S, V)`` of whole sequences, no cache: the chunked scan
    and the grouped attention as prefill runs them."""
    x = embed(cfg, params, tokens)
    last = tokens.shape[1] - 1
    for layer, kind in enumerate(cfg.layer_types):
        pl = params[f"layer_{layer}"]
        y = rms_norm(x, pl["mixer_norm"], cfg.eps)
        if kind == "mamba":
            out, _, _ = mamba_prefill(cfg, pl["mamba"], y, last)
        else:
            q, k, v = attn_project(cfg, y, pl["attn"])
            out = _mm(attend_grouped(cfg, q, k, v), pl["attn"]["o"],
                      cfg.dtype)
        x = mlp_half(cfg, pl, residual(cfg, x, out))
    return logits(cfg, params, x)
