"""Plain float32 hybrid state-space / attention decoder: the reference the
``granite-4.0-h-micro-serve-kv8`` configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys
(``model_type`` ``granitemoehybrid``), float32 throughout, every matrix
product at ``Precision.HIGHEST``. No cache, no kernels, and **no chunks**:
the state-space recurrence is a ``lax.scan`` over the positions, one token
at a time, so that it is independent of the program's chunked (SSD) form and
of its one-step kernel alike. Nothing is imported from the program under
test. It is given the seeded weights the benchmark made (bfloat16) and
upcasts them a layer at a time, inside that layer's jitted function, so
that 12.8 GB of float32 never stand beside the 6.4 GB tree.

``x0 = E[token] * embedding_multiplier``. Layer ``l``: ``x = x +
residual_multiplier * mixer_l(RMSNorm(x))``, then ``x = x +
residual_multiplier * W_down(silu(W_gate y) * W_up y)`` with ``y =
RMSNorm(x)`` (``rms_norm_eps``). Logits: ``RMSNorm(x) E^T / logits_scaling``
(tied embedding). *Attention* (``layer_types[l] == "attention"``):
``num_attention_heads`` query heads and ``num_key_value_heads`` key/value
heads of ``hidden_size / num_attention_heads``, no bias, no rotary
(``position_embedding_type`` "nope"), scores times ``attention_multiplier``,
causal softmax, query head ``h`` reads key/value head ``h // (heads / kv
heads)``. *Mamba-2* (``mamba_n_heads`` heads of ``mamba_d_head``, one group,
``mamba_d_state``, convolution width ``mamba_d_conv`` with bias): ``[z | xBC
| dt] = W_in y``; ``xBC_t = silu(sum_j w_j xBC_{t-3+j} + b)`` (depthwise,
causal, zeros before the start); ``[x | B | C] = xBC_t``; ``dt = softplus(dt
+ dt_bias)``; ``A = -exp(A_log)`` a head; ``h_t = exp(dt_t A) h_{t-1} + dt_t
x_t (outer) B_t``; ``y_t = h_t C_t + D x_t``; out ``W_out RMSNorm(y_t *
silu(z_t))`` (the norm over all ``d_inner`` channels, with a weight).

Departures from the published module: the MLP's ``input_linear`` is held as
its two halves (``gate``, ``up``), which is the same product; the published
``time_step_limit`` (0, inf) clamps nothing and is left out; no mixture of
experts (``num_local_experts`` 0).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def attention(y, pa, cfg: dict):
    """Causal grouped-query attention of one sequence ``y (S, D)``."""
    s = y.shape[0]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    q = mm(y, pa["q"]).reshape(s, h, d)
    k = jnp.repeat(mm(y, pa["k"]).reshape(s, hk, d), h // hk, axis=1)
    v = jnp.repeat(mm(y, pa["v"]).reshape(s, hk, d), h // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI)
    scores = scores * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return mm(o.reshape(s, h * d), pa["o"])


def mamba(y, pm, cfg: dict):
    """The Mamba-2 mixer of one sequence ``y (S, D)``, the recurrence one
    position at a time."""
    s = y.shape[0]
    hm, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di, k = hm * p, cfg["mamba_d_conv"]
    proj = mm(y, pm["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di: di + di + 2 * n], proj[:, -hm:]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(pm["conv_w"][j] * padded[j: j + s] for j in range(k))
    xbc = jax.nn.silu(conv + pm["conv_b"])
    x = xbc[:, :di].reshape(s, hm, p)
    bm, cm = xbc[:, di: di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + pm["dt_bias"])  # (S, Hm)
    a = -jnp.exp(pm["A_log"])

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return h, jnp.sum(h * c_t, axis=-1)

    _, yh = jax.lax.scan(step, jnp.zeros((hm, p, n), F32), (x, dt, bm, cm))
    yh = yh + pm["D"][:, None] * x
    g = yh.reshape(s, di) * jax.nn.silu(z)
    return mm(rms_norm(g, pm["norm"], cfg["rms_norm_eps"]), pm["out_proj"])


@partial(jax.jit, static_argnums=(2,))
def _layer(x, pl, cfg_items):
    cfg = dict(cfg_items)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    y = rms_norm(x, pl["mixer_norm"], eps)
    mixed = (mamba(y, pl["mamba"], cfg) if "mamba" in pl
             else attention(y, pl["attn"], cfg))
    x = x + r * mixed
    y = rms_norm(x, pl["mlp_norm"], eps)
    pm = pl["mlp"]
    return x + r * mm(jax.nn.silu(mm(y, pm["gate"])) * mm(y, pm["up"]),
                      pm["down"])


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``."""
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "attention_multiplier", "residual_multiplier", "rms_norm_eps",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv")
    return tuple((k, cfg[k]) for k in keys)


def hidden_states(params, tokens, cfg: dict):
    """Final hidden states ``(S, D)`` of one sequence, a jitted layer at a
    time (two programs: one a kind of layer)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        pl = params[f"layer_{i}"]
        if ("mamba" in pl) != (kind == "mamba"):
            raise ValueError(f"layer {i} is {kind!r}, its weights are not")
        x = _layer(x, pl, items)
    return x


def forward(params, tokens, cfg: dict):
    """Logits ``(S, V)`` of one sequence (small sizes, tests)."""
    x = hidden_states(params, tokens, cfg)
    y = rms_norm(x, params["norm_f"], cfg["rms_norm_eps"])
    return mm(y, params["embed"].T) / cfg["logits_scaling"]


@partial(jax.jit, static_argnums=(5, 6))
def _gaps_at(norm_f, embed, x, positions, served, eps, scaling):
    """``x (S, D)`` final hidden states; ``positions``/``served`` ``(N,)``:
    where each served token was predicted and which it was. Returns (gap,
    the reference's own choice) ``(N,)``."""
    logits = mm(rms_norm(x[positions], norm_f, eps), embed.T) / scaling
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got, jnp.argmax(logits, axis=-1)


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      pad_multiple: int = 256, longest: int = 0,
                      most_outputs: int = 0):
    """For each request, teacher-force ``prompt + served tokens`` through
    the reference and return one array per request of ``best logit - served
    token's logit`` at every served position (0 where the served token is
    the reference's own choice), plus the share of served tokens that are
    the reference's choice. Served token ``j`` of a prompt of ``s`` tokens
    is predicted at position ``s - 1 + j`` from ``prompt + outputs[:j]``.
    A sequence is padded on the right (inert: attention is causal and the
    recurrence runs forward) to the mix's ``longest`` and its answer to
    ``most_outputs``, so that every run of a cell uses the same compiled
    programs."""
    longest = max([longest] + [len(p) + len(o) - 1
                               for p, o in zip(prompts, outputs)])
    s_pad = -(-longest // pad_multiple) * pad_multiple
    n_out = max([most_outputs] + [len(o) for o in outputs])
    gaps, agree, total = [], 0, 0
    for prompt, output in zip(prompts, outputs):
        seq = list(prompt) + list(output[:-1])
        tokens = np.zeros((s_pad,), np.int32)
        tokens[: len(seq)] = seq
        k = len(output)
        positions = np.zeros((n_out,), np.int32)
        served = np.zeros((n_out,), np.int32)
        positions[:k] = len(prompt) - 1 + np.arange(k)
        served[:k] = output
        x = hidden_states(params, jnp.asarray(tokens), cfg)
        gap, best = _gaps_at(params["norm_f"], params["embed"], x,
                             jnp.asarray(positions), jnp.asarray(served),
                             cfg["rms_norm_eps"], cfg["logits_scaling"])
        gap, best = np.asarray(gap), np.asarray(best)
        gaps.append(gap[:k].astype(np.float64))
        agree += int(np.sum(best[:k] == served[:k]))
        total += k
    return gaps, agree / max(total, 1)
