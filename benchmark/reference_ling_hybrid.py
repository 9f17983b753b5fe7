"""Plain float32 hybrid KDA / latent-attention decoder with group-limited
routed experts: the reference the ``ling-3.0-flash-vl-serve-kv8``
configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys
(Ling-3.0-flash-VL's language model), float32 throughout, every matrix
product at ``Precision.HIGHEST``. No cache, no kernels, **no chunks** (the
delta rule is a ``lax.scan`` over the positions, one token at a time, so that
it is independent of the program's chunked form and of its one-step kernel
alike), every head's key and value rebuilt from the latent, no sorting of
tokens: the experts held are a plain loop, each applied to every token and
weighted by the token's combine weight for it (zero where the router did not
choose it). Nothing is imported from the program under test. It is given the
seeded weights the benchmark made (bfloat16) and upcasts them as it goes, a
block of experts at a time.

``x0 = E[token]``; layer ``l``: ``h = x + mixer_l(RMSNorm(x))``, ``x' = h +
FFN_l(RMSNorm(h))`` (``rms_norm_eps``); logits ``RMSNorm(x) W_head`` (untied).
Which published layer a layer is, ``layers_kept`` says (all of them where the
key is absent): layer ``i`` of the source is latent attention where ``(i + 1)
% layer_group_size == 0``, KDA otherwise, and its FFN is dense where ``i <
first_k_dense_replace`` (:func:`layer_plan`).

*KDA* (``num_attention_heads`` heads of ``head_dim`` keys and values,
convolution width ``short_conv_kernel_size``, no bias): ``[q | k | v] = W_qkv
y``, ``f = W_f y``, ``[b | g] = W_bg y``; ``[q | k | v]_t = silu(sum_j w_j [q
| k | v]_{t-3+j})`` (depthwise, causal, zeros before the start); a head: ``q =
q / |q| / sqrt(d)``, ``k = k / |k|`` (``x / sqrt(sum x^2 + 1e-6)``); ``log
alpha = kda_lower_bound * sigmoid(exp(A_log) (f + dt_bias))``, a number a key
channel a head; ``beta = sigmoid(b)``; ``S~ = Diag(alpha) S``, ``u = beta (v -
S~^T k)``, ``S = S~ + k u^T``, ``o = S^T q``; out ``W_out (RMSNorm(o) *
sigmoid(g))``, the norm a head with one weight of ``head_dim``, ``g`` a
number a head. *Latent attention*: ``q = W_q y`` a head ``[q_nope | q_rope]``;
``[c_kv | k_r] = W_kva y``; ``c = RMSNorm(c_kv)``; ``q_rope`` and ``k_r``
rotated (interleaved pairs, ``rope_theta``) at the token's position, ``k_r``
shared by all heads; ``[k_nope_h | v_h] = W_kvb c``; scores ``(q_nope_h .
k_nope_h + q_rope_h . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``,
causal softmax; ``W_o (o_h * sigmoid(g_h))``, ``g = W_g y`` a number a head.
*Experts*: ``s = sigmoid(W_r y)`` over all the published experts; ``s' = s +
bias``; a group's score is the sum of its two largest ``s'`` (``n_group``
groups of neighbours); the ``topk_group`` best groups stay; the
``num_experts_per_tok`` largest ``s'`` among what stays are chosen; weights
the chosen ``s`` over their sum, times ``routed_scaling_factor``; ``sum w_i
E_i(y) + E_shared(y)``. The tree holds ``num_experts`` of the
``num_experts_published`` experts, those from ``first_expert`` on: the sum
runs over them alone, and what the absent experts would have added is left
out, as the program leaves it out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def layer_plan(cfg: dict) -> list:
    """``[(kind, dense)]`` a layer of the tree: ``kind`` ``"kda"`` or
    ``"mla"``, ``dense`` whether its FFN is the dense SwiGLU."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             i < cfg["first_k_dense_replace"]) for i in kept]


def mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


def rope(x, positions, theta):
    """``x (S, [H,] d)``: each pair ``(x[2i], x[2i+1])`` turned by the
    angle ``position * theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), F32)
    ang = positions.astype(F32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def swiglu(y, p):
    return mm(jax.nn.silu(mm(y, p["gate"])) * mm(y, p["up"]), p["down"])


def kda_recurrence(q, k, v, alpha, beta):
    """The delta rule gated a key channel, a position at a time from a zero
    state: ``q``, ``k``, ``alpha (S, H, dk)``, ``v (S, H, dv)``, ``beta (S,
    H)`` -> ``(o (S, H, dv)``, the state after the last position ``(H, dk,
    dv))``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        state = alpha_t[:, :, None] * state
        u = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    state, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32),
                            (q, k, v, alpha, beta))
    return o, state


def kda(y, pk, cfg: dict):
    """The KDA mixer of one sequence ``y (S, D)`` (already normed), the
    recurrence one position at a time."""
    s = y.shape[0]
    h, d, kw = (cfg["num_attention_heads"], cfg["head_dim"],
                cfg["short_conv_kernel_size"])
    qkv, f, bg = mm(y, pk["qkv"]), mm(y, pk["f"]), mm(y, pk["bg"])
    padded = jnp.pad(qkv, ((kw - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(pk["conv_w"][j] * padded[j: j + s]
                          for j in range(kw)))
    q, k, v = (qkv[:, i * h * d: (i + 1) * h * d].reshape(s, h, d)
               for i in range(3))
    q, k = l2_norm(q) / math.sqrt(d), l2_norm(k)
    gate = jnp.exp(pk["A_log"])[:, None] * (f + pk["dt_bias"]).reshape(s, h, d)
    alpha = jnp.exp(cfg["kda_lower_bound"] * jax.nn.sigmoid(gate))
    o, _ = kda_recurrence(q, k, v, alpha, jax.nn.sigmoid(bg[:, :h]))
    g = jax.nn.sigmoid(bg[:, h:])[:, :, None]
    o = rms_norm(o, pk["norm"], cfg["rms_norm_eps"]) * g
    return mm(o.reshape(s, h * d), pk["out"])


def attention(y, pa, cfg: dict, q_block: int):
    """Causal latent attention of one sequence ``y (S, D)`` (already
    normed), every head's key and value rebuilt from the latent."""
    s = y.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = mm(y, pa["q"]).reshape(s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])], axis=-1)
    kv = mm(y, pa["kv_a"])
    c = rms_norm(kv[:, :rkv], pa["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], pos, cfg["rope_theta"])
    kv_h = mm(c, pa["kv_b"]).reshape(s, h, dn + dv)
    k = jnp.concatenate(
        [kv_h[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, h, dr))],
        axis=-1)
    v = kv_h[..., dn:]
    outs = []
    for lo in range(0, s, q_block):
        scores = jnp.einsum("qhd,khd->hqk", q[lo: lo + q_block], k,
                            precision=HI) / math.sqrt(dn + dr)
        causal = pos[None, :] <= pos[lo: lo + q_block, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HI))
    o = jnp.concatenate(outs) * jax.nn.sigmoid(mm(y, pa["g"]))[:, :, None]
    return mm(o.reshape(s, h * dv), pa["o"])


def combine_weights(y, pm, cfg: dict):
    """``(T, E)`` over all the published experts: each token's weight for
    each, zero where the expert was not chosen."""
    scores = jax.nn.sigmoid(mm(y, pm["router"]))
    biased = scores + pm["bias"]
    t, e = scores.shape
    n_group = cfg["n_group"]
    if n_group > 1:
        groups = biased.reshape(t, n_group, e // n_group)
        two = -jnp.sort(-groups, axis=-1)[..., :2]
        best = jnp.argsort(-jnp.sum(two, axis=-1), axis=-1,
                           stable=True)[:, : cfg["topk_group"]]
        stays = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        biased = jnp.where(jnp.repeat(stays, e // n_group, axis=1), biased,
                           -jnp.inf)
    chosen = jnp.argsort(-biased, axis=-1,
                         stable=True)[:, : cfg["num_experts_per_tok"]]
    mask = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(1.0)
    picked = scores * mask
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked * cfg["routed_scaling_factor"]


@jax.jit
def _experts_block(y, w, gate, up, down):
    """``sum_e w[:, e] * E_e(y)`` over one block of experts, one at a
    time."""
    def one(acc, xs):
        w_e, g, u, d = xs
        return acc + w_e[:, None] * swiglu(y, {"gate": g, "up": u,
                                               "down": d}), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (w.T, gate, up, down))
    return acc


@partial(jax.jit, static_argnums=(2, 3))
def _mixer_half(x, pl, cfg_items, q_block):
    cfg = dict(cfg_items)
    y = rms_norm(x, pl["mixer_norm"], cfg["rms_norm_eps"])
    h = x + (kda(y, pl["kda"], cfg) if "kda" in pl
             else attention(y, pl["attn"], cfg, q_block))
    return h, rms_norm(h, pl["ffn_norm"], cfg["rms_norm_eps"])


_dense_jit = jax.jit(swiglu)
_moe_head = jax.jit(
    lambda y, pm, cfg_items: (combine_weights(y, pm, dict(cfg_items)),
                              swiglu(y, pm["shared"])),
    static_argnums=(2,))


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``; a clamped SwiGLU on a layer that is kept is
    refused."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = cfg.get(key) or ()
        if any(limits[i] for i in kept if i < len(limits)):
            raise ValueError(f"{key} is non-zero on a layer that is kept: "
                             "the reference clamps nothing")
    keys = ("num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kda_lower_bound", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "n_group", "topk_group")
    return tuple((k, cfg[k]) for k in keys)


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 32):
    """Final hidden states ``(S, D)`` of one sequence, layer by layer; the
    expert layers go one jitted block of the held experts at a time."""
    items = _cfg_items(cfg)
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    x = params["embed"][tokens].astype(F32)
    for i, (kind, dense) in enumerate(layer_plan(cfg)):
        pl = params[f"layer_{i}"]
        if ("kda" if kind == "kda" else "attn") not in pl or (
                "mlp" in pl) != dense:
            raise ValueError(f"layer {i} is {kind!r}, dense {dense}; its "
                             "weights are not")
        h, y = _mixer_half(x, {k: v for k, v in pl.items()
                               if k not in ("mlp", "moe")}, items, q_block)
        if dense:
            x = h + _dense_jit(y, pl["mlp"])
            continue
        pm = pl["moe"]
        w, out = _moe_head(y, {k: pm[k] for k in
                               ("router", "bias", "shared")}, items)
        w = w[:, first: first + held]  # the held experts' columns
        for lo in range(0, held, expert_block):
            hi = lo + expert_block
            out = out + _experts_block(y, w[:, lo:hi], pm["gate"][lo:hi],
                                       pm["up"][lo:hi], pm["down"][lo:hi])
        x = h + out
    return x


def forward(params, tokens, cfg: dict, q_block: int = 512,
            expert_block: int = 32):
    """Logits ``(S, V)`` of one sequence (small sizes, tests)."""
    x = hidden_states(params, tokens, cfg, q_block, expert_block)
    return mm(rms_norm(x, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"])


@partial(jax.jit, static_argnums=(5,))
def _gaps_at(norm_f, head, x, positions, served, eps):
    """``x (S, D)`` final hidden states; ``positions``/``served`` ``(N,)``:
    where each served token was predicted and which it was. Returns (gap,
    the reference's own choice) ``(N,)``."""
    logits = mm(rms_norm(x[positions], norm_f, eps), head)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got, jnp.argmax(logits, axis=-1)


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      pad_multiple: int = 256, longest: int = 0,
                      most_outputs: int = 0, q_block: int = 512,
                      expert_block: int = 32):
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
        x = hidden_states(params, jnp.asarray(tokens), cfg, q_block,
                          expert_block)
        gap, best = _gaps_at(params["norm_f"], params["head"], x,
                             jnp.asarray(positions), jnp.asarray(served),
                             cfg["rms_norm_eps"])
        gap, best = np.asarray(gap), np.asarray(best)
        gaps.append(gap[:k].astype(np.float64))
        agree += int(np.sum(best[:k] == served[:k]))
        total += k
    return gaps, agree / max(total, 1)
