"""Plain float32 window/global grouped-query decoder with routed experts:
the reference the ``smallthinker-21b-serve-kv8`` configuration's ``correct``
is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys,
float32 throughout, every matrix product at ``Precision.HIGHEST``. Whole
sequences under a banded causal mask: no cache, no ring, no kernels, no
sorting of tokens. The experts are a plain loop, each applied to every token
and weighted by the token's combine weight for it (zero where the router did
not choose it). Nothing is imported from the program under test. It is given
the seeded weights the benchmark made (bfloat16) and upcasts them as it
goes, a block of experts at a time, since 3.97 B parameters in float32 do
not fit beside the bfloat16 tree; queries go in blocks too, so that no ``(H,
S, S)`` tensor is held.

Block ``l``, with ``d_head`` 128, 28 query heads over 4 K/V heads (query
head ``h`` reads K/V head ``h // 7``) and no bias anywhere::

    y  = RMSNorm(x; in_norm)                       eps = rms_norm_eps
    r  = y W_r                                     the router reads y
    q, k, v = y W_q, y W_k, y W_v
    rope_layout[l] = 1: q, k turned at the token's position, all of d_head,
        theta = rope_theta, pairs (i, i + d_head / 2); 0: not turned
    s_ij = q_i . k_j / sqrt(d_head)
    seen: j <= i, and where sliding_window_layout[l] = 1, i - j < sliding_window_size
    x1 = x + softmax(s) v W_o
    z  = RMSNorm(x1; post_norm)
    idx = top-k of r (k = moe_num_active_primary_experts); w = softmax(r[idx])
    x2 = x1 + sum_i w_i W_down,idx_i (relu(W_gate,idx_i z) * W_up,idx_i z)

then the final ``RMSNorm`` and the untied head.

Departures from the published description, each an inference where the
``config.json`` holds no key (the configuration's ``assumed`` lists them):
the router reads the attention's input ("router placed before attention");
the expert is a ReLU-gated linear unit ("sparse ReGLU"); the rotary pairs
are half-split, as in the family's modelling code; the window holds the
token itself and the ``W - 1`` before it; no bias. ``norm_topk_prob`` changes
nothing, since a softmax over the chosen logits sums to 1. Left out: the
paper's secondary (predictor) experts and its sparse head, for which the
config holds no key; ``rope_scaling`` is null.

``router_reads`` (``"block_input"``; ``"expert_input"`` routes from ``z``)
and ``expert_gate`` (``"relu"``; ``"silu"``) are read from the
configuration where a test states them, to show that the comparison tells
those readings apart.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
GATES = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def rope(x, positions, theta):
    """``x (S, H, d)``: each pair ``(x[i], x[i + d/2])`` turned by the angle
    ``position * theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), F32)
    ang = (positions.astype(F32)[:, None] * inv)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(x, pa, cfg: dict, layer: int, q_block: int):
    """Attention of one sequence ``x (S, D)`` (already normed) at layer
    ``layer``: causal, banded where the layer has a window."""
    s = x.shape[0]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pos = jnp.arange(s)
    q = mm(x, pa["q"]).reshape(s, h, dh)
    k = mm(x, pa["k"]).reshape(s, hk, dh)
    v = mm(x, pa["v"]).reshape(s, hk, dh)
    if cfg["rope_layout"][layer]:
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, h // hk, axis=1)  # query head h reads head h // (H/Hk)
    v = jnp.repeat(v, h // hk, axis=1)
    outs = []
    for lo in range(0, s, q_block):
        qb = q[lo: lo + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        scores = scores / math.sqrt(dh)
        back = pos[lo: lo + q_block, None] - pos[None, :]
        seen = back >= 0
        if cfg["sliding_window_layout"][layer]:
            seen &= back < cfg["sliding_window_size"]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HI))
    return mm(jnp.concatenate(outs).reshape(s, h * dh), pa["o"])


def combine_weights(r, cfg: dict):
    """``(T, E)`` from the router's logits ``r``: each token's weight for
    each expert, the softmax over its chosen logits, zero elsewhere."""
    k = cfg["moe_num_active_primary_experts"]
    chosen = jnp.argsort(-r, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(r, chosen, axis=-1)
    w = jax.nn.softmax(picked, axis=-1)
    return jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], chosen].set(w)


@partial(jax.jit, static_argnums=(5,))
def _experts_block(z, w, gate, up, down, act):
    """``sum_e w[:, e] * E_e(z)`` over one block of experts, one at a
    time."""
    def one(acc, xs):
        w_e, g, u, d = xs
        return acc + w_e[:, None] * mm(GATES[act](mm(z, g)) * mm(z, u),
                                       d), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(z), (w.T, gate, up, down))
    return acc


@partial(jax.jit, static_argnums=(2, 3, 4))
def _attn_half(x, pl, cfg_items, layer, q_block):
    """``(x1, z, the combine weights)`` of one block."""
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, pl["in_norm"], eps)
    x1 = x + attention(y, pl["attn"], cfg, layer, q_block)
    z = rms_norm(x1, pl["post_norm"], eps)
    reads = y if cfg["router_reads"] == "block_input" else z
    return x1, z, combine_weights(mm(reads, pl["router"]), cfg)


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "sliding_window_size",
            "moe_num_active_primary_experts")
    n = cfg["num_hidden_layers"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_layout", tuple(cfg["rope_layout"][:n])),
        ("sliding_window_layout", tuple(cfg["sliding_window_layout"][:n])),
        ("router_reads", cfg.get("router_reads", "block_input")),
    )


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 32):
    """Final hidden states ``(S, D)`` of one sequence, layer by layer; the
    experts go one jitted block at a time."""
    items = _cfg_items(cfg)
    act = cfg.get("expert_gate", "relu")
    x = params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        pl = params[f"layer_{i}"]
        pm = pl["moe"]
        x1, z, w = _attn_half(
            x, {"in_norm": pl["in_norm"], "post_norm": pl["post_norm"],
                "attn": pl["attn"], "router": pm["router"]},
            items, i, q_block)
        out = jnp.zeros_like(z)
        for lo in range(0, cfg["moe_num_primary_experts"], expert_block):
            hi = lo + expert_block
            out = out + _experts_block(z, w[:, lo:hi], pm["gate"][lo:hi],
                                       pm["up"][lo:hi], pm["down"][lo:hi],
                                       act)
        x = x1 + out
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
                      pad_multiple: int = 256, lengths=(),
                      most_outputs: int = 0, q_block: int = 512,
                      expert_block: int = 32):
    """For each request, teacher-force ``prompt + served tokens`` through
    the reference and return one array per request of ``best logit - served
    token's logit`` at every served position (0 where the served token is
    the reference's own choice), plus the share of served tokens that are
    the reference's choice. Served token ``j`` of a prompt of ``s`` tokens
    is predicted at position ``s - 1 + j`` from ``prompt + outputs[:j]``.
    A sequence is padded on the right (inert under the causal mask) to the
    shortest of ``lengths`` (the longest sequence of each of the mix's
    prompt groups) that holds it and its answer to ``most_outputs``, so that
    every run of a cell uses the same few compiled programs, and a short
    request does not cost a long one's work."""
    n_out = max([most_outputs] + [len(o) for o in outputs])
    gaps, agree, total = [], 0, 0
    for prompt, output in zip(prompts, outputs):
        seq = list(prompt) + list(output[:-1])
        fits = [n for n in sorted(lengths) if n >= len(seq)] or [len(seq)]
        tokens = np.zeros((-(-fits[0] // pad_multiple) * pad_multiple,),
                          np.int32)
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
