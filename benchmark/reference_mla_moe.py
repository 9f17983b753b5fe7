"""Plain float32 latent-attention (MLA) decoder with routed experts: the
reference the ``joyai-flash-serve-kv8`` configuration's ``correct`` is held
to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys,
float32 throughout, every matrix product at ``Precision.HIGHEST``. No cache
(every key and value of every head is rebuilt from the latent, the
expanded form), no kernels, no sorting of tokens: the experts are a plain
loop, each applied to every token and weighted by the token's combine
weight for it (zero where the router did not choose it). Nothing is
imported from the program under test. It is given the seeded weights the
benchmark made (bfloat16) and upcasts them as it goes, a block of experts
at a time, since 5.56 B parameters in float32 do not fit beside the
bfloat16 tree; queries go in blocks too, so that no ``(H, S, S)`` tensor is
held.

Block ``l``: ``h = x + Attn(RMSNorm(x))``, ``x' = h + FFN_l(RMSNorm(h))``;
``RMSNorm`` at ``rms_norm_eps``; final ``RMSNorm``, then the untied head.
Attention: ``c_q = RMSNorm(W_qa x)``; ``q = W_qb c_q`` per head ``[q_nope |
q_rope]``; ``[c_kv | k_r] = W_kva x``; ``c = RMSNorm(c_kv)``; ``q_rope`` and
``k_r`` rotated (interleaved pairs, ``rope_theta``, no scaling) at the
token's position, ``k_r`` shared by all heads; ``[k_nope_h | v_h] = W_kvb
c``; scores ``(q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(qk_head_dim)``,
causal softmax; ``W_o concat_h(sum p v_h)``. Layers below
``first_k_dense_replace``: ``W_down(silu(W_gate y) * W_up y)``. The others:
``s = sigmoid(W_g y)``; the ``num_experts_per_tok`` experts of largest ``s +
b`` (``n_group`` 1: no group limit); weights ``s_i / sum_chosen s`` (``norm_
topk_prob``) times ``routed_scaling_factor``; ``sum w_i E_i(y) +
E_shared(y)``. The multi-token prediction module is not part of the
next-token logits and is left out (``num_nextn_predict_layers`` -> 0 under
``reduced``).
"""

from __future__ import annotations

import math
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


def attention(x, pa, cfg: dict, q_block: int):
    """Causal attention of one sequence ``x (S, D)`` (already normed)."""
    s = x.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = mm(rms_norm(mm(x, pa["q_a"]), pa["q_a_norm"], eps), pa["q_b"])
    q = q.reshape(s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])], axis=-1)
    kv = mm(x, pa["kv_a"])
    c = rms_norm(kv[:, :rkv], pa["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], pos, cfg["rope_theta"])
    kv_h = mm(c, pa["kv_b"]).reshape(s, h, dn + dv)
    k = jnp.concatenate(
        [kv_h[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, h, dr))],
        axis=-1)
    v = kv_h[..., dn:]
    outs = []
    for lo in range(0, s, q_block):
        qb = q[lo: lo + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        scores = scores / math.sqrt(dn + dr)
        causal = pos[None, :] <= pos[lo: lo + q_block, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HI))
    return mm(jnp.concatenate(outs).reshape(s, h * dv), pa["o"])


def combine_weights(y, pm, cfg: dict):
    """``(T, E)``: each token's weight for each expert, zero where the
    expert was not chosen."""
    scores = jax.nn.sigmoid(mm(y, pm["router"]))
    k = cfg["num_experts_per_tok"]
    chosen = jnp.argsort(-(scores + pm["bias"]), axis=-1, stable=True)[:, :k]
    mask = jnp.zeros_like(scores).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(1.0)
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
def _attn_half(x, pl, cfg_items, q_block):
    cfg = dict(cfg_items)
    h = x + attention(rms_norm(x, pl["attn_norm"], cfg["rms_norm_eps"]),
                      pl["attn"], cfg, q_block)
    return h, rms_norm(h, pl["ffn_norm"], cfg["rms_norm_eps"])


_dense_jit = jax.jit(swiglu)
_moe_head = jax.jit(
    lambda y, pm, cfg_items: (combine_weights(y, pm, dict(cfg_items)),
                              swiglu(y, pm["shared"])),
    static_argnums=(2,))


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "n_routed_experts")
    return tuple((k, cfg[k]) for k in keys)


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 32):
    """Final hidden states ``(S, D)`` of one sequence, layer by layer; the
    expert layers go one jitted block of experts at a time."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        pl = params[f"layer_{i}"]
        h, y = _attn_half(x, {k: pl[k] for k in
                              ("attn_norm", "ffn_norm", "attn")},
                          items, q_block)
        if "mlp" in pl:
            x = h + _dense_jit(y, pl["mlp"])
            continue
        pm = pl["moe"]
        w, out = _moe_head(y, {k: pm[k] for k in
                               ("router", "bias", "shared")}, items)
        for lo in range(0, cfg["n_routed_experts"], expert_block):
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
    A sequence is padded on the right (inert under the causal mask) to the
    mix's ``longest`` and its answer to ``most_outputs``, so that every run
    of a cell uses the same compiled programs."""
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
