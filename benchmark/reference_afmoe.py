"""Plain float32 sandwich-normed window/full grouped-query decoder with a
held share of sigmoid-routed experts beside a shared expert: the reference
the ``trinity-large-serve-kv8`` configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys
(``model_type: afmoe``), float32 throughout, every matrix product at
``Precision.HIGHEST``. Whole sequences under a banded causal mask written
out: no cache, no ring, no kernels, no sorting of tokens. The experts held
are a plain loop, each applied to every token and weighted by the token's
combine weight for it (zero where the router did not choose it). Nothing is
imported from the program under test; the matrix product, the norm, the
half-split rotation and the reading of served tokens are
``reference_window_moe``'s own plain functions. It is given the seeded weights
the benchmark made (bfloat16) and upcasts them as it goes, a block of
experts at a time; queries go in blocks too, so that no ``(H, S, S)`` tensor
is held.

Which published layer a layer is, ``layers_kept`` says (all of them where
the file is no cut): layer ``i`` attends a window where ``layer_types[i]`` is
``sliding_attention`` and its FFN is dense where ``i < num_dense_layers``.
Block ``l``, with 48 query heads over 8 K/V heads of 128 (query head ``h``
reads K/V head ``h // 6``), RMSNorm at ``rms_norm_eps`` and no bias::

    x0 = embed[tokens] * sqrt(hidden_size)              mup_enabled
    a  = RMSNorm(x; in_norm)
    q, k, v = a W_q, a W_k, a W_v
    q, k = RMSNorm(q; q_norm), RMSNorm(k; k_norm)       over a head's 128
    sliding layer: q, k turned at the token's position, all of head_dim,
        theta = rope_theta, pairs (i, i + head_dim / 2); full layer: not turned
    s_ij = q_i . k_j / sqrt(head_dim)
    seen: j <= i, and on a sliding layer i - j < sliding_window
    h  = x + RMSNorm((softmax(s) v * sigmoid(a W_g)) W_o; post_attn_norm)
    m  = RMSNorm(h; pre_mlp_norm)
    f  = W_down (silu(W_gate m) * W_up m)               a dense layer
       = shared(m) + sum_{i in chosen, held} w_i E_i(m) otherwise
    x' = h + RMSNorm(f; post_mlp_norm)
    s = sigmoid(m W_r) over all num_experts_published; chosen = the
        num_experts_per_tok largest s + bias; w = s[chosen] / (sum + 1e-20)
        * route_scale

then the final ``RMSNorm`` and the untied head. The chip's share: the tree
holds ``num_experts`` experts of the ``num_experts_published``, those from
``first_expert`` on; a chosen expert that is not held adds nothing.

Departures from the published description, each an inference where the
``config.json`` holds no key (the configuration's ``assumed`` lists them):
the rotation on the sliding layers alone and its half-split pairs, the place
of the four norms, the gate as wide as the heads' output, the window holding
the token itself and the ``W - 1`` before it. ``described_as``'s
"depth-scaled" is an initialisation of the norms' gains and nothing at
inference.

Four readings are read from the configuration where a test states them, to
show that the comparison tells them apart: ``rotate_full_layers`` (true turns
``q`` and ``k`` on the full layers too), ``qk_norm`` (false leaves ``q`` and
``k`` as projected), ``attention_gate`` (``"head_wise"`` gates a head by one
number, the first of its 128) and ``sandwich`` (false adds a sub-layer's
output as it is).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_window_moe import F32, HI, _gaps_at, mm, rms_norm, rope


def layer_plan(cfg: dict) -> list:
    """``(window, dense)`` of each layer kept: its window (0 on a full
    layer) and whether its FFN is the dense one."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [(cfg["sliding_window"]
             if cfg["layer_types"][i] == "sliding_attention" else 0,
             i < cfg["num_dense_layers"]) for i in kept]


def swiglu(x, p):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def attention(a, pa, cfg: dict, window: int, q_block: int):
    """Gated attention of one sequence ``a (S, D)`` (already normed), up to
    and with the output projection: causal, banded where ``window``."""
    s = a.shape[0]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = mm(a, pa["q"]).reshape(s, h, dh)
    k = mm(a, pa["k"]).reshape(s, hk, dh)
    v = mm(a, pa["v"]).reshape(s, hk, dh)
    if cfg["qk_norm"]:
        q, k = rms_norm(q, pa["q_norm"], eps), rms_norm(k, pa["k_norm"], eps)
    if window or cfg["rotate_full_layers"]:
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
        if window:
            seen &= back < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HI))
    gate = jax.nn.sigmoid(mm(a, pa["g"])).reshape(s, h, dh)
    if cfg["attention_gate"] == "head_wise":
        gate = gate[:, :, :1]
    return mm((jnp.concatenate(outs) * gate).reshape(s, h * dh), pa["o"])


def combine_weights(m, pm, cfg: dict):
    """``(T, E)`` over all the published experts: each token's weight for
    each, zero where the expert was not chosen."""
    scores = jax.nn.sigmoid(mm(m, pm["router"]))
    chosen = jnp.argsort(-(scores + pm["bias"]), axis=-1,
                         stable=True)[:, : cfg["num_experts_per_tok"]]
    picked = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(1.0) * scores
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return picked * cfg["route_scale"]


@jax.jit
def _experts_block(m, w, gate, up, down):
    """``sum_e w[:, e] * E_e(m)`` over one block of experts, one at a
    time."""
    def one(acc, xs):
        w_e, g, u, d = xs
        return acc + w_e[:, None] * swiglu(m, {"gate": g, "up": u,
                                               "down": d}), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), (w.T, gate, up, down))
    return acc


def _added(x, out, w, cfg: dict):
    """The residual stream after a sub-layer's output: normed first under
    the sandwich."""
    return x + (rms_norm(out, w, cfg["rms_norm_eps"]) if cfg["sandwich"]
                else out)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _attn_half(x, pl, cfg_items, window, q_block):
    """``(h, m)`` of one block: the stream after attention, and the FFN's
    input."""
    cfg = dict(cfg_items)
    a = rms_norm(x, pl["in_norm"], cfg["rms_norm_eps"])
    h = _added(x, attention(a, pl["attn"], cfg, window, q_block),
               pl["post_attn_norm"], cfg)
    return h, rms_norm(h, pl["pre_mlp_norm"], cfg["rms_norm_eps"])


_dense_jit = jax.jit(swiglu)
_moe_head = jax.jit(
    lambda m, pm, cfg_items: (combine_weights(m, pm, dict(cfg_items)),
                              swiglu(m, pm["shared"])),
    static_argnums=(2,))
_added_jit = jax.jit(lambda x, out, w, cfg_items: _added(
    x, out, w, dict(cfg_items)), static_argnums=(3,))


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``; a router in groups is refused."""
    for key in ("n_group", "topk_group"):
        if cfg.get(key, 1) != 1:
            raise ValueError(f"{key} = {cfg[key]}: the reference selects "
                             "among all the experts at once")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("the reference's router scores by a sigmoid")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "route_norm", "route_scale")
    return tuple((k, cfg[k]) for k in keys) + (
        ("rotate_full_layers", cfg.get("rotate_full_layers", False)),
        ("qk_norm", cfg.get("qk_norm", True)),
        ("attention_gate", cfg.get("attention_gate", "element_wise")),
        ("sandwich", cfg.get("sandwich", True)),
    )


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 8):
    """Final hidden states ``(S, D)`` of one sequence, layer by layer; the
    expert layers go one jitted block of the held experts at a time."""
    items = _cfg_items(cfg)
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    x = params["embed"][tokens].astype(F32)
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    for i, (window, dense) in enumerate(layer_plan(cfg)):
        pl = params[f"layer_{i}"]
        if ("mlp" in pl) != dense:
            raise ValueError(f"layer {i} is dense {dense}; its weights are "
                             "not")
        h, m = _attn_half(x, {k: v for k, v in pl.items()
                              if k not in ("mlp", "moe", "post_mlp_norm")},
                          items, window, q_block)
        if dense:
            out = _dense_jit(m, pl["mlp"])
        else:
            pm = pl["moe"]
            w, out = _moe_head(m, {k: pm[k] for k in
                                   ("router", "bias", "shared")}, items)
            w = w[:, first: first + held]  # the held experts' columns
            for lo in range(0, held, expert_block):
                hi = lo + expert_block
                out = out + _experts_block(m, w[:, lo:hi], pm["gate"][lo:hi],
                                           pm["up"][lo:hi],
                                           pm["down"][lo:hi])
        x = _added_jit(h, out, pl["post_mlp_norm"], items)
    return x


def forward(params, tokens, cfg: dict, q_block: int = 512,
            expert_block: int = 8):
    """Logits ``(S, V)`` of one sequence (small sizes, tests)."""
    x = hidden_states(params, tokens, cfg, q_block, expert_block)
    return mm(rms_norm(x, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"])


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      pad_multiple: int = 256, lengths=(),
                      most_outputs: int = 0, q_block: int = 512,
                      expert_block: int = 8):
    """``reference_window_moe.served_token_gaps`` over this module's
    :func:`hidden_states`: for each request, ``best logit - served token's
    logit`` at every served position of ``prompt + served tokens``
    teacher-forced through the reference, and the share of served tokens
    that are the reference's own choice. A sequence is padded on the right
    (inert under the causal mask) to the shortest of ``lengths`` that holds
    it, so that every run of a cell uses the same few compiled programs."""
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
