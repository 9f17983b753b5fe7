"""Plain float32 hybrid gated delta-rule / attention decoder: the reference
the ``olmo-hybrid-7b-serve-kv8`` configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys
(``model_type`` ``olmo_hybrid``), float32 throughout, every matrix product
at ``Precision.HIGHEST``. No cache, no kernels, and **no chunks**: the delta
rule is a ``lax.scan`` over the positions, one token at a time, so that it
is independent of the program's chunked (WY) form and of its one-step kernel
alike. Nothing is imported from the program under test. It is given the
seeded weights the benchmark made (bfloat16) and upcasts them a layer at a
time, inside that layer's jitted function, so that 16 GB of float32 never
stand beside the 8 GB tree.

``x0 = E[token]``. Layer ``l``: ``h = x + RMSNorm(mixer_l(x))``, then ``x' =
h + RMSNorm(W_down(silu(W_gate h) * W_up h))`` (``rms_norm_eps``; no norm
before the mixer or the MLP). Logits: ``RMSNorm(x) W_head`` (untied).
*Full attention* (``layer_types[l] == "full_attention"``): ``q =
RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the whole projected width,
``v = W_v x``; ``num_attention_heads`` query heads and
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads``, no bias, no rotary, scores over the root of the head
size, causal softmax. *Gated delta rule* (``linear_num_value_heads`` heads,
keys of ``linear_key_head_dim``, values of ``linear_value_head_dim``,
convolution width ``linear_conv_kernel_dim``, no bias): ``[q | k | v | z] =
W_in x``, ``[b | a] = W_ba x``; ``[q | k | v]_t = silu(sum_j w_j [q | k |
v]_{t-3+j})`` (depthwise, causal, zeros before the start); a head: ``q = q /
|q| / sqrt(d_k)``, ``k = k / |k|`` (``x rsqrt(sum x^2 + 1e-6)``); ``beta = 2
sigmoid(b)`` (``linear_allow_neg_eigval``, else ``sigmoid(b)``); ``alpha =
exp(-exp(A_log) softplus(a + dt_bias))``; ``S~ = alpha S``, ``u = beta (v -
S~^T k)``, ``S = S~ + k u^T``, ``o = S^T q``; out ``W_out (RMSNorm(o) *
silu(z))``, the norm a head with one weight of ``d_v``.

What the published config does not settle is the family's convention (the
configuration's ``assumed`` lists it): the block's norm order is Olmo 2's
and 3's, the q/k norm is over the whole width as theirs is, ``rope_theta``
null is read as no rotary, and the delta-rule layer is Qwen3-Next's
``GatedDeltaNet`` (whose config keys ``linear_*`` are this model's) with as
many key heads as value heads.
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


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


def attention(x, pa, cfg: dict):
    """Causal attention of one sequence ``x (S, D)``."""
    s = x.shape[0]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    q = rms_norm(mm(x, pa["q"]), pa["q_norm"], eps).reshape(s, h, d)
    k = rms_norm(mm(x, pa["k"]), pa["k_norm"], eps).reshape(s, hk, d)
    k = jnp.repeat(k, h // hk, axis=1)
    v = jnp.repeat(mm(x, pa["v"]).reshape(s, hk, d), h // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return mm(o.reshape(s, h * d), pa["o"])


def delta_recurrence(q, k, v, alpha, beta):
    """The gated delta rule a position at a time from a zero state: ``q``,
    ``k (S, H, dk)``, ``v (S, H, dv)``, ``alpha``, ``beta (S, H)`` -> ``(o (S,
    H, dv)``, the state after the last position ``(H, dk, dv))``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        state = alpha_t[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    state, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32),
                            (q, k, v, alpha, beta))
    return o, state


def delta_rule(x, pg, cfg: dict):
    """The gated delta-rule mixer of one sequence ``x (S, D)``, the
    recurrence one position at a time."""
    s = x.shape[0]
    h, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, kw = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    c = h * (2 * dk + dv)
    proj, ba = mm(x, pg["in_proj"]), mm(x, pg["ba_proj"])
    qkv, z = proj[:, :c], proj[:, c:].reshape(s, h, dv)
    padded = jnp.pad(qkv, ((kw - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(pg["conv_w"][j] * padded[j: j + s]
                          for j in range(kw)))
    q = l2_norm(qkv[:, : h * dk].reshape(s, h, dk)) / np.sqrt(dk)
    k = l2_norm(qkv[:, h * dk: 2 * h * dk].reshape(s, h, dk))
    v = qkv[:, 2 * h * dk:].reshape(s, h, dv)
    beta = jax.nn.sigmoid(ba[:, :h])
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(pg["A_log"])
                    * jax.nn.softplus(ba[:, h:] + pg["dt_bias"]))
    o, _ = delta_recurrence(q, k, v, alpha, beta)
    g = rms_norm(o, pg["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return mm(g.reshape(s, h * dv), pg["out_proj"])


@partial(jax.jit, static_argnums=(2,))
def _layer(x, pl, cfg_items):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    mixed = (delta_rule(x, pl["gdn"], cfg) if "gdn" in pl
             else attention(x, pl["attn"], cfg))
    h = x + rms_norm(mixed, pl["mixer_norm"], eps)
    pm = pl["mlp"]
    out = mm(jax.nn.silu(mm(h, pm["gate"])) * mm(h, pm["up"]), pm["down"])
    return h + rms_norm(out, pl["mlp_norm"], eps)


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``; a stated rotary base is refused."""
    theta = cfg.get("rope_theta",
                    (cfg.get("rope_parameters") or {}).get("rope_theta"))
    if theta is not None:
        raise ValueError(f"rope_theta {theta!r}: the reference rotates "
                         "nothing (the published value is null)")
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "rms_norm_eps", "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval")
    return tuple((k, cfg[k]) for k in keys)


def hidden_states(params, tokens, cfg: dict):
    """Final hidden states ``(S, D)`` of one sequence, a jitted layer at a
    time (two programs: one a kind of layer)."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    for i, kind in enumerate(cfg["layer_types"]):
        pl = params[f"layer_{i}"]
        if ("gdn" in pl) != (kind == "linear_attention"):
            raise ValueError(f"layer {i} is {kind!r}, its weights are not")
        x = _layer(x, pl, items)
    return x


def forward(params, tokens, cfg: dict):
    """Logits ``(S, V)`` of one sequence (small sizes, tests)."""
    x = hidden_states(params, tokens, cfg)
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
                      pad_multiple: int = 64, longest: int = 0,
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
        gap, best = _gaps_at(params["norm_f"], params["head"], x,
                             jnp.asarray(positions), jnp.asarray(served),
                             cfg["rms_norm_eps"])
        gap, best = np.asarray(gap), np.asarray(best)
        gaps.append(gap[:k].astype(np.float64))
        agree += int(np.sum(best[:k] == served[:k]))
        total += k
    return gaps, agree / max(total, 1)
