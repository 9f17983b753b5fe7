"""Plain float32 looped decoder: one stack of sandwich-normed multi-head
attention and dense SwiGLU layers run ``total_ut_steps`` times over a
sequence, with an exit gate after every pass: the reference the
``ouro-2.6b-serve-kv8`` configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys
(``model_type: ouro``), float32 throughout, every matrix product at
``Precision.HIGHEST``. Whole sequences under a causal mask written out: no
cache, no kernels, no batching, no scan. Nothing is imported from the
program under test; the matrix product, the norm and the half-split rotation
are ``reference_window_moe``'s own plain functions. It is given the seeded
weights the benchmark made (bfloat16) and upcasts one layer's at a time (the
float32 tree would be 10.7 GB); queries go in blocks, so that no ``(H, S,
S)`` tensor is held. A sequence, with ``T = total_ut_steps``, ``L =
num_hidden_layers``, 16 heads of 128 for queries, keys and values alike,
RMSNorm at ``rms_norm_eps``::

    x = E[tokens]
    for t in 0..T-1:                      the same L layers' weights every pass
      for l in 0..L-1:
        a = RMSNorm(x; in_norm_l)
        q, k, v = a W_q, a W_k, a W_v
        q, k turned at the token's position, all of head_dim, theta =
            rope_theta, pairs (i, i + head_dim / 2)
        s_ij = q_i . k_j / sqrt(head_dim), seen: j <= i       this pass's k
        x = x + RMSNorm(softmax(s) v W_o; post_attn_norm_l)
        m = RMSNorm(x; pre_mlp_norm_l)
        x = x + RMSNorm(W_down (silu(W_gate m) * W_up m); post_mlp_norm_l)
      x = RMSNorm(x; norm_f)              closes the pass, enters the next
      lam_t = sigmoid(x . w_exit + b_exit)
    logits = x W_head                     after pass T

and ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass taking what is
left; a token leaves at the first ``t`` whose running sum of ``p`` reaches
``early_exit_threshold``, which at the published 1.0 is pass ``T`` for every
token. The reference computes every pass and reads the last one's logits,
and refuses a threshold under 1.

Departures from the published description, each an inference where the
``config.json`` holds no key (the configuration's ``assumed`` lists them,
from the family's published modelling code as recalled):

* no bias on any projection;
* the sandwich: a sub-layer's output is normed before it is added (four
  norms a layer);
* the final norm closes every pass and its output enters the next, so the
  head reads the last pass's closed stream without another norm;
* the exit gate is one linear map of the closed stream to a number, with a
  bias, under a sigmoid;
* pass ``t`` attends to the keys and values pass ``t`` itself computed for
  the positions before (a cache slot a pass a layer), never another pass's.

One reading is read from the configuration where a test states it, to show
that the comparison tells it apart: ``norm_between_passes`` (false applies
the final norm after the last pass alone).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_window_moe import F32, HI, mm, rms_norm, rope


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``; a config the equations above are not is refused."""
    if cfg.get("early_exit_threshold", 1) < 1:
        raise ValueError(
            f"early_exit_threshold {cfg['early_exit_threshold']}: the "
            "reference computes every pass and reads the last one's logits")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the reference's attention is plain multi-head")
    if set(cfg.get("layer_types", ["full_attention"])) != {"full_attention"}:
        raise ValueError("the reference's layers all attend every key")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("the reference's rotation is unscaled")
    keys = ("num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def attention(a, pa, items, q_block: int):
    """Attention of one sequence ``a (S, D)`` (already normed), up to and
    with the output projection: causal, every key before the query and its
    own."""
    cfg = dict(items)
    s = a.shape[0]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    q = rope(mm(a, pa["q"]).reshape(s, h, dh), pos, cfg["rope_theta"])
    k = rope(mm(a, pa["k"]).reshape(s, h, dh), pos, cfg["rope_theta"])
    v = mm(a, pa["v"]).reshape(s, h, dh)
    outs = []
    for lo in range(0, s, q_block):
        qb = q[lo: lo + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        seen = pos[lo: lo + q_block, None] >= pos[None, :]
        probs = jax.nn.softmax(
            jnp.where(seen, scores / math.sqrt(dh), -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HI))
    return mm(jnp.concatenate(outs).reshape(s, h * dh), pa["o"])


@partial(jax.jit, static_argnums=(2, 3))
def _layer(x, pl, items, q_block):
    """One layer over ``x (S, D)``."""
    eps = dict(items)["rms_norm_eps"]
    a = rms_norm(x, pl["in_norm"], eps)
    x = x + rms_norm(attention(a, pl["attn"], items, q_block),
                     pl["post_attn_norm"], eps)
    m = rms_norm(x, pl["pre_mlp_norm"], eps)
    pm = pl["mlp"]
    f = mm(jax.nn.silu(mm(m, pm["gate"])) * mm(m, pm["up"]), pm["down"])
    return x + rms_norm(f, pl["post_mlp_norm"], eps)


@partial(jax.jit, static_argnums=(3, 4))
def _close(x, norm_f, gate, eps, normed):
    """The end of a pass: ``(the stream as the next pass takes it, lam)``."""
    if normed:
        x = rms_norm(x, norm_f, eps)
    return x, jax.nn.sigmoid(
        jnp.sum(x * gate["w"], axis=-1) + gate["b"])


def hidden_states(params, tokens, cfg: dict, q_block: int = 512):
    """``(hidden (T, S, D), lam (T, S))`` of one sequence: the stream as
    each pass closes it, the last pass's being what the head reads, and each
    pass's gate. A pass at a time, a layer at a time."""
    items = _cfg_items(cfg)
    passes, n_layer = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    between = cfg.get("norm_between_passes", True)
    x = params["embed"][tokens].astype(F32)
    hidden, lams = [], []
    for t in range(passes):
        for i in range(n_layer):
            x = _layer(x, params[f"layer_{i}"], items, q_block)
        x, lam = _close(x, params["norm_f"], params["exit"],
                        cfg["rms_norm_eps"], between or t == passes - 1)
        hidden.append(x)
        lams.append(lam)
    return jnp.stack(hidden), jnp.stack(lams)


def exit_mass(lams):
    """``p (T, ...)`` from the passes' gates ``lams (T, ...)``: ``p_t =
    lam_t prod_{j<t} (1 - lam_j)``, the last taking what is left."""
    p, left = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def forward(params, tokens, cfg: dict, q_block: int = 512):
    """``(logits (S, V), hidden (T, S, D), lam (T, S))`` of one sequence
    (small sizes, tests)."""
    hidden, lams = hidden_states(params, tokens, cfg, q_block)
    return mm(hidden[-1], params["head"]), hidden, lams


@jax.jit
def _gaps_at(head, x, positions, served):
    """``x (S, D)`` the last pass's closed stream; ``positions``/``served``
    ``(N,)``: where each served token was predicted and which it was.
    Returns (gap, the reference's own choice) ``(N,)``."""
    logits = mm(x[positions], head)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got, jnp.argmax(logits, axis=-1)


def _padded(n: int, lengths, pad_multiple: int) -> int:
    """The shortest of ``lengths`` that holds ``n`` positions (``n`` itself
    if none does), in whole multiples of ``pad_multiple``."""
    fits = [m for m in sorted(lengths) if m >= n] or [n]
    return -(-fits[0] // pad_multiple) * pad_multiple


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      pad_multiple: int = 32, lengths=(),
                      most_outputs: int = 0, q_block: int = 512):
    """For each request, teacher-force ``prompt + served tokens`` through
    the reference and return one array per request of ``best logit - served
    token's logit`` at every served position (0 where the served token is
    the reference's own choice), plus the share of served tokens that are
    the reference's choice. Served token ``j`` of a prompt of ``s`` tokens is
    predicted at position ``s - 1 + j`` from ``prompt + outputs[:j]``. A
    sequence is padded on the right (inert under the causal mask) to the
    shortest of ``lengths`` that holds it, so that every run of a cell uses
    the same few compiled programs."""
    n_out = max([most_outputs] + [len(o) for o in outputs])
    gaps, agree, total = [], 0, 0
    for prompt, output in zip(prompts, outputs):
        seq = list(prompt) + list(output[:-1])
        tokens = np.zeros((_padded(len(seq), lengths, pad_multiple),),
                          np.int32)
        tokens[: len(seq)] = seq
        k = len(output)
        positions = np.zeros((n_out,), np.int32)
        served = np.zeros((n_out,), np.int32)
        positions[:k] = len(prompt) - 1 + np.arange(k)
        served[:k] = output
        hidden, _ = hidden_states(params, jnp.asarray(tokens), cfg, q_block)
        gap, best = _gaps_at(params["head"], hidden[-1],
                             jnp.asarray(positions), jnp.asarray(served))
        gap, best = np.asarray(gap), np.asarray(best)
        gaps.append(gap[:k].astype(np.float64))
        agree += int(np.sum(best[:k] == served[:k]))
        total += k
    return gaps, agree / max(total, 1)


def compile_ahead(params, cfg: dict, lengths, pad_multiple: int = 32,
                  most_outputs: int = 0, q_block: int = 512) -> None:
    """Lower and compile every jitted piece :func:`served_token_gaps` will
    call for sequences padded to ``lengths``, from shapes alone (``params``
    may be a tree of ``jax.ShapeDtypeStruct``): the compiles of a cold run's
    last minute, which the benchmark's driver has a thread do beside its
    set-up. The pieces hold no kernel, so the compile cache hands them back
    whatever frames they were traced under. The layers share one program a
    length, and so do the passes."""
    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    p = jax.tree.map(shape, params)
    items = _cfg_items(cfg)
    d = cfg["hidden_size"]
    for n in sorted({_padded(n, (), pad_multiple) for n in lengths}):
        x = jax.ShapeDtypeStruct((n, d), F32)
        _layer.lower(x, p["layer_0"], items, q_block).compile()
        _close.lower(x, p["norm_f"], p["exit"], cfg["rms_norm_eps"],
                     True).compile()
        at = jax.ShapeDtypeStruct((most_outputs,), jnp.int32)
        _gaps_at.lower(p["head"], x, at, at).compile()
