"""Plain float32 grouped-query decoder with QK-norm and routed experts under
the block mask, and the replay of block diffusion's denoising steps: the
reference the ``sdar-30b-a3b-serve-kv8`` configuration's ``correct`` is held
to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys,
float32 throughout, every matrix product at ``Precision.HIGHEST`` (callers
on the chip also run it under ``jax.default_matmul_precision("highest")``).
Whole sequences: no kernels, no pages, no cache kept between calls, no
batching of requests, no sorting of tokens. The experts are a plain loop,
each applied to every token and weighted by the token's combine weight for it
(zero where the router did not choose it). Nothing is imported from the
program under test. It is given the seeded weights the benchmark made
(bfloat16) and upcasts them as it goes, a block of experts at a time; queries
go in blocks too, so that no ``(H, S, S)`` tensor is held at 1,280 positions.

Block ``l``, with ``head_dim`` 128, 32 query heads over 4 K/V heads (query
head ``h`` reads K/V head ``h // 8``) and no bias anywhere::

    a  = RMSNorm(x; in_norm)                       eps = rms_norm_eps
    q, k, v = a W_q, a W_k, a W_v
    q = RMSNorm(q; q_norm), k = RMSNorm(k; k_norm)   over a head's head_dim
    q, k turned at the token's position, all of head_dim, theta = rope_theta,
        pairs (i, i + head_dim / 2)
    s_ij = q_i . k_j / sqrt(head_dim)
    seen: j // L <= i // L                         L = block_length
    x1 = x + softmax(s) v W_o
    h  = RMSNorm(x1; post_norm)
    r  = softmax(h W_r) over all experts; idx = the num_experts_per_tok
        largest; w = r[idx] / sum(r[idx])          norm_topk_prob
    x2 = x1 + sum_i w_i W_down,idx_i (silu(W_gate,idx_i h) * W_up,idx_i h)

then the final ``RMSNorm`` and the untied head; position ``i``'s logits
predict position ``i``'s token (no shift).

Generation is block diffusion (the configuration's ``assumed`` has the
procedure): a block starts as its known tokens and ``mask_token_id``
elsewhere and is run, over everything before it in its final form, until no
mask is left. :func:`replay` rebuilds a block as the server saw it at a
denoising step from the request's tokens and each token's unmask step, and
returns that step's logits.

Departures from the published code, each where the ``config.json`` holds no
key (the configuration's ``assumed`` lists them): QK-norm before the rotation
with one gain vector for all heads; half-split rotary pairs; the block
length, the mask id and the schedule. Departures from a literal replay: the
steps of one request are computed together, as rows that read the keys and
values of the one full forward of the request's final tokens (under the
block mask a final block's keys and values depend on nothing after it, so
they are what a forward of the prefix alone would give); nothing is kept
from one call to the next. A last block the request's length cut short is
not replayed: the tokens the server discarded, and their unmask steps, are
not in the request.

``in_block`` (``"both_ways"``; ``"causal"`` lets a position see no later
position of its own block) is read from the configuration where a test
states it, to show that the comparison tells the two masks apart.
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
    """``x (..., S, H, d)`` at ``positions (..., S)``: each pair ``(x[i],
    x[i + d/2])`` turned by the angle ``position * theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), F32)
    ang = (positions.astype(F32)[..., None] * inv)[..., None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def project(a, pa, cfg: dict, positions):
    """``a (..., S, D)`` (already normed) -> ``q (..., S, H, dh)``, ``k``,
    ``v (..., S, Hk, dh)``: ``q`` and ``k`` normed a head, then turned."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    lead = a.shape[:-1]
    q = rms_norm(mm(a, pa["q"]).reshape(*lead, h, dh), pa["q_norm"], eps)
    k = rms_norm(mm(a, pa["k"]).reshape(*lead, hk, dh), pa["k_norm"], eps)
    v = mm(a, pa["v"]).reshape(*lead, hk, dh)
    return rope(q, positions, theta), rope(k, positions, theta), v


def _seen_in_block(i, j, cfg: dict):
    """Whether key ``j`` of query ``i``'s own block is visible."""
    return jnp.ones(jnp.broadcast_shapes(i.shape, j.shape), bool) if (
        cfg["in_block"] == "both_ways") else j <= i


def attention(a, pa, cfg: dict, q_block: int):
    """Attention of one sequence ``a (S, D)`` (already normed) under the
    block mask, in blocks of queries. Returns ``(out (S, D), k, v (S, Hk,
    dh))``."""
    s = a.shape[0]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = cfg["block_length"]
    pos = jnp.arange(s)
    q, k, v = project(a, pa, cfg, pos)
    kh = jnp.repeat(k, h // hk, axis=1)  # query head h reads head h // (H/Hk)
    vh = jnp.repeat(v, h // hk, axis=1)
    outs = []
    for lo in range(0, s, q_block):
        qb = q[lo: lo + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, kh, precision=HI)
        scores = scores / math.sqrt(dh)
        i, j = pos[lo: lo + q_block, None], pos[None, :]
        seen = (j // n < i // n) | ((j // n == i // n)
                                    & _seen_in_block(i, j, cfg))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, vh, precision=HI))
    return mm(jnp.concatenate(outs).reshape(s, h * dh), pa["o"]), k, v


def combine_weights(r, cfg: dict):
    """``(T, E)`` from the router's logits ``r``: the softmax over all
    experts, kept at the ``num_experts_per_tok`` largest and divided by
    their sum, zero elsewhere."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(r, axis=-1)
    chosen = jnp.argsort(-r, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    w = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], chosen].set(w)


@jax.jit
def _experts_block(z, w, gate, up, down):
    """``sum_e w[:, e] * E_e(z)`` over one block of experts, one at a
    time."""
    def one(acc, xs):
        w_e, g, u, d = xs
        return acc + w_e[:, None] * mm(jax.nn.silu(mm(z, g)) * mm(z, u),
                                       d), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(z), (w.T, gate, up, down))
    return acc


def _experts(h, w, pm, expert_block: int):
    out = jnp.zeros_like(h)
    for lo in range(0, pm["gate"].shape[0], expert_block):
        hi = lo + expert_block
        out = out + _experts_block(h, w[:, lo:hi], pm["gate"][lo:hi],
                                   pm["up"][lo:hi], pm["down"][lo:hi])
    return out


def _tail_half(x1, pl, cfg: dict):
    h = rms_norm(x1, pl["post_norm"], cfg["rms_norm_eps"])
    return h, combine_weights(mm(h, pl["router"]), cfg)


@partial(jax.jit, static_argnums=(2, 3))
def _seq_half(x, pl, cfg_items, q_block):
    """``(x1, h, the combine weights, k, v)`` of one block over a whole
    sequence ``x (S, D)``."""
    cfg = dict(cfg_items)
    a = rms_norm(x, pl["in_norm"], cfg["rms_norm_eps"])
    o, k, v = attention(a, pl["attn"], cfg, q_block)
    x1 = x + o
    return (x1, *_tail_half(x1, pl, cfg), k, v)


@partial(jax.jit, static_argnums=(6,))
def _rows_half(x, pl, k_seq, v_seq, positions, start, cfg_items):
    """The same for replayed blocks ``x (R, L, D)`` at ``positions (R,
    L)``: a block reads the sequence's ``k_seq``, ``v_seq (S, Hk, dh)``
    before ``start (R,)`` and its own ``L`` keys. ``(x1, h, w)`` with the
    rows flat, ``(R * L, ...)``."""
    cfg = dict(cfg_items)
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    r, n, d = x.shape
    a = rms_norm(x, pl["in_norm"], cfg["rms_norm_eps"])
    q, k, v = project(a, pl["attn"], cfg, positions)
    g = h // hk
    past = jnp.einsum("rlhd,shd->rhls", q, jnp.repeat(k_seq, g, axis=1),
                      precision=HI)
    own = jnp.einsum("rlhd,rmhd->rhlm", q, jnp.repeat(k, g, axis=2),
                     precision=HI)
    before = jnp.arange(k_seq.shape[0])[None, :] < start[:, None]  # (R, S)
    at = jnp.arange(n)
    scores = jnp.concatenate([
        jnp.where(before[:, None, None, :], past, -jnp.inf),
        jnp.where(_seen_in_block(at[:, None], at[None, :], cfg), own,
                  -jnp.inf),
    ], axis=-1) / math.sqrt(dh)
    probs = jax.nn.softmax(scores, axis=-1)
    s = k_seq.shape[0]
    o = (jnp.einsum("rhls,shd->rlhd", probs[..., :s],
                    jnp.repeat(v_seq, g, axis=1), precision=HI)
         + jnp.einsum("rhlm,rmhd->rlhd", probs[..., s:],
                      jnp.repeat(v, g, axis=2), precision=HI))
    x1 = (x + mm(o.reshape(r, n, h * dh), pl["attn"]["o"])).reshape(r * n, d)
    return (x1, *_tail_half(x1, pl, cfg))


def _cfg_items(cfg: dict) -> tuple:
    """The numbers of the configuration the reference computes with, as a
    hashable for ``jit``; a configuration this block is not is refused."""
    for key, want in (("use_sliding_window", False), ("rope_scaling", None),
                      ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                      ("tie_word_embeddings", False),
                      ("norm_topk_prob", True)):
        if cfg.get(key, want) != want:
            raise ValueError(f"reference: {key} = {cfg[key]!r}")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "block_length")
    return tuple((k, cfg[k]) for k in keys) + (
        ("in_block", cfg.get("in_block", "both_ways")),)


def _layer_params(pl):
    return {"in_norm": pl["in_norm"], "post_norm": pl["post_norm"],
            "attn": pl["attn"], "router": pl["moe"]["router"]}


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 32, rows=None):
    """Final hidden states ``(S, D)`` of one sequence under the block mask,
    layer by layer; the experts go one jitted block at a time. ``rows
    (tokens (R, L), positions (R, L), start (R,))``: blocks replayed beside
    it, each over the sequence's positions before its ``start`` and its own
    ``L``; their final hidden states ``(R, L, D)`` are returned too."""
    items = _cfg_items(cfg)
    x = params["embed"][tokens].astype(F32)
    if rows is not None:
        row_tokens, positions, start = rows
        y = params["embed"][row_tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        pl = params[f"layer_{i}"]
        x1, h, w, k, v = _seq_half(x, _layer_params(pl), items, q_block)
        x = x1 + _experts(h, w, pl["moe"], expert_block)
        if rows is not None:
            y1, h, w = _rows_half(y, _layer_params(pl), k, v, positions,
                                  start, items)
            y = (y1 + _experts(h, w, pl["moe"], expert_block)).reshape(
                y.shape)
    return x if rows is None else (x, y)


def forward(params, tokens, cfg: dict, q_block: int = 512,
            expert_block: int = 32):
    """Logits ``(S, V)`` of one sequence under the block mask (small sizes,
    tests): position ``i``'s row predicts position ``i``'s token."""
    x = hidden_states(params, tokens, cfg, q_block, expert_block)
    return mm(rms_norm(x, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"])


def whole_blocks(prompt, output, cfg: dict) -> int:
    """Blocks of a request's generation that are whole in ``output``: its
    first holds the ``len(prompt) % L`` prompt tokens past the prompt's
    last whole block, and a last one the request's length cut short is left
    out."""
    n = cfg["block_length"]
    return (len(prompt) + len(output)) // n - len(prompt) // n


def replay_rows(prompt, output, unmask_step, cfg: dict, blocks=None):
    """The denoising steps of a request's ``blocks`` (all whole ones where
    None) as the server saw them: ``(block, step)`` pairs, and for each a
    row of ``(tokens (L,)``: the block with the positions whose unmask step
    is under ``step`` known (a prompt token's is -1) and ``mask_token_id``
    elsewhere``, positions (L,), start)``. A block has a step for every
    unmask step its positions hold."""
    n, mask = cfg["block_length"], cfg["mask_token_id"]
    seq = np.asarray(list(prompt) + list(output), np.int64)
    when = np.concatenate([np.full((len(prompt),), -1, np.int64),
                           np.asarray(unmask_step, np.int64)])
    first = len(prompt) // n * n
    if blocks is None:
        blocks = range(whole_blocks(prompt, output, cfg))
    pairs, tokens, positions, starts = [], [], [], []
    for b in blocks:
        at = first + b * n + np.arange(n)
        for s in range(int(when[at].max()) + 1):
            pairs.append((int(b), s))
            tokens.append(np.where(when[at] < s, seq[at], mask))
            positions.append(at)
            starts.append(at[0])
    return pairs, (np.asarray(tokens, np.int32),
                   np.asarray(positions, np.int32),
                   np.asarray(starts, np.int32))


def final_tokens(prompt, output, cfg: dict, length: int = 0):
    """The request's final tokens up to its last whole block, zero-padded
    to ``length`` (later blocks: no real position sees them)."""
    n = cfg["block_length"]
    seq = (list(prompt) + list(output))
    seq = seq[: len(seq) // n * n]
    tokens = np.zeros((max(length, len(seq)),), np.int32)
    tokens[: len(seq)] = seq
    return tokens


def replay(params, prompt, output, unmask_step, cfg: dict, blocks=None,
           q_block: int = 512, expert_block: int = 32):
    """Every denoising step of a request's ``blocks`` through the
    reference (small sizes, tests): ``[(block, step, logits (L, V),
    confidence (L,))]``, the logits and the greedy token's softmax
    probability at the block's positions as the step's forward gives them,
    every earlier block in its final form."""
    pairs, rows = replay_rows(prompt, output, unmask_step, cfg, blocks)
    tokens = final_tokens(prompt, output, cfg)
    _, y = hidden_states(params, jnp.asarray(tokens), cfg, q_block,
                         expert_block, rows=tuple(map(jnp.asarray, rows)))
    logits = mm(rms_norm(y, params["norm_f"], cfg["rms_norm_eps"]),
                params["head"])
    conf = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
    logits, conf = np.asarray(logits), np.asarray(conf)
    return [(b, s, logits[i], conf[i]) for i, (b, s) in enumerate(pairs)]


@partial(jax.jit, static_argnums=(4,))
def _step_readings(norm_f, head, y, served, eps):
    """``y (R, L, D)`` final hidden states of replayed steps, ``served (R,
    L)`` the token the server holds at each position in the end. Returns,
    each ``(R, L)``: the best logit less the served token's, the log of the
    greedy token's softmax probability, and the reference's own choice."""
    def one(row):
        y_r, served_r = row
        logits = mm(rms_norm(y_r, norm_f, eps), head)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served_r[:, None], axis=-1)[:, 0]
        return (best - got,
                best - jax.nn.logsumexp(logits, axis=-1),
                jnp.argmax(logits, axis=-1))

    return jax.lax.map(one, (y, served))


def step_gaps(gap, logconf, when, step: int):
    """What one replayed step says of the server's unmasking, from the
    reference's readings at the block's positions (``gap``, ``logconf (L,)``
    of :func:`_step_readings`) and the positions' unmask steps ``when
    (L,)``. The server unmasked the ``k`` positions whose unmask step is
    ``step`` among those masked then (unmask step at least ``step``).
    Returns ``(served gaps (k,)``: by how much each of their tokens' logit
    lies below the reference's best there; ``unmask gaps (k,)``: by how much
    the reference's log-confidence there lies below its ``k``-th most
    confident masked position, 0 where the server's choice is among the
    reference's ``k`` most confident)``. Gaps, not positions: with seeded
    weights near-ties flip on rounding."""
    when = np.asarray(when)
    here, masked = when == step, when >= step
    kth = np.sort(np.asarray(logconf)[masked])[::-1][int(here.sum()) - 1]
    return (np.asarray(gap)[here],
            np.maximum(kth - np.asarray(logconf)[here], 0.0))


def replay_gaps(params, cfg: dict, prompt, output, unmask_step, blocks,
                length: int, rows_to: int, q_block: int = 512,
                expert_block: int = 32):
    """The benchmark's comparison for one request: every denoising step of
    ``blocks`` replayed (the sequence padded to ``length`` and the steps to
    ``rows_to`` rows, so that every request of a cell runs the same
    compiled pieces) and read by :func:`step_gaps`. Returns ``(served gaps,
    unmask gaps, how many served tokens are the reference's own choice)``,
    the first two one value a checked position."""
    pairs, rows = replay_rows(prompt, output, unmask_step, cfg, blocks)
    assert len(pairs) <= rows_to, (len(pairs), rows_to)
    pad = rows_to - len(pairs)
    tokens, positions, starts = (
        np.concatenate([a, np.repeat(a[:1], pad, axis=0)]) for a in rows)
    final = final_tokens(prompt, output, cfg, length)
    served = final[positions]
    _, y = hidden_states(params, jnp.asarray(final), cfg, q_block,
                         expert_block,
                         rows=tuple(map(jnp.asarray,
                                        (tokens, positions, starts))))
    gap, logconf, best = map(np.asarray, _step_readings(
        params["norm_f"], params["head"], y, jnp.asarray(served),
        cfg["rms_norm_eps"]))
    when = np.concatenate([np.full((len(prompt),), -1, np.int64),
                           np.asarray(unmask_step, np.int64)])
    served_gaps, unmask_gaps, agree = [], [], 0
    for i, (b, s) in enumerate(pairs):
        at = positions[i]
        g, u = step_gaps(gap[i], logconf[i], when[at], s)
        served_gaps.append(g)
        unmask_gaps.append(u)
        here = when[at] == s
        agree += int(np.sum(best[i][here] == served[i][here]))
    return (np.concatenate(served_gaps).astype(np.float64),
            np.concatenate(unmask_gaps).astype(np.float64), agree)


def compile_ahead(tree, cfg: dict, length: int, rows_to: int,
                  q_block: int = 512, expert_block: int = 32) -> None:
    """Lower and compile, from the parameter tree's shapes alone, the
    pieces :func:`replay_gaps` calls at ``length`` positions and ``rows_to``
    replayed steps (a driver runs this beside its own compiles)."""
    items = _cfg_items(cfg)
    n, d = cfg["block_length"], cfg["hidden_size"]
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    pl = tree["layer_0"]
    sds = jax.ShapeDtypeStruct
    x, y = sds((length, d), F32), sds((rows_to, n, d), F32)
    kv = sds((length, hk, dh), F32)
    at = sds((rows_to, n), jnp.int32)
    _seq_half.lower(x, _layer_params(pl), items, q_block).compile()
    _rows_half.lower(y, _layer_params(pl), kv, kv, at,
                     sds((rows_to,), jnp.int32), items).compile()
    block = {k: sds((expert_block,) + v.shape[1:], v.dtype)
             for k, v in pl["moe"].items() if k != "router"}
    for rows in (length, rows_to * n):
        _experts_block.lower(
            sds((rows, d), F32), sds((rows, expert_block), F32),
            block["gate"], block["up"], block["down"]).compile()
    _step_readings.lower(tree["norm_f"], tree["head"], y, at,
                         cfg["rms_norm_eps"]).compile()
