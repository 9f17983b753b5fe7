"""Plain float32 latent-attention (MLA) decoder with routed experts, YaRN
positions and manifold-constrained hyper-connections: the reference the
``xing4.0-29b-a4b-serve-kv8`` configuration's ``correct`` is held to.

Straightforward ``jax.numpy`` from the published ``config.json`` keys,
float32 throughout, every matrix product at ``Precision.HIGHEST``. No cache
(every key and value of every head is rebuilt from the latent), no kernels,
no batching: one sequence, layer by layer. Nothing is imported from the
program under test; the matrix product, the norm, the SwiGLU, the router's
combine weights and the comparison of logits are ``reference_mla_moe``'s
(same mathematics, JoyAI's file). It is given the seeded weights the
benchmark made (bfloat16) and upcasts them as it goes, a block of experts at
a time; queries go in blocks too, so that no ``(H, S, S)`` tensor is held.

**Hyper-connections** (``hc_mult = n`` streams; DeepSeek's "mHC" over
Hyper-Connections, arXiv:2409.19606). ``X_0[i] = e`` for every stream.
Every sublayer ``F`` with its RMSNorm (attention, FFN: two a layer) has its
own ``phi (nD, 2n + n^2)``, ``alpha (3,)``, ``base (2n + n^2,)``::

    x^     = vec(X)                                   (nD,)
    m      = (x^ phi) / sqrt(mean(x^^2) + rms_norm_eps)
    H_pre  = sigmoid(alpha_0 m[0:n]  + base[0:n]) + hc_eps
    H_post = 2 sigmoid(alpha_1 m[n:2n] + base[n:2n])
    A      = clip(alpha_2 mat(m[2n:]) + base[2n:], clamp_min, clamp_max)
    M      = softmax of each row of A
    hc_sinkhorn_iters times:  M <- M / (rows' sums + hc_eps), then
                              M <- M / (columns' sums + hc_eps)
    u      = sum_i H_pre[i] X[i]
    y      = F(RMSNorm_w(u))
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

(:func:`sinkhorn` is the loop over ``(n, n)`` matrices.) In front of the
final norm, ``x = sum_i (sigmoid(alpha_h m_h + base_h) + hc_eps)[i] X[i]``
with ``m_h`` from a fourth set ``phi_h (nD, n)``.

**YaRN** as the DeepSeek-V3 family computes it: pair ``i`` of the ``d / 2``
turns by ``position * inv_i``, ``inv_i = theta^(-2i/d)`` blended with
``inv_i / factor`` by a linear ramp between the correction dims of
``beta_fast`` and ``beta_slow`` turns over ``original_max_position_
embeddings``; cosines and sines times ``mscale(factor, mscale) /
mscale(factor, mscale_all_dim)``, the softmax scale times ``mscale(factor,
mscale_all_dim)^2``, ``mscale(f, m) = 0.1 m ln f + 1``.

**Departures from the published description**, each a convention on seeded
weights that the config does not fix (the file's ``assumed`` lists them):
``X'[i]`` sums ``H_res[i, j] X[j]`` over ``j`` (row ``i`` of ``H_res`` makes
stream ``i``); a Sinkhorn iteration norms rows, then columns, ``hc_eps``
added to each sum; the read-out is the ``pre`` half of a hyper-connection;
rotary pairs are interleaved. The experts are a loop, each applied to the
tokens that chose it, gathered into ``S / expert_rows_share`` rows (a block
in which an expert was chosen by more is computed again over ``S`` rows).
The multi-token prediction module is left out (``num_nextn_predict_layers``
-> 0 under ``reduced``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_mla_moe import (
    F32, HI, _cfg_items as _moe_items, _dense_jit, _gaps_at, _moe_head, mm,
    rms_norm, swiglu,
)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(d: int, theta: float, scaling) -> np.ndarray:
    """The ``d / 2`` pairs' angles a position, float64; ``scaling`` is the
    published ``rope_scaling`` (None: plain)."""
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if scaling is None:
        return inv
    positions = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return (d * math.log(positions / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return inv / scaling["factor"] * ramp + inv * (1.0 - ramp)


def rope(x, positions, theta, scaling):
    """``x (S, [H,] d)``: each pair ``(x[2i], x[2i+1])`` turned by the
    angle ``position * frequency_i``, times YaRN's rotation scale."""
    inv = jnp.asarray(yarn_frequencies(x.shape[-1], theta, scaling), F32)
    ang = positions.astype(F32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    scale = 1.0
    if scaling is not None:
        scale = (yarn_mscale(scaling["factor"], scaling["mscale"])
                 / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(cfg: dict) -> float:
    scaling = cfg.get("rope_scaling")
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    if scaling is not None:
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def attention(x, pa, cfg: dict, q_block: int):
    """Causal attention of one sequence ``x (S, D)`` (already normed)."""
    s = x.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta, scaling = cfg["rope_theta"], cfg.get("rope_scaling")
    pos = jnp.arange(s)
    q = mm(rms_norm(mm(x, pa["q_a"]), pa["q_a_norm"], eps), pa["q_b"])
    q = q.reshape(s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, theta, scaling)], axis=-1)
    kv = mm(x, pa["kv_a"])
    c = rms_norm(kv[:, :rkv], pa["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], pos, theta, scaling)
    kv_h = mm(c, pa["kv_b"]).reshape(s, h, dn + dv)
    k = jnp.concatenate(
        [kv_h[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, h, dr))],
        axis=-1)
    v = kv_h[..., dn:]
    # Whole blocks of queries: the last one's rows past ``S`` are dropped.
    q = jnp.pad(q, ((0, -s % q_block), (0, 0), (0, 0)))

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, q_block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        scores = scores * softmax_scale(cfg)
        causal = pos[None, :] <= lo + jnp.arange(q_block)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)

    # A block of queries at a time, one after the other.
    outs = jax.lax.map(block, jnp.arange(0, s, q_block))
    return mm(outs.reshape(-1, h * dv)[:s], pa["o"])


def sinkhorn(logits, iters: int, eps: float):
    """``logits (..., n, n)`` -> the rows' softmax, then ``iters`` times the
    rows normed and the columns normed."""
    def iteration(_, mat):
        mat = mat / (jnp.sum(mat, axis=-1, keepdims=True) + eps)
        return mat / (jnp.sum(mat, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, iteration,
                             jax.nn.softmax(logits, axis=-1))


def hc_mixes(streams, hc, cfg: dict):
    """``streams (S, n, D)`` -> ``(H_pre (S, n), H_post (S, n), H_res (S, n,
    n))``; the last two None for a set without them (the read-out's)."""
    s, n, _ = streams.shape
    flat = streams.reshape(s, -1)
    m = mm(flat, hc["phi"]) / jnp.sqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    alpha, base, eps = hc["alpha"], hc["base"], cfg["hc_eps"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + base[:n]) + eps
    if m.shape[1] == n:
        return h_pre, None, None
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + base[n:2 * n])
    logits = jnp.clip(
        (alpha[2] * m[:, 2 * n:] + base[2 * n:]).reshape(s, n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(logits, cfg["hc_sinkhorn_iters"], eps)


def hc_read(streams, hc, cfg: dict):
    """``(u (S, D), H_post, H_res)``: what the sublayer reads and the mixes
    of the way back."""
    h_pre, h_post, h_res = hc_mixes(streams, hc, cfg)
    return jnp.einsum("si,sid->sd", h_pre, streams, precision=HI), h_post, h_res


def hc_write(streams, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``."""
    return (jnp.einsum("sij,sjd->sid", h_res, streams, precision=HI)
            + h_post[:, :, None] * y[:, None, :])


def _hc_items(cfg: dict) -> tuple:
    keys = ("rms_norm_eps", "hc_eps", "hc_sinkhorn_iters",
            "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
    return tuple((k, cfg[k]) for k in keys)


def _attn_items(cfg: dict) -> tuple:
    """The numbers the attention half computes with, hashable for ``jit``
    (``rope_scaling`` as its items)."""
    scaling = cfg.get("rope_scaling")
    return _moe_items(cfg) + _hc_items(cfg) + (
        ("rope_scaling", None if scaling is None
         else tuple(sorted(scaling.items()))),)


def _as_cfg(items: tuple) -> dict:
    cfg = dict(items)
    if cfg.get("rope_scaling") is not None:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _attn_half(streams, pl, items, q_block):
    """The attention sublayer, then what the FFN sublayer reads: ``(X', y
    normed, H_post, H_res)``."""
    cfg = _as_cfg(items)
    eps = cfg["rms_norm_eps"]
    u, h_post, h_res = hc_read(streams, pl["hc_attn"], cfg)
    out = attention(rms_norm(u, pl["attn_norm"], eps), pl["attn"], cfg,
                    q_block)
    streams = hc_write(streams, out, h_post, h_res)
    u, h_post, h_res = hc_read(streams, pl["hc_ffn"], cfg)
    return streams, rms_norm(u, pl["ffn_norm"], eps), h_post, h_res


_hc_write_jit = jax.jit(hc_write, donate_argnums=(0,))


@partial(jax.jit, static_argnums=(2,))
def _read_out(streams, hc, items):
    """The one stream the final norm takes."""
    return hc_read(streams, hc, dict(items))[0]


@partial(jax.jit, static_argnums=(2,))
def _spread(embed, tokens, n):
    """The embedding repeated into ``n`` streams, float32."""
    x = embed[tokens].astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))


@partial(jax.jit, static_argnums=(5,))
def _experts_block(y, w, gate, up, down, rows):
    """``sum_e w[:, e] * E_e(y)`` over one block of experts, one at a time,
    each over the tokens that chose it, ``rows`` of them at most; beside it
    the most tokens that chose one expert (a block in which that is more
    than ``rows`` left some out)."""
    def one(acc, xs):
        w_e, g, u, d = xs
        chose = jnp.sum(w_e > 0)
        (at,) = jnp.nonzero(w_e > 0, size=rows, fill_value=0)
        weight = jnp.where(jnp.arange(rows) < chose, w_e[at], 0.0)
        out = weight[:, None] * swiglu(y[at], {"gate": g, "up": u, "down": d})
        return acc.at[at].add(out), chose

    acc, chose = jax.lax.scan(one, jnp.zeros_like(y),
                              (w.T, gate, up, down))
    return acc, jnp.max(chose)


def _experts(y, w, pm, n_experts: int, expert_block: int, rows: int):
    out = jnp.zeros_like(y)
    for lo in range(0, n_experts, expert_block):
        hi = lo + expert_block
        block = (y, w[:, lo:hi], pm["gate"][lo:hi], pm["up"][lo:hi],
                 pm["down"][lo:hi])
        part, most = _experts_block(*block, rows)
        if int(most) > rows:  # an expert more chosen than reckoned with
            part, _ = _experts_block(*block, y.shape[0])
        out = out + part
    return out


def hidden_states(params, tokens, cfg: dict, q_block: int = 512,
                  expert_block: int = 32, expert_rows_share: int = 1):
    """What the final norm takes, ``(S, D)``, of one sequence, layer by
    layer; the expert layers go one jitted block of experts at a time, an
    expert over ``S / expert_rows_share`` tokens."""
    items = _attn_items(cfg)
    streams = _spread(params["embed"], tokens, cfg["hc_mult"])
    rows = -(-tokens.shape[0] // expert_rows_share)
    for i in range(cfg["num_hidden_layers"]):
        pl = params[f"layer_{i}"]
        streams, y, h_post, h_res = _attn_half(
            streams, {k: pl[k] for k in ("attn_norm", "ffn_norm", "attn",
                                         "hc_attn", "hc_ffn")},
            items, q_block)
        if "mlp" in pl:
            out = _dense_jit(y, pl["mlp"])
        else:
            pm = pl["moe"]
            w, out = _moe_head(y, {k: pm[k] for k in
                                   ("router", "bias", "shared")},
                               _moe_items(cfg))
            out = out + _experts(y, w, pm, cfg["n_routed_experts"],
                                 expert_block, rows)
        streams = _hc_write_jit(streams, out, h_post, h_res)
    return _read_out(streams, params["hc_head"], _hc_items(cfg))


def forward(params, tokens, cfg: dict, q_block: int = 512,
            expert_block: int = 32, expert_rows_share: int = 1):
    """Logits ``(S, V)`` of one sequence (small sizes, tests)."""
    x = hidden_states(params, tokens, cfg, q_block, expert_block,
                      expert_rows_share)
    return mm(rms_norm(x, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"])


def compile_ahead(params, cfg: dict, lengths, pad_multiple: int = 256,
                  most_outputs: int = 0, q_block: int = 512,
                  expert_block: int = 32, expert_rows_share: int = 1) -> None:
    """Lower and compile every jitted piece :func:`served_token_gaps` will
    call for sequences padded to ``lengths``, from shapes alone (``params``
    may be a tree of ``jax.ShapeDtypeStruct``): the compiles of a cold run's
    last minute, which the benchmark's driver has a thread do beside its
    set-up. The pieces hold no kernel, so the compile cache hands them back
    whatever frames they were traced under. Layers of one kind share their
    programs; the slices and sums between the pieces are left to the run."""
    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def aot(fn, *args):
        fn.lower(*args).compile()
        return jax.eval_shape(fn, *args)

    p = jax.tree.map(shape, params)
    items = _attn_items(cfg)
    for n in sorted({-(-n // pad_multiple) * pad_multiple for n in lengths}):
        streams = aot(_spread, p["embed"],
                      jax.ShapeDtypeStruct((n,), jnp.int32), cfg["hc_mult"])
        kinds = set()
        for i in range(cfg["num_hidden_layers"]):
            pl = p[f"layer_{i}"]
            if ("mlp" in pl) in kinds:
                continue
            kinds.add("mlp" in pl)
            streams, y, h_post, h_res = aot(
                _attn_half, streams,
                {k: pl[k] for k in ("attn_norm", "ffn_norm", "attn",
                                    "hc_attn", "hc_ffn")}, items, q_block)
            if "mlp" in pl:
                out = aot(_dense_jit, y, pl["mlp"])
            else:
                pm = pl["moe"]
                w, out = aot(_moe_head, y, {k: pm[k] for k in
                                            ("router", "bias", "shared")},
                             _moe_items(cfg))
                block = [jax.ShapeDtypeStruct((expert_block,) + a.shape[1:],
                                              a.dtype)
                         for a in (pm["gate"], pm["up"], pm["down"])]
                aot(_experts_block, y,
                    jax.ShapeDtypeStruct((n, expert_block), w.dtype), *block,
                    -(-n // expert_rows_share))
            streams = aot(_hc_write_jit, streams, out, h_post, h_res)
        x = aot(_read_out, streams, p["hc_head"], _hc_items(cfg))
        at = jax.ShapeDtypeStruct((most_outputs,), jnp.int32)
        aot(_gaps_at, p["norm_f"], p["head"], x, at, at, cfg["rms_norm_eps"])


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      pad_multiple: int = 256, lengths=(),
                      most_outputs: int = 0, q_block: int = 512,
                      expert_block: int = 32, expert_rows_share: int = 1):
    """For each request, teacher-force ``prompt + served tokens`` through
    the reference and return one array per request of ``best logit - served
    token's logit`` at every served position (0 where the served token is
    the reference's own choice), plus the share of served tokens that are
    the reference's choice. Served token ``j`` of a prompt of ``s`` tokens
    is predicted at position ``s - 1 + j`` from ``prompt + outputs[:j]``.
    A sequence is padded on the right (inert under the causal mask) to the
    shortest of ``lengths`` (the longest sequence of each of the mix's
    prompt groups) that holds it and its answer to ``most_outputs``, so that
    every run of a cell uses the same few compiled programs, and an 8k
    request does not cost a 16k one's work."""
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
                          expert_block, expert_rows_share)
        gap, best = _gaps_at(params["norm_f"], params["head"], x,
                             jnp.asarray(positions), jnp.asarray(served),
                             cfg["rms_norm_eps"])
        gap, best = np.asarray(gap), np.asarray(best)
        gaps.append(gap[:k].astype(np.float64))
        agree += int(np.sum(best[:k] == served[:k]))
        total += k
    return gaps, agree / max(total, 1)
