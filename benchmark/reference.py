"""Plain float32 GPT-2: the reference every cell's ``correct`` is held to.

Straightforward ``jax.numpy``, float32 throughout, every matrix product at
``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs in bf16
passes). No kernels, no cache, no batching tricks, and nothing imported
from the program under test: it is given the seeded weights and tokens the
benchmark made and returns numbers.

It follows the published GPT-2 (pre-LayerNorm blocks, fused qkv, tanh GELU
``gelu_new``, learned positions, tied output head). One departure, which
is the program's and which the configuration files state under ``as_run``:
LayerNorm's epsilon is the program's 1e-6 where the published config, kept
in the file, says 1e-5 (:func:`epsilon`).

Two users:

* serving -- :func:`served_token_gaps` runs the model once over each
  sampled request's prompt and served tokens and reports, for every served
  token, how far its logit lies below the reference's best;
* training -- :func:`make_train_reference` follows the first optimizer
  steps (loss, first gradient, parameter change) on the same batches, in
  blocks of rows so that it fits beside nothing else.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def epsilon(cfg: dict) -> float:
    """LayerNorm's epsilon as the program runs it: the configuration's
    ``as_run`` value where it states one, else the published one."""
    return cfg.get("as_run", {}).get("layer_norm_epsilon",
                                     cfg["layer_norm_epsilon"])


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def dense(x, p):
    return jnp.matmul(x, p["kernel"], precision=HI) + p["bias"]


def block(x, lp, n_head: int, eps: float):
    """One pre-LN transformer block over ``x (B, S, D)``, causal."""
    b, s, d = x.shape
    dh = d // n_head
    y = layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
    qkv = dense(y, lp["attn"]["attn_qkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HI)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + dense(o, lp["attn"]["attn_proj"])
    y = layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps)
    h = gelu_new(dense(y, lp["mlp"]["mlp_in"]))
    return x + dense(h, lp["mlp"]["mlp_out"])


def embed(params, tokens):
    s = tokens.shape[1]
    return params["wte"]["embedding"][tokens] + params["wpe"]["embedding"][:s]


def head(params, x, eps):
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    return jnp.matmul(x, params["wte"]["embedding"].T, precision=HI)


def forward(params, tokens, cfg: dict):
    """Logits ``(B, S, vocab)`` of the whole model (small sizes, tests)."""
    x = embed(params, tokens)
    for i in range(cfg["n_layer"]):
        x = block(x, params[f"h_{i}"], cfg["n_head"], epsilon(cfg))
    return head(params, x, epsilon(cfg))


def lm_loss(logits, tokens):
    """Mean next-token cross entropy."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# ---------------------------------------------------------------------------
# Serving: how far below the reference's best does each served token lie?
# ---------------------------------------------------------------------------

_embed_jit = jax.jit(embed)
_block_jit = jax.jit(block, static_argnums=(2, 3))


@partial(jax.jit, static_argnums=(4,))
def _gaps_at(params, x, positions, served, eps):
    """``x (B, S, D)`` final hidden states; ``positions``/``served``
    ``(B, N)``: where each served token was predicted and which it was.
    Returns (gap, best) ``(B, N)``."""
    picked = jnp.take_along_axis(x, positions[..., None], axis=1)
    logits = head(params, picked, eps)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return best - got, jnp.argmax(logits, axis=-1)


def served_token_gaps(params, cfg: dict, prompts, outputs,
                      block_rows: int = 4, pad_multiple: int = 128,
                      longest: int = 0, most_outputs: int = 0):
    """For each request, teacher-force ``prompt + served tokens`` through
    the reference, layer by layer, and return one array per request of
    ``best logit - served token's logit`` at every served position (0 where
    the served token is the reference's own choice), plus the share of
    served tokens that are the reference's choice.

    Served token ``j`` of a prompt of ``s`` tokens is predicted at position
    ``s - 1 + j`` from ``prompt + outputs[:j]``. Rows are padded on the
    right (inert under the causal mask) and run ``block_rows`` at a time so
    that attention's ``(B, H, S, S)`` float32 scores fit. ``longest`` and
    ``most_outputs`` (the mix's longest sequence and answer) fix the padded
    shapes, so that every run of a cell uses the same compiled programs."""
    eps = epsilon(cfg)
    n = len(prompts)
    longest = max([longest] + [len(p) + len(o) - 1
                               for p, o in zip(prompts, outputs)])
    s_pad = -(-longest // pad_multiple) * pad_multiple
    n_out = max([most_outputs] + [len(o) for o in outputs])
    gaps, agree, total = [], 0, 0
    for lo in range(0, n, block_rows):
        rows = list(range(lo, min(lo + block_rows, n)))
        tokens = np.zeros((block_rows, s_pad), np.int32)
        positions = np.zeros((block_rows, n_out), np.int32)
        served = np.zeros((block_rows, n_out), np.int32)
        for r, i in enumerate(rows):
            seq = list(prompts[i]) + list(outputs[i][:-1])
            tokens[r, : len(seq)] = seq
            k = len(outputs[i])
            positions[r, :k] = len(prompts[i]) - 1 + np.arange(k)
            served[r, :k] = outputs[i]
        x = _embed_jit(params, jnp.asarray(tokens))
        for layer in range(cfg["n_layer"]):
            x = _block_jit(x, params[f"h_{layer}"], cfg["n_head"], eps)
        gap, best = _gaps_at(params, x, jnp.asarray(positions),
                             jnp.asarray(served), eps)
        gap, best = np.asarray(gap), np.asarray(best)
        for r, i in enumerate(rows):
            k = len(outputs[i])
            gaps.append(gap[r, :k].astype(np.float64))
            agree += int(np.sum(best[r, :k] == served[r, :k]))
            total += k
    return gaps, agree / max(total, 1)


# ---------------------------------------------------------------------------
# Training: the first optimizer steps, in float32, in blocks of rows.
# ---------------------------------------------------------------------------

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def leaf_names(tree):
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    """Euclidean norm of every leaf, in flatten order, float32 ``(n,)``."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _scan_loss(params, tokens, cfg: dict):
    eps = epsilon(cfg)
    layers = [params[f"h_{i}"] for i in range(cfg["n_layer"])]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    x = embed(params, tokens)
    x, _ = jax.lax.scan(
        lambda h, lp: (block(h, lp, cfg["n_head"], eps), None), x, stacked
    )
    return lm_loss(head(params, x, eps), tokens)


def round_to_bits(x, bits: int, bucket: int):
    """``x`` as an integer gradient codec of ``bits`` bits a value keeps it:
    in buckets of ``bucket`` consecutive values, each value rounded to the
    nearest of ``2**bits`` evenly spaced levels from the bucket's least to
    its greatest. The control's lower precision; plain arithmetic, nothing
    of the program's codec."""
    flat = x.reshape(-1)
    pad = -flat.size % bucket
    b = jnp.pad(flat, (0, pad), mode="edge").reshape(-1, bucket)
    lo = jnp.min(b, axis=1, keepdims=True)
    unit = (jnp.max(b, axis=1, keepdims=True) - lo) / (2 ** bits - 1)
    level = jnp.round((b - lo) / jnp.where(unit > 0, unit, 1.0))
    return (lo + level * unit).reshape(-1)[: flat.size].reshape(x.shape)


def make_train_reference(cfg: dict, lr: float, n_steps: int, n_blocks: int,
                         row_groups: int = 1, block_sharding=None,
                         gradient_bits: int = 0, gradient_bucket: int = 512):
    """``fn(params, batches)``, ``batches`` a tuple of ``n_steps`` arrays of
    ``(rows, seq)`` tokens, -> dict of float32
    arrays: ``loss (n_steps,)`` (mean over all rows of each step's batch),
    ``grad_norm (leaves,)`` of the first step's mean gradient, and
    ``delta_norm (leaves,)`` of ``params after n_steps - params``, under
    plain Adam (optax's defaults). Each batch is taken ``n_blocks`` equal
    blocks of rows at a time and the block gradients averaged.

    ``row_groups``: the rows lie in that many equal contiguous groups (one
    per chip); every block takes an equal part of each group, so that with
    ``block_sharding`` (a constraint for the ``(n_blocks, rows / n_blocks,
    seq)`` view, rows over the chips) no row has to move.

    ``gradient_bits`` (the control only; 0 is the reference proper): every
    group's mean gradient is kept apart, rounded to that many bits a value
    (:func:`round_to_bits`), the rounded gradients are averaged over the
    groups and the mean is rounded again, as a scatter-reduce-allgather
    over the chips at that width would. All else stays float32."""

    def group_mean_grads(params, batch):
        """(mean loss, the mean gradient of each group's rows: every leaf
        with a leading axis of ``row_groups``)."""
        rows, seq = batch.shape
        per = rows // (row_groups * n_blocks)
        blocks = batch.reshape(row_groups, n_blocks, per, seq)
        blocks = blocks.transpose(1, 0, 2, 3)
        one_group = jax.value_and_grad(_scan_loss)
        zeros = jax.tree.map(
            lambda x: jnp.zeros((row_groups,) + x.shape, x.dtype), params)

        def one(carry, tokens):
            loss_sum, grad_sum = carry
            loss, grad = jax.vmap(one_group, in_axes=(None, 0, None))(
                params, tokens, cfg)
            return (loss_sum + jnp.mean(loss),
                    jax.tree.map(jnp.add, grad_sum, grad)), None

        (loss_sum, grad_sum), _ = jax.lax.scan(
            one, (jnp.float32(0), zeros), blocks)
        return (loss_sum / n_blocks,
                jax.tree.map(lambda g: g / n_blocks, grad_sum))

    def rounded_mean_grad(params, batch):
        loss, grads = group_mean_grads(params, batch)

        def keep(g):
            each = jax.vmap(
                lambda x: round_to_bits(x, gradient_bits, gradient_bucket))(g)
            return round_to_bits(jnp.mean(each, axis=0), gradient_bits,
                                 gradient_bucket)

        return loss, jax.tree.map(keep, grads)

    def mean_loss_and_grad(params, batch):
        rows, seq = batch.shape
        per = rows // (row_groups * n_blocks)
        blocks = batch.reshape(row_groups, n_blocks, per, seq)
        blocks = blocks.transpose(1, 0, 2, 3).reshape(
            n_blocks, row_groups * per, seq)
        if block_sharding is not None:
            blocks = jax.lax.with_sharding_constraint(blocks, block_sharding)
        zeros = jax.tree.map(jnp.zeros_like, params)

        def one(carry, tokens):
            loss_sum, grad_sum = carry
            loss, grad = jax.value_and_grad(_scan_loss)(params, tokens, cfg)
            return (loss_sum + loss,
                    jax.tree.map(jnp.add, grad_sum, grad)), None

        (loss_sum, grad_sum), _ = jax.lax.scan(
            one, (jnp.float32(0), zeros), blocks
        )
        return (loss_sum / n_blocks,
                jax.tree.map(lambda g: g / n_blocks, grad_sum))

    def run(params, batches):
        tokens = jnp.stack(batches)
        zeros = jax.tree.map(jnp.zeros_like, params)

        def one_step(carry, xs):
            p, mu, nu = carry
            t, batch = xs
            loss, grad = (rounded_mean_grad if gradient_bits
                          else mean_loss_and_grad)(p, batch)
            mu = jax.tree.map(
                lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grad)
            nu = jax.tree.map(
                lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grad)
            c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
            p = jax.tree.map(
                lambda w, m, v: w - lr * (m / c1)
                / (jnp.sqrt(v / c2) + ADAM_EPS),
                p, mu, nu,
            )
            return (p, mu, nu), (loss, leaf_norms(grad))

        steps = jnp.arange(1, n_steps + 1, dtype=jnp.float32)
        (last, _, _), (losses, grad_norms) = jax.lax.scan(
            one_step, (params, zeros, zeros), (steps, tokens))
        delta = jax.tree.map(jnp.subtract, last, params)
        return {"loss": losses, "grad_norm": grad_norms[0],
                "delta_norm": leaf_norms(delta)}

    return jax.jit(run)


def worst_leaf_gap(got, ref):
    """Largest over the leaves of ``|got - ref| / max(ref, median(ref))``:
    the gap between two NORMS of each leaf (not the norm of a difference),
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger, since some gradients are all but zero. Returns
    (gap, index of the worst leaf)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    gaps = np.abs(got - ref) / scale
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst
