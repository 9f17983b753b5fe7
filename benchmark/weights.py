"""Seeded GPT-2 weights and token batches, made on the device.

The benchmark owns the weights: the program under test and the plain
reference are both handed the tree this module makes, so neither takes
anything the other produced. One jitted call draws every leaf from
``--seed`` on the device, in float32 (the type the program keeps its
parameters in); nothing is drawn on the host.

The tree has the layout of the program's flax module (``wte``, ``wpe``,
``h_<i>/{ln_1,attn/{attn_qkv,attn_proj},ln_2,mlp/{mlp_in,mlp_out}}``,
``ln_f``). Values follow GPT-2's published initialisation (normal, std
0.02; the two residual projections scaled by 1/sqrt(2 n_layer)), with two
departures that make ``correct`` able to see a fault. Biases and LayerNorm
offsets are drawn too (std 0.02), so that a path that drops one changes the
result. And the fused qkv kernel is drawn at std 0.05: at 0.02 attention
over random weights is all but uniform, averages the cache's rounding away
and adds little to the residual stream, and on the chip the tokens served
from 4-bit pages could not be told from those served from 8-bit pages
(PR 24); at 0.05 attention is peaked and its precision shows in the logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STD = 0.02
QKV_STD = 0.05


def key_for(seed: int, stream: int = 0):
    """A PRNG key for ``--seed`` (any whole number; the driver's are large)."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**63)), stream)


def _draw(cfg: dict, key):
    n_layer, d = cfg["n_layer"], cfg["n_embd"]
    d_ff = cfg.get("n_inner") or 4 * d
    keys = iter(jax.random.split(key, 20))
    init = cfg.get("init", {})
    std, qkv_std = init.get("std", STD), init.get("qkv_std", QKV_STD)
    proj_std = std / math.sqrt(2 * n_layer)

    def normal(shape, std=std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    # One draw per kind of leaf, stacked over the layers, then sliced: a
    # dozen random ops to compile instead of a dozen per layer.
    stacked = {
        "ln_1": (1.0 + normal((n_layer, d)), normal((n_layer, d))),
        "ln_2": (1.0 + normal((n_layer, d)), normal((n_layer, d))),
        "attn_qkv": (normal((n_layer, d, 3 * d), qkv_std),
                     normal((n_layer, 3 * d))),
        "attn_proj": (normal((n_layer, d, d), proj_std), normal((n_layer, d))),
        "mlp_in": (normal((n_layer, d, d_ff)), normal((n_layer, d_ff))),
        "mlp_out": (normal((n_layer, d_ff, d), proj_std),
                    normal((n_layer, d))),
    }

    def dense(name, i):
        kernel, bias = stacked[name]
        return {"kernel": kernel[i], "bias": bias[i]}

    def norm(name, i):
        scale, bias = stacked[name]
        return {"scale": scale[i], "bias": bias[i]}

    params = {
        "wte": {"embedding": normal((cfg["vocab_size"], d))},
        "wpe": {"embedding": normal((cfg["n_positions"], d))},
        "ln_f": {"scale": 1.0 + normal((d,)), "bias": normal((d,))},
    }
    for i in range(n_layer):
        params[f"h_{i}"] = {
            "ln_1": norm("ln_1", i),
            "attn": {"attn_qkv": dense("attn_qkv", i),
                     "attn_proj": dense("attn_proj", i)},
            "ln_2": norm("ln_2", i),
            "mlp": {"mlp_in": dense("mlp_in", i),
                    "mlp_out": dense("mlp_out", i)},
        }
    return params


def make_params(cfg: dict, seed: int, sharding=None):
    """The whole parameter tree from the seed, in one jitted call.

    ``sharding``: where every leaf goes (a replicated ``NamedSharding`` for
    the four-chip cell, so each chip draws its own identical copy); None
    leaves the tree on the default device."""
    fn = jax.jit(lambda k: _draw(cfg, k), out_shardings=sharding)
    return fn(key_for(seed, 1))


def make_token_batches(n_batches: int, rows: int, seq: int, vocab: int,
                       seed: int, sharding=None):
    """``n_batches`` arrays of ``(rows, seq)`` int32 tokens from the seed,
    made on the device in one jitted call; every row differs. ``sharding``
    places the rows over the chips."""
    fn = jax.jit(
        lambda k: tuple(
            jax.random.randint(ki, (rows, seq), 0, vocab, jnp.int32)
            for ki in jax.random.split(k, n_batches)
        ),
        out_shardings=sharding,
    )
    return list(fn(key_for(seed, 2)))
