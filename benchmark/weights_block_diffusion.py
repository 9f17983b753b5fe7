"""Seeded weights of a grouped-query decoder with QK-norm and routed experts
that generates by diffusion over blocks, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_block_diffusion.py``) are both handed the tree this
module makes. Leaves are drawn from ``--seed`` on the device, straight in the
type the configuration states for its parameters (bfloat16: 4.98 B parameters
are 9.97 GB); norms and the router are float32. The tree's layout is the
program's (``torch_cgx_tpu/models/sdar_moe.py`` writes it out).

Initialisation (the configuration's ``init`` block states every number; how
each was chosen, so that the reference's logits tell 4-bit pages from 8-bit
ones; PERF.md section 2 has the chip's readings of the three draws tried).
Normal with ``std`` (0.02, the family's ``initializer_range``) unless named;
the routed experts uniform with the stated deviation. A token has to matter
to the stream it enters, or every masked position of a block (all the same
row of the embedding) reads the same logits: ``embed_std`` 1.0, the rows
enter the stream at its own scale. ``q`` and ``k`` are normed a head before
the scores, so their projections' scale reaches nothing and the scores'
deviation is the product of the two gains: ``qk_norm_gain`` 1.4 each gives
1.96, a softmax over 1,000 keys that rests on a few dozen of them, so that a
key's or a value's rounding in a page is not averaged away (at 2.0 the
softmax rests on a handful and bfloat16's own rounding of the scores turned
more than half the served tokens). ``o_std`` 0.04 has the attention add
about half the stream's scale a layer (a value's component is 0.9, a few
dozen keys' mean 0.2, times ``o_std x sqrt(4096)``). The experts decide
whether a seeded model can be compared at all: the eighth and the ninth
largest of 128 router logits lie a twentieth of their deviation apart, so
the rounding of the router's input moves the eighth expert of one token in
ten a layer whatever the router's scale, and what a moved expert does to
the stream is its weight times its size. ``expert_down_std`` 0.02 (a
layer's experts add a tenth of the stream's scale, not half) and
``router_std`` 0.1 in float32 (logits of deviation 4.5: the softmax over
the chosen eight is peaked and the eighth weighs a hundredth of the first)
make that small; with 0.1 and 0.02 a fifth of the sound runs' served tokens
were not the reference's own. Norm weights are 1 + normal(``std``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights_window_moe import key_for

DEFAULTS = {
    "std": 0.02, "embed_std": 1.0, "qk_norm_gain": 1.4, "o_std": 0.04,
    "expert_down_std": 0.02, "router_std": 0.1,
}
EXPERT_CHUNK = 32  # experts drawn at a time


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    n_layer = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 16 * n_layer + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def norm(n, gain=1.0):
        return gain * (1.0 + normal((n,), dtype=jnp.float32))

    def experts(shape, std):
        """``(E, a, b)`` drawn ``EXPERT_CHUNK`` experts at a time, uniform
        with the deviation ``std`` (``weights_mla_moe.py`` says why)."""
        chunk = min(EXPERT_CHUNK, e)
        half = std * math.sqrt(3.0)  # uniform on +-half has deviation std
        parts = jax.lax.map(
            lambda k: jax.random.uniform(
                k, (chunk,) + shape, jnp.float32, -half, half).astype(dt),
            jax.random.split(next(keys), e // chunk),
        )
        return parts.reshape((e,) + shape)

    params = {"embed": normal((cfg["vocab_size"], d), init["embed_std"]),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i in range(n_layer):
        params[f"layer_{i}"] = {
            "in_norm": norm(d), "post_norm": norm(d),
            "attn": {
                "q": normal((d, h * dh)), "k": normal((d, hk * dh)),
                "v": normal((d, hk * dh)),
                "o": normal((h * dh, d), init["o_std"]),
                "q_norm": norm(dh, init["qk_norm_gain"]),
                "k_norm": norm(dh, init["qk_norm_gain"]),
            },
            "moe": {
                "router": normal((d, e), init["router_std"], jnp.float32),
                "gate": experts((d, fe), init["std"]),
                "up": experts((d, fe), init["std"]),
                "down": experts((fe, d), init["expert_down_std"]),
            },
        }
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
