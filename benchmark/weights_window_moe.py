"""Seeded weights of a window/global grouped-query decoder with routed
experts, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_window_moe.py``) are both handed the tree this module
makes. Leaves are drawn from ``--seed`` on the device, straight in the type
the configuration states for its parameters (bfloat16: 3.97 B parameters are
7.9 GB); norms and the router are float32. The tree's layout is the
program's (``torch_cgx_tpu/models/window_moe.py`` writes it out).

Initialisation (the configuration's ``init`` block; PERF.md section 2 says
what each is for). Everything is normal with ``std`` unless named (the
routed experts uniform with the same deviation): ``qk_std`` draws the query
and key projections, so that attention's scores over random weights have a
spread of about 1.5 on the global layers too, which have no position to
peak on; ``expert_down_std`` sets how much of the residual stream one routed
expert is; ``router_std`` draws the router (no selection bias: the top
logits decide). Norm weights are 1 + normal(``std``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DEFAULTS = {
    "std": 0.02, "qk_std": 0.025, "o_std": 0.02, "expert_down_std": 0.007,
    "router_std": 0.02,
}
EXPERT_CHUNK = 32  # experts drawn at a time


def key_for(seed: int, stream: int = 0):
    """A PRNG key for ``--seed`` (any whole number; the driver's are
    large). The generator is XLA's own (``rbg``): threefry takes tens of
    seconds over 4 B values."""
    key = jax.random.key(int(seed) % (2**63), impl="rbg")
    return jax.random.fold_in(key, stream)


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, fe = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    n_layer = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 16 * n_layer + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def norm(n):
        return 1.0 + normal((n,), dtype=jnp.float32)

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

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i in range(n_layer):
        params[f"layer_{i}"] = {
            "in_norm": norm(d), "post_norm": norm(d),
            "attn": {
                "q": normal((d, h * dh), init["qk_std"]),
                "k": normal((d, hk * dh), init["qk_std"]),
                "v": normal((d, hk * dh)),
                "o": normal((h * dh, d), init["o_std"]),
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
