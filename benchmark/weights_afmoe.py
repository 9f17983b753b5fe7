"""Seeded weights of a sandwich-normed window/full grouped-query decoder with
a held share of sigmoid-routed experts, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_afmoe.py``) are both handed the tree this module
makes, drawn from ``--seed`` on the device straight in the type the
configuration states for its parameters (bfloat16); norms, the router and its
selection bias are float32. The tree's layout is the program's
(``torch_cgx_tpu/models/afmoe.py`` writes it out); which layers are dense and
which attend a window is the reference's ``layer_plan``. The tree holds
``num_experts`` experts a layer (the chip's share) under a router and a bias
of ``num_experts_published``, and ``vocab_size`` rows of the embedding and
the head.

Initialisation (the configuration's ``init`` block states every number).
Normal with ``std`` unless named. The block norms what every sub-layer
returns before it is added, and ``q`` and ``k`` a head before the scores, so
no output projection's scale reaches the stream and the scores' spread is 1
whatever ``std`` is: what is left to choose is the routed experts' weight
beside the shared expert (``expert_down_std`` against ``std``: alike, so one
held expert at its combine weight of about 0.6 adds what the shared one
does) and the routing. The router is normal ``router_std`` (logits of
deviation 1.1 over a unit-scale input) and the selection bias normal
``bias_std``, both float32: the top four of 256 sigmoid scores lie about
0.01 apart, so a bias of 0.002 leaves the selection the scores' and the
routing even, as a trained router's is (Ling's finding, PERF.md section 6,
PR 37). The routed experts are drawn uniform with the stated deviation,
``EXPERT_CHUNK`` at a time (``weights_mla_moe.py`` says why).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import reference_afmoe as reference
from benchmark.weights_window_moe import key_for

DEFAULTS = {"std": 0.02, "expert_down_std": 0.02, "router_std": 0.02,
            "bias_std": 0.002}
EXPERT_CHUNK = 8  # experts drawn at a time (a matrix is 9.4 M values)


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    published = cfg.get("num_experts_published", held)
    plan = reference.layer_plan(cfg)
    keys = iter(jax.random.split(key, 32 * len(plan) + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def norm(n):
        return 1.0 + normal((n,), dtype=jnp.float32)

    def experts(shape, std):
        chunk = min(EXPERT_CHUNK, held)
        half = std * math.sqrt(3.0)  # uniform on +-half has deviation std
        parts = jax.lax.map(
            lambda k: jax.random.uniform(
                k, (chunk,) + shape, jnp.float32, -half, half).astype(dt),
            jax.random.split(next(keys), held // chunk),
        )
        return parts.reshape((held,) + shape)

    def swiglu(width):
        return {"gate": normal((d, width)), "up": normal((d, width)),
                "down": normal((width, d))}

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i, (_, dense) in enumerate(plan):
        layer = {
            "in_norm": norm(d), "post_attn_norm": norm(d),
            "pre_mlp_norm": norm(d), "post_mlp_norm": norm(d),
            "attn": {
                "q": normal((d, h * dh)), "k": normal((d, hk * dh)),
                "v": normal((d, hk * dh)), "g": normal((d, h * dh)),
                "o": normal((h * dh, d)),
                "q_norm": norm(dh), "k_norm": norm(dh),
            },
        }
        if dense:
            layer["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            layer["moe"] = {
                "router": normal((d, published), init["router_std"],
                                 jnp.float32),
                "bias": normal((published,), init["bias_std"], jnp.float32),
                "gate": experts((d, fe), init["std"]),
                "up": experts((d, fe), init["std"]),
                "down": experts((fe, d), init["expert_down_std"]),
                "shared": swiglu(fe * cfg["num_shared_experts"]),
            }
        params[f"layer_{i}"] = layer
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
