"""Seeded weights of an MLA decoder with routed experts, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_mla_moe.py``) are both handed the tree this module
makes. Leaves are drawn from ``--seed`` on the device, straight in the type
the configuration states for its parameters (bfloat16: 5.56 B parameters
are 11 GB, and float32 would not fit one chip); norms, the router and its
selection bias are float32. The tree's layout is the program's
(``torch_cgx_tpu/models/mla_moe.py`` writes it out).

Initialisation (the configuration's ``init`` block; PERF.md section 2 says
what each is for). Everything is normal with ``std`` unless named (the
routed experts uniform with the same deviation):
``q_b_std`` and ``kv_b_std`` make queries and keys large enough that
attention over random weights is peaked, so that the rounding of a cached
latent shows in the logits; ``expert_down_std`` sets how much of the
residual stream one routed expert is, which is what a near-tie of the
router between the last expert chosen and the first left out costs when it
falls the other way under bfloat16 activations; ``router_std`` and
``bias_std`` draw the router and its selection bias. Norm weights are 1 +
normal(``std``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DEFAULTS = {
    "std": 0.02, "q_b_std": 0.035, "kv_b_std": 0.06, "o_std": 0.02,
    "expert_down_std": 0.007, "router_std": 0.02, "bias_std": 0.05,
}
EXPERT_CHUNK = 32  # experts drawn at a time


def key_for(seed: int, stream: int = 0):
    """A PRNG key for ``--seed`` (any whole number; the driver's are
    large). The generator is XLA's own (``rbg``): threefry takes tens of
    seconds over 5.5 B values."""
    key = jax.random.key(int(seed) % (2**63), impl="rbg")
    return jax.random.fold_in(key, stream)


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    n_layer, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    keys = iter(jax.random.split(key, 64 * n_layer + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def norm(n):
        return 1.0 + normal((n,), dtype=jnp.float32)

    def experts(shape, std):
        """``(E, a, b)`` drawn ``EXPERT_CHUNK`` experts at a time, so that
        the generator's temporaries stay small beside 2.4 GB leaves; uniform
        with the deviation ``std``, not normal: the routed experts are 87 %
        of the values, this way no inverse error function runs over 4.8 B
        of them (the whole tree is drawn in 3 s on the chip, PR 27), and
        sums over 768 or 2,048 of them are normal either way."""
        chunk = min(EXPERT_CHUNK, e)
        half = std * math.sqrt(3.0)  # uniform on +-half has deviation std
        parts = jax.lax.map(
            lambda k: jax.random.uniform(
                k, (chunk,) + shape, jnp.float32, -half, half).astype(dt),
            jax.random.split(next(keys), e // chunk),
        )
        return parts.reshape((e,) + shape)

    def swiglu(width, down_std=init["std"]):
        return {"gate": normal((d, width)), "up": normal((d, width)),
                "down": normal((width, d), down_std)}

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i in range(n_layer):
        layer = {
            "attn_norm": norm(d), "ffn_norm": norm(d),
            "attn": {
                "q_a": normal((d, rq)), "q_a_norm": norm(rq),
                "q_b": normal((rq, h * (dn + dr)), init["q_b_std"]),
                "kv_a": normal((d, rkv + dr)), "kv_a_norm": norm(rkv),
                "kv_b": normal((rkv, h * (dn + dv)), init["kv_b_std"]),
                "o": normal((h * dv, d), init["o_std"]),
            },
        }
        if i < n_dense:
            layer["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            layer["moe"] = {
                "router": normal((d, e), init["router_std"], jnp.float32),
                "bias": normal((e,), init["bias_std"], jnp.float32),
                "gate": experts((d, fe), init["std"]),
                "up": experts((d, fe), init["std"]),
                "down": experts((fe, d), init["expert_down_std"]),
                "shared": swiglu(fs),
            }
        params[f"layer_{i}"] = layer
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
