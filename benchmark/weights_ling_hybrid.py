"""Seeded weights of a hybrid KDA / latent-attention decoder with routed
experts, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_ling_hybrid.py``) are both handed the tree this module
makes, drawn from ``--seed`` on the device straight in the type the
configuration states for its parameters (bfloat16); norms, the router and its
bias, the convolution, ``A_log`` and ``dt_bias`` are float32. The tree's
layout is the program's (``torch_cgx_tpu/models/ling_hybrid.py`` writes it
out); which layers are KDA, latent attention, dense or experts is the
reference's ``layer_plan``. The tree holds ``num_experts`` experts a layer
(the chip's share) under a router and a bias of ``num_experts_published``,
and ``vocab_size`` rows of the embedding and the head.

Initialisation (the configuration's ``init`` block states every number;
PERF.md section 2 says what each is for). Normal with ``std`` unless named;
the block is pre-norm, so what a mixer and an FFN return reaches the residual
stream at the scale of their output projections. ``q_std`` and ``kv_b_std``
set the latent attention's scores (the JoyAI configuration's reasoning: a
spread of about 1.5 over a lane's keys; ``q`` here is projected from the
2,560-wide normed input, not from a 1,536-wide latent, so its deviation is
0.035 x sqrt(1,536 / 2,560)); ``expert_down_std`` makes one routed expert
about 1 % of the residual stream; the router normal ``router_std`` and its
selection bias normal ``bias_std``, float32; the KDA layers' ``q``, ``k``,
``v`` projection normal ``qkv_std``. The configuration draws the last two
small (0.005, 0.004) so that the routing is even, as a trained router's is:
SiLU after the convolution has a positive mean, which at ``std`` adds up
over the state's memory into a direction every lane shares, lanes that are
alike route alike, and a bias of 0.05 on top halves the experts a decode
step touches (the configuration's ``assumed`` list has the readings). The routed experts are drawn
uniform with the stated deviation, ``EXPERT_CHUNK`` at a time
(``weights_mla_moe.py`` says why).

The KDA decay, ``log alpha = L sigmoid(A (f + dt_bias))`` with ``L =
kda_lower_bound``: ``A = exp(A_log)`` uniform in ``[A_lo, A_hi]`` a head, and
a rate ``r`` a key channel, log-uniform in ``[rate_lo, rate_hi]``, with
``dt_bias = logit(r / -L) / A``, so that a channel whose ``f`` is 0 decays by
``alpha = exp(-r)`` a token: at 0.0028 to 0.94 the median ``alpha`` is 0.95, a
tenth of the channels lie over 0.995 and a tenth under 0.6 (slow channels
carry a state's rounding for hundreds of tokens: the control). ``f_std``
draws ``W_f``, which moves a channel's rate with the token: at ``std`` its
spread (1.0 under the gate's ``A``) would swamp ``dt_bias`` as Olmo's ``W_ba``
did; at 0.004 it moves ``r`` by a fifth either way.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import reference_ling_hybrid as reference

DEFAULTS = {
    "std": 0.02, "q_std": 0.027, "kv_b_std": 0.06, "o_std": 0.02,
    "expert_down_std": 0.007, "router_std": 0.02, "bias_std": 0.05,
    "f_std": 0.004, "qkv_std": 0.02, "A_lo": 0.5, "A_hi": 2.0, "rate_lo": 0.0028,
    "rate_hi": 0.94,
}
EXPERT_CHUNK = 32  # experts drawn at a time


def key_for(seed: int, stream: int = 0):
    """A PRNG key for ``--seed`` (any whole number; the driver's are
    large). The generator is XLA's own (``rbg``), as the other serving
    configurations draw theirs."""
    key = jax.random.key(int(seed) % (2**63), impl="rbg")
    return jax.random.fold_in(key, stream)


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, h, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    rkv, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    held, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    published = cfg.get("num_experts_published", held)
    kw = cfg["short_conv_kernel_size"]
    floor = -float(cfg["kda_lower_bound"])
    plan = reference.layer_plan(cfg)
    keys = iter(jax.random.split(key, 32 * len(plan) + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

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

    def swiglu(width, down_std=init["std"]):
        return {"gate": normal((d, width)), "up": normal((d, width)),
                "down": normal((width, d), down_std)}

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i, (kind, dense) in enumerate(plan):
        layer = {"mixer_norm": norm(d), "ffn_norm": norm(d)}
        if kind == "kda":
            gate = uniform((h,), init["A_lo"], init["A_hi"])
            rate = jnp.exp(uniform((h, dh), math.log(init["rate_lo"]),
                                   math.log(init["rate_hi"]))) / floor
            half = 1.0 / math.sqrt(kw)
            layer["kda"] = {
                "qkv": normal((d, 3 * h * dh), init["qkv_std"]),
                "f": normal((d, h * dh), init["f_std"]),
                "bg": normal((d, 2 * h)),
                "conv_w": uniform((kw, 3 * h * dh), -half, half),
                "A_log": jnp.log(gate),
                # sigmoid(A dt_bias) = rate
                "dt_bias": ((jnp.log(rate) - jnp.log1p(-rate))
                            / gate[:, None]).reshape(h * dh),
                "norm": norm(dh),
                "out": normal((h * dh, d), init["o_std"]),
            }
        else:
            layer["attn"] = {
                "q": normal((d, h * (dn + dr)), init["q_std"]),
                "kv_a": normal((d, rkv + dr)), "kv_a_norm": norm(rkv),
                "kv_b": normal((rkv, h * (dn + dv)), init["kv_b_std"]),
                "g": normal((d, h)),
                "o": normal((h * dv, d), init["o_std"]),
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
                "shared": swiglu(cfg["moe_shared_expert_intermediate_size"]),
            }
        params[f"layer_{i}"] = layer
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
