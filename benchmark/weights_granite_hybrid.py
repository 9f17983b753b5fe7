"""Seeded weights of a hybrid state-space / attention decoder, made on the
device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_granite_hybrid.py``) are both handed the tree this
module makes, drawn from ``--seed`` on the device straight in the type the
configuration states for its parameters (bfloat16); norms, the convolution
and the per-head ``A_log``, ``D``, ``dt_bias`` are float32. The tree's
layout is the program's (``torch_cgx_tpu/models/granite_hybrid.py`` writes
it out).

Initialisation (the configuration's ``init`` block states every number;
PERF.md section 2 says what each is for). Normal with ``std`` unless named:
``embed_std`` draws the tied embedding small, so that ``x0 = 12 E[token]``
does not carry the token's own row through every layer to the head (at 0.02
the served token is the prompt's last token again, five deviations clear of
the rest, and no rounding anywhere can show);
``q_std`` and ``k_std`` make the scores of an attention without positions,
scaled by ``attention_multiplier`` 1/64 and not by 1/8, spread enough that
the softmax is peaked and a page's rounding shows in the logits; ``o_std``
and ``out_proj_std`` set what a mixer adds to the residual stream;
``xbc_std`` draws the columns of ``in_proj`` that make ``x``, ``B`` and
``C`` (the recurrent term ``h C`` is their triple product, and has to be a
fair share of ``y`` beside the skip ``D x`` for the state's precision to
show); ``dt_std`` its ``dt`` columns. The family's convention for the rest:
``A`` uniform in ``[A_lo, A_hi]`` (1-16), ``dt_bias`` the inverse softplus of
a ``dt`` log-uniform in ``[dt_lo, dt_hi]`` (0.001-0.1), so that ``exp(dt
A)`` spans slow and fast heads; the convolution uniform in +-
``1/sqrt(d_conv)`` with a small bias; ``D`` ones. Norm weights are 1 +
normal(``std``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DEFAULTS = {
    "std": 0.02, "embed_std": 0.02, "q_std": 0.02, "k_std": 0.02,
    "v_std": 0.02, "o_std": 0.02, "xbc_std": 0.02, "dt_std": 0.02,
    "out_proj_std": 0.02, "conv_bias_std": 0.02, "A_lo": 1.0, "A_hi": 16.0,
    "dt_lo": 0.001, "dt_hi": 0.1, "D": 1.0,
}


def key_for(seed: int, stream: int = 0):
    """A PRNG key for ``--seed`` (any whole number; the driver's are
    large). The generator is XLA's own (``rbg``), as the other serving
    configurations draw theirs."""
    key = jax.random.key(int(seed) % (2**63), impl="rbg")
    return jax.random.fold_in(key, stream)


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    hm, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    di, k = hm * cfg["mamba_d_head"], cfg["mamba_d_conv"]
    kinds = cfg["layer_types"]
    keys = iter(jax.random.split(key, 32 * len(kinds) + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def norm(width):
        return 1.0 + normal((width,), dtype=jnp.float32)

    params = {"embed": normal((cfg["vocab_size"], d), init["embed_std"]),
              "norm_f": norm(d)}
    for i, kind in enumerate(kinds):
        layer = {
            "mixer_norm": norm(d), "mlp_norm": norm(d),
            "mlp": {"gate": normal((d, f)), "up": normal((d, f)),
                    "down": normal((f, d))},
        }
        if kind == "attention":
            layer["attn"] = {
                "q": normal((d, h * dh), init["q_std"]),
                "k": normal((d, hk * dh), init["k_std"]),
                "v": normal((d, hk * dh), init["v_std"]),
                "o": normal((h * dh, d), init["o_std"]),
            }
        else:
            step = jnp.exp(uniform((hm,), math.log(init["dt_lo"]),
                                   math.log(init["dt_hi"])))
            half = 1.0 / math.sqrt(k)
            layer["mamba"] = {
                "in_proj": jnp.concatenate([
                    normal((d, di)), normal((d, di + 2 * n), init["xbc_std"]),
                    normal((d, hm), init["dt_std"]),
                ], axis=1),
                "conv_w": uniform((k, di + 2 * n), -half, half),
                "conv_b": normal((di + 2 * n,), init["conv_bias_std"],
                                 jnp.float32),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((hm,), init["A_lo"], init["A_hi"])),
                "D": jnp.full((hm,), init["D"], jnp.float32),
                "norm": norm(di),
                "out_proj": normal((di, d), init["out_proj_std"]),
            }
        params[f"layer_{i}"] = layer
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
