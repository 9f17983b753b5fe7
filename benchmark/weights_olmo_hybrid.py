"""Seeded weights of a hybrid gated delta-rule / attention decoder, made on
the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_olmo_hybrid.py``) are both handed the tree this
module makes, drawn from ``--seed`` on the device straight in the type the
configuration states for its parameters (bfloat16); norms, the convolution
and the per-head ``A_log``, ``dt_bias`` are float32. The tree's layout is the
program's (``torch_cgx_tpu/models/olmo_hybrid.py`` writes it out).

Initialisation (the configuration's ``init`` block states every number;
PERF.md section 2 says what each is for). Normal with ``std`` unless named.
The block norms what a mixer and an MLP return (``x + RMSNorm(f(x))``), so
no projection's scale reaches the residual stream, and two numbers set it:
``embed_std`` (1: the token's own row weighs as much in ``x`` as one
sublayer's output, which has unit size under its norm) and the norm weights,
1 + normal(``std``). ``q_norm_mean`` and ``k_norm_mean`` are the means of
the full-attention layers' query and key norm weights: the scores of an
attention without positions are ``q . k / sqrt(128)`` of normed rows, whose
spread over a lane's keys is the product of the two weights' sizes, and it
has to be large enough that the softmax is peaked and a page's rounding
shows in the logits. ``ba_std`` draws ``W_ba``, whose ``a`` column adds to
``dt_bias`` inside the softplus: drawn as wide as the rest, its spread (1.2
at the first layer, 7 at the last: ``x`` grows with depth under a block that
norms after) swamps ``dt_bias``, every head is wiped (``alpha`` under 1e-3)
every ten tokens or so, no head is slow, and a bfloat16 state's roundings
die before they add up. The delta-rule family's convention for the rest: ``A``
uniform in ``[A_lo, A_hi]`` (0-16), ``dt_bias`` the inverse softplus of a
``dt`` log-uniform in ``[dt_lo, dt_hi]`` (0.001-0.1), so that ``alpha =
exp(-A softplus(a + dt_bias))`` spans slow and fast heads; the convolution
uniform in +- ``1/sqrt(d_conv)``, no bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DEFAULTS = {
    "std": 0.02, "embed_std": 1.0, "q_norm_mean": 1.0, "k_norm_mean": 1.0,
    "ba_std": 0.02,
    "A_lo": 0.0, "A_hi": 16.0, "dt_lo": 0.001, "dt_hi": 0.1,
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
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    hg, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, k = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    kinds = cfg["layer_types"]
    keys = iter(jax.random.split(key, 16 * len(kinds) + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def norm(width, mean=1.0):
        return mean + normal((width,), dtype=jnp.float32)

    params = {"embed": normal((cfg["vocab_size"], d), init["embed_std"]),
              "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d)}
    for i, kind in enumerate(kinds):
        layer = {
            "mixer_norm": norm(d), "mlp_norm": norm(d),
            "mlp": {"gate": normal((d, f)), "up": normal((d, f)),
                    "down": normal((f, d))},
        }
        if kind == "full_attention":
            layer["attn"] = {
                "q": normal((d, h * dh)), "k": normal((d, hk * dh)),
                "v": normal((d, hk * dh)), "o": normal((h * dh, d)),
                "q_norm": norm(h * dh, init["q_norm_mean"]),
                "k_norm": norm(hk * dh, init["k_norm_mean"]),
            }
        else:
            step = jnp.exp(uniform((hg,), math.log(init["dt_lo"]),
                                   math.log(init["dt_hi"])))
            half = 1.0 / math.sqrt(k)
            layer["gdn"] = {
                "in_proj": normal((d, 2 * hg * (dk + dv))),
                "ba_proj": normal((d, 2 * hg), init["ba_std"]),
                "conv_w": uniform((k, hg * (2 * dk + dv)), -half, half),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((hg,), init["A_lo"], init["A_hi"])),
                "norm": norm(dv),
                "out_proj": normal((hg * dv, d)),
            }
        params[f"layer_{i}"] = layer
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
