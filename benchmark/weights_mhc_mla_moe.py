"""Seeded weights of a hyper-connected MLA decoder with routed experts, made
on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_mhc_mla_moe.py``) are both handed the tree this
module makes. The latent attention, the experts, the embedding and the head
are ``weights_mla_moe``'s draw (JoyAI's: its docstring says what each
deviation is for), from the same ``--seed``; this module adds the
hyper-connections' leaves, float32 as the configuration states:
``layer_<i>/hc_attn`` and ``hc_ffn`` ``{phi (nD, 2n + n^2), alpha (3,), base
(2n + n^2,)}`` and ``hc_head {phi (nD, n), alpha (1,), base (n,)}``. The
tree's layout is the program's (``torch_cgx_tpu/models/mla_moe.py`` writes
it out).

The hyper-connections' draw (the configuration's ``init`` block). ``phi`` is
normal with deviation ``hc_phi_std / sqrt(nD)``: the flattened streams are
normed to unit size, so each mix ``m`` is normal with deviation
``hc_phi_std`` over tokens. ``alpha`` is ``(hc_alpha_pre, hc_alpha_post,
hc_alpha_res)``. ``base`` is normal with ``hc_base_pre_std``,
``hc_base_post_std`` and, for the stream-to-stream logits, ``hc_res_diag``
on the diagonal plus normal ``hc_base_res_std``: a diagonal that dominates
without drowning the rest, so that ``H_res`` is far from the identity and
from the uniform matrix, moves with the token, and is not doubly stochastic
before its iterations have run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import weights_mla_moe
from benchmark.weights_mla_moe import key_for

HC_DEFAULTS = {
    "hc_phi_std": 1.0, "hc_alpha_pre": 1.0, "hc_alpha_post": 1.0,
    "hc_alpha_res": 0.5, "hc_base_pre_std": 1.0, "hc_base_post_std": 0.5,
    "hc_res_diag": 1.2, "hc_base_res_std": 0.4,
}


def _hc(key, init: dict, n: int, width: int, mixes: bool):
    """One hyper-connection's leaves; the read-out's without ``mixes``."""
    k_phi, k_pre, k_post, k_res = jax.random.split(key, 4)
    phi_std = init["hc_phi_std"] / math.sqrt(n * width)

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    base = [normal(k_pre, (n,), init["hc_base_pre_std"])]
    alpha = [init["hc_alpha_pre"]]
    if mixes:
        base += [
            normal(k_post, (n,), init["hc_base_post_std"]),
            (init["hc_res_diag"] * jnp.eye(n)
             + normal(k_res, (n, n), init["hc_base_res_std"])).reshape(-1),
        ]
        alpha += [init["hc_alpha_post"], init["hc_alpha_res"]]
    base = jnp.concatenate(base)
    return {"phi": normal(k_phi, (n * width, base.shape[0]), phi_std),
            "alpha": jnp.asarray(alpha, jnp.float32), "base": base}


def _draw(cfg: dict, key):
    params = weights_mla_moe._draw(cfg, key)
    init = dict(HC_DEFAULTS, **cfg.get("init", {}))
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    keys = iter(jax.random.split(jax.random.fold_in(key, 48),
                                 2 * cfg["num_hidden_layers"] + 1))
    for i in range(cfg["num_hidden_layers"]):
        for name in ("hc_attn", "hc_ffn"):
            params[f"layer_{i}"][name] = _hc(next(keys), init, n, d, True)
    params["hc_head"] = _hc(next(keys), init, n, d, False)
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
