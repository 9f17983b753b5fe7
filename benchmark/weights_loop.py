"""Seeded weights of a looped sandwich-normed multi-head decoder with a dense
SwiGLU and an exit gate, made on the device.

The benchmark owns the weights: the program under test and the plain
reference (``reference_loop.py``) are both handed the tree this module makes,
drawn from ``--seed`` on the device straight in the type the configuration
states for its parameters (bfloat16); the norms and the exit gate are
float32. The tree's layout is the program's (``torch_cgx_tpu/models/ouro.py``
writes it out). One set of ``num_hidden_layers`` layers: the passes share it.

Initialisation (the configuration's ``init`` block states every number).
Normal with ``std`` unless named. The block norms what every sub-layer
returns before it is added and the final norm closes every pass, so no
output projection's scale reaches the stream. What is left to choose is the
scores' spread and how much of a token the stream keeps:

* ``qk_std``: ``q`` and ``k`` are projections of a unit-scale input, so a
  score over ``sqrt(head_dim)`` has deviation ``hidden_size x qk_std**2``
  (2,048 x 0.02**2 = 0.8 at the plain deviation). A spread near 1 makes the
  softmax read the cache in earnest, which is what lets the 8-bit pages and
  the 4-bit control be told apart.
* ``embed_std``: the embedding's rows enter the stream as they are drawn
  (no scale at the entrance), beside sub-layer outputs of unit scale: at
  0.02 a token is 2 % of the stream after the first sub-layer, at 1.0 it is
  an equal part.
* ``out_norm_gain``: the mean of the two norms' weights that scale what a
  sub-layer returns (post-attention, post-MLP). A relative perturbation of
  the stream comes back from a sub-layer about as large as it went in, on a
  term as large as the gain: over a pass of ``2 L`` sub-layers at gain ``s``
  a perturbation's variance grows by some ``1 + 2 L s^2`` (the final norm
  then brings the stream back to unit scale, and the next pass does the
  same), so at ``s = 1`` and 48 layers the four passes multiply it ten
  thousand times: bfloat16 rounding alone turned a quarter of the served
  tokens on the chip, and 4-bit pages could not be told from 8-bit ones
  (``PERF.md`` section 2, PR 52). At ``s = 1 / sqrt(2 L)`` a pass's
  sub-layers together add what the pass was given, and the growth is a few
  times over the four passes: what differs between two runs is then what
  they were given to differ by.
* ``exit_std``, ``exit_bias``: the gate's weight vector is normal
  ``exit_std`` over a unit-scale closed stream (a logit of deviation
  ``exit_std x sqrt(hidden_size)``) and its bias is one draw of normal
  ``exit_bias`` deviation, so ``lam_t`` lies inside (0, 1) and moves with
  the token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights_window_moe import key_for

DEFAULTS = {"std": 0.02, "qk_std": 0.02, "embed_std": 0.02,
            "out_norm_gain": 1.0, "exit_std": 0.02, "exit_bias": 1.0}


def _draw(cfg: dict, key):
    dt = jnp.dtype(cfg["precision"]["params"])
    init = dict(DEFAULTS, **cfg.get("init", {}))
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    n_layer = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 16 * n_layer + 8))

    def normal(shape, std=init["std"], dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def norm(n, gain=1.0):
        return gain * (1.0 + normal((n,), dtype=jnp.float32))

    params = {
        "embed": normal((cfg["vocab_size"], d), init["embed_std"]),
        "head": normal((d, cfg["vocab_size"])), "norm_f": norm(d),
        "exit": {"w": normal((d,), init["exit_std"], jnp.float32),
                 "b": normal((), init["exit_bias"], jnp.float32)},
    }
    for i in range(n_layer):
        params[f"layer_{i}"] = {
            "in_norm": norm(d), "pre_mlp_norm": norm(d),
            "post_attn_norm": norm(d, init["out_norm_gain"]),
            "post_mlp_norm": norm(d, init["out_norm_gain"]),
            "attn": {"q": normal((d, width), init["qk_std"]),
                     "k": normal((d, width), init["qk_std"]),
                     "v": normal((d, width)), "o": normal((width, d))},
            "mlp": {"gate": normal((d, f)), "up": normal((d, f)),
                    "down": normal((f, d))},
        }
    return params


def make_params(cfg: dict, seed: int):
    """The whole parameter tree from the seed, in one jitted call."""
    return jax.jit(lambda k: _draw(cfg, k))(key_for(seed, 1))
