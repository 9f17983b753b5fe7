"""The looped adapter: a decoder whose ``n_layer`` layers run ``T`` times a
token, behind the one scheduler. :class:`LoopServer` serves
``models/ouro.py``.

A position is ``T x n_layer`` layer applications over ``n_layer`` layers'
weights and leaves ``T x n_layer`` layers' worth of ``k`` and ``v``: pass
``t`` of layer ``l`` writes the slot ``(t, l)`` and reads what pass ``t``
wrote there for the positions before, no other pass's. The cache's layers
are therefore not the weights': the adapter states ``cache_passes = T`` and
the programs give every layer's pools and tails a pass dimension
(``serving/adapter.py``, "A cache layer is not a weight layer"); a page id,
a lane's table row, ``n_pages`` and ``tail_len`` stay one a lane.

Both forwards are one ``lax.scan`` over the passes whose body is the
``n_layer`` layers once, so a compiled program holds one copy of the stack
whatever ``T`` is. The decode step's scan carries the residual stream and
the tails (every pass's rows, written in place at the pass's index) and
closes over the pools, which a pass reads through the lane's table plus its
offset (``adapter.pass_view``); the prefill's carries the stream and stacks
each pass's payload rows. The read is the K/V adapters' guarded one:
``adapter.attend_paged`` with the lane's committed pages as the guard
(``adapter.page_live``), built once a step and shared by all ``T x
n_layer`` reads and both streams.

The exit gate runs on the served path in float32 after every pass
(``ouro.close_pass``). The served token is the last pass's: at the
published threshold of 1.0 no token leaves early, and a lower one is
refused by the model's config. What the gates said is counted a step
(``step_counters``): ``loop.passes``, the passes the active lanes took
(``T`` a lane; the number an early exit would lower), and
``loop.exit_mass.<t>``, the lanes' exit probability at pass ``t`` in
thousandths (1,000 a lane over the passes).

The disaggregated path's frames name a layer and a page and no pass, and
it refuses this adapter (``transport.require_kv_streams``); it is served
with local prefill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ouro
from ..models import window_moe as wm
from ..models.mla_moe import rms_norm
from .adapter import (
    Adapter,
    attend_paged,
    lane_masks,
    page_live,
    page_specs,
    pass_view,
)


class LoopServer(Adapter):
    """Model adapter for one ``(OuroConfig, params)`` pair; cache streams
    ``k`` and ``v`` on every layer, every one of the ``cfg.passes`` passes
    with pages and tails of its own."""

    kind = "loop"
    guards_global_read = True

    def __init__(self, model_cfg, params, serve=None):
        super().__init__(model_cfg, params, serve)
        self.cache_passes = model_cfg.passes
        self.step_counters = ("loop.passes",) + tuple(
            f"loop.exit_mass.{t + 1}" for t in range(model_cfg.passes))

    def cache_streams(self, layer: int):
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(self.cfg.n_head, self.cfg.d_head)])
        return (("k", spec), ("v", spec))

    # -- forwards ----------------------------------------------------------

    def prefill_passes(self, tokens, positions):
        """Every pass of a (right-padded) prompt: ``(x (T, B, S, D)`` each
        pass's closed stream, ``lam (T, B, S)`` its gate, ``ks``, ``vs``: a
        list a layer of ``(T, B, S, H, dh)`` f32, ``k`` rotated)``. One scan
        over the passes; its body is the layers once."""
        cfg = self.cfg

        def one_pass(x, _):
            ks, vs = [], []
            for layer in range(cfg.n_layer):
                pl = self.p[f"layer_{layer}"]
                a = rms_norm(x, pl["in_norm"], cfg.eps)
                q, k, v = ouro.attn_project(cfg, a, pl["attn"], positions)
                ks.append(k)
                vs.append(v)
                o = wm.attend_blocks(cfg, q, k, v, 0)
                x = ouro.ffn_half(cfg, pl, ouro.attn_out(cfg, pl, x, o))
            x, lam = ouro.close_pass(cfg, self.p, x)
            return x, (x, lam, ks, vs)

        _, out = jax.lax.scan(one_pass, ouro.embed(cfg, self.p, tokens),
                              None, length=cfg.passes)
        return out

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the last
        pass's logits at ``last_idx``, then every layer's ``k`` and ``v (T,
        B, S, H, dh)`` f32, a pass's payload a row of the leading
        dimension. Right-padding is inert for every real position under the
        causal mask."""
        x, _, ks, vs = self.prefill_passes(tokens, positions)
        x_last = jax.lax.dynamic_index_in_dim(x[-1], last_idx, 1)
        if self.cache_passes == 1:  # no pass dimension anywhere
            ks, vs = [k[0] for k in ks], [v[0] for v in vs]
        return ouro.logits(self.cfg, self.p, x_last)[:, -1], ks, vs

    def decode_forward(self, state, streams):
        """One decode position: for every pass, this token's ``k`` and
        ``v`` into the pass's raw tails and the pass's committed pages read
        through the lane's table at the pass's offset, the live slots alone
        (``page_live``, one mask for every read of the step); one scan over
        the passes that carries the stream and the tails. Returns ``(the
        last pass's logits (B, V), the new tails by stream, a layer's ``(T,
        B, page_tokens, width)``, the step's counts)``."""
        cfg = self.cfg
        positions = state["pos"][:, None]
        masks = lane_masks(self.serve, state)
        live = page_live(self.serve, state)

        def one_pass(carry, t):
            x, tails = carry
            view = pass_view(state, tails, t, self.serve)
            new = {"k": [], "v": []}
            for layer in range(cfg.n_layer):
                pl = self.p[f"layer_{layer}"]
                a = rms_norm(x, pl["in_norm"], cfg.eps)
                q, k, v = ouro.attn_project(cfg, a[:, None], pl["attn"],
                                            positions)
                o, written = attend_paged(
                    view, layer, streams[layer], masks, q, k, v, cfg.dtype,
                    np.sqrt(cfg.d_head), live=live, at_pass=t)
                for name, tail in written.items():
                    new[name].append(tail)
                x = ouro.ffn_half(cfg, pl, ouro.attn_out(cfg, pl, x, o))
            x, lam = ouro.close_pass(cfg, self.p, x)
            return (x, {n: tuple(v) for n, v in new.items()}), lam

        tails = {name: tuple(state[f"tail_{name}"]) for name in ("k", "v")}
        if cfg.passes == 1:  # the state has no pass dimension: one here
            tails = jax.tree.map(lambda tail: tail[None], tails)
        (x, tails), lams = jax.lax.scan(
            one_pass, (ouro.embed(cfg, self.p, state["tokens"]), tails),
            jnp.arange(cfg.passes, dtype=jnp.int32))
        if cfg.passes == 1:
            tails = jax.tree.map(lambda tail: tail[0], tails)
        return (ouro.logits(cfg, self.p, x), tails,
                self._counts(lams, state["active"]))

    def _counts(self, lams, active):
        """``step_counters`` of one step from the passes' gates ``lams (T,
        B)``: the passes the active lanes took, then their exit mass a pass
        in thousandths. A lane's thousandths are differences of its
        rounded running sum, which ends at 1,000."""
        passes = self.cfg.passes
        running = jnp.round(
            1000.0 * jnp.cumsum(ouro.exit_mass(lams), axis=0)
        ).astype(jnp.int32).at[-1].set(1000)
        mass = jnp.diff(running, axis=0, prepend=0)
        lanes = active.astype(jnp.int32)
        return jnp.concatenate([
            (passes * jnp.sum(lanes))[None], jnp.sum(mass * lanes, axis=1)])
