"""Mixture-of-Experts with expert parallelism (EP).

The reference has no MoE/EP (SURVEY.md §2.3: "TP / PP / EP: absent") — this
subsystem is designed fresh for TPU rather than ported. GShard-style dense
dispatch, shaped for the MXU and for GSPMD expert parallelism:

* :class:`MoEMlp` — drop-in replacement for the dense ``Mlp`` block: top-k
  softmax router, capacity-bounded one-hot dispatch (no dynamic shapes —
  token->slot assignment is a cumsum over one-hots, overflowing tokens are
  dropped and ride the residual connection), per-expert FFN as batched
  einsums over a leading expert dimension.
* **EP sharding**: every tensor with a leading expert axis gets a
  ``with_sharding_constraint`` on the ``ep`` mesh axis (when configured);
  expert weights shard via :func:`moe_param_spec`. XLA/GSPMD then inserts
  the dispatch/combine ``all_to_all`` pair over ICI — the explicit-MPI
  equivalent the reference would have needed is exactly what SURVEY.md §7
  says should collapse into the compiler.
* **Load-balance auxiliary loss** (Switch-Transformer form) is sown under
  ``intermediates/moe_aux_loss``; collect with :func:`aux_loss`.
* :func:`dropless_moe` — the serving plane's expert layer (DeepSeek-V3
  form: sigmoid scores, a selection bias, normalised top-k weights times a
  scaling factor): the token-expert assignments sorted by expert and every
  expert's SwiGLU run over its own contiguous rows through
  ``ops.dispatch.grouped_matmul`` (the ``cgx_grouped_matmul`` kernel on the
  chip, ``jax.lax.ragged_dot`` elsewhere). No capacity, so no token is ever
  dropped, at any skew. Told which experts it holds (``held``: a contiguous range, a
  chip's share of a layer that several chips divide), it routes over all of
  them and computes its own experts' part of the result, without the
  exchange; all of them is the default. Selection may be limited to the best
  ``topk_group`` of ``n_group`` groups of experts. The routing may also be
  the caller's (``routing``: a model whose router reads another tensor than
  the experts compute on, :func:`softmax_topk_route`), and the gate a ReLU.

Composes with the quantized gradient allreduce: expert weights are regular
pytree leaves, so per-layer compression configs apply (pattern
``.*experts.*`` etc.).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import dispatch
from ..utils import compat
from ..wire import dispatch as wire_dispatch
from ..wire.edges import EDGE_MOE_A2A


_warned_constraint = False


def ep_dispatch(exp_in: jax.Array, axis_name: str, *, name: str = "moe.dispatch"):
    """Explicit expert-parallel dispatch ``all_to_all`` through the wire
    dispatcher (the ``moe_a2a`` edge) — for MoE layers running INSIDE
    ``shard_map`` over ``axis_name`` (the GSPMD path in :class:`MoEMlp`
    instead lets the compiler insert the collective, which the edge
    registry cannot see).

    ``exp_in`` is this device's dense dispatch buffer ``(E, C, D)``
    (every expert's slots, local tokens). Returns ``(E/ws, ws*C, D)``:
    this device's experts' slots, gathered from every rank. Raw unless a
    ``moe_a2a`` edge config resolves; with one, the payload rides the
    quantized wire (packed bit-planes + per-slice meta, STE backward).
    Requires ``E % ws == 0``."""
    ws = compat.axis_size(axis_name)
    if exp_in.shape[0] % ws:
        raise ValueError(
            f"ep_dispatch: expert dim {exp_in.shape[0]} not divisible by "
            f"axis size {ws}"
        )
    return wire_dispatch.wire_all_to_all(
        exp_in, axis_name, split_axis=0, concat_axis=1,
        kind=EDGE_MOE_A2A, name=name,
    )


def ep_combine(exp_out: jax.Array, axis_name: str, *, name: str = "moe.combine"):
    """Inverse of :func:`ep_dispatch`: ``(E/ws, ws*C, D)`` expert outputs
    back to the token-owning ranks as ``(E, C, D)`` — the combine
    ``all_to_all``, same ``moe_a2a`` edge surface."""
    return wire_dispatch.wire_all_to_all(
        exp_out, axis_name, split_axis=1, concat_axis=0,
        kind=EDGE_MOE_A2A, name=name,
    )


def _capacity(tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    return max(1, int(np.ceil(tokens * top_k * factor / n_experts)))


class MoEMlp(nn.Module):
    """Top-k routed expert FFN.

    Shapes: x (B, S, D) -> (B, S, D); experts hold (E, D, F) / (E, F, D)
    kernels with F = ratio * d_model.
    """

    d_model: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    ratio: int = 4
    dtype: Any = jnp.bfloat16
    ep_axis: Optional[str] = None  # mesh axis to shard the expert dim over

    def _constrain(self, t, spec):
        if self.ep_axis is None:
            return t
        try:
            return jax.lax.with_sharding_constraint(t, spec)
        except (ValueError, RuntimeError) as e:
            # No mesh context (eager / plain jit without set_mesh) or a bad
            # axis name: EP degrades to replicated experts. Never silent —
            # on a real pod that is an OOM/perf cliff.
            global _warned_constraint
            if not _warned_constraint:
                _warned_constraint = True
                from ..utils.logging import get_logger

                get_logger().warning(
                    "MoE EP sharding constraint %s not applied (%s); experts "
                    "will be REPLICATED. Run under `with jax.set_mesh(mesh):`"
                    " with an %r mesh axis to shard them.",
                    spec, e, self.ep_axis,
                )
            return t

    @nn.compact
    def __call__(self, x, train: bool = True):
        b, s, d = x.shape
        e, k = self.n_experts, self.top_k
        if not 1 <= k <= e:
            raise ValueError(
                f"top_k={k} must be in [1, n_experts={e}]"
            )
        f = self.ratio * self.d_model
        t = b * s
        cap = _capacity(t, e, k, self.capacity_factor)
        ep = self.ep_axis

        xt = x.reshape(t, d)
        # Router in f32 (tiny matmul; numerics matter more than speed).
        router = self.param(
            "router", nn.initializers.lecun_normal(), (d, e), jnp.float32
        )
        logits = xt.astype(jnp.float32) @ router  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)

        # Top-k gates: iteratively take the argmax, mask, renormalize the
        # selected gates to sum to 1 per token (GShard convention).
        masked = probs
        sel_onehots, sel_gates = [], []
        for _ in range(k):
            idx = jnp.argmax(masked, axis=-1)  # (T,)
            oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, E)
            sel_onehots.append(oh)
            sel_gates.append(jnp.sum(probs * oh, axis=-1))  # (T,)
            masked = masked * (1.0 - oh)
        denom = sum(sel_gates) + 1e-9

        # Load-balance aux loss (Switch form): E * sum_e fraction_e * prob_e,
        # computed on the top-1 assignment.
        frac = jnp.mean(sel_onehots[0], axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        self.sow(
            "intermediates", "moe_aux_loss",
            jnp.asarray(e, jnp.float32) * jnp.sum(frac * mean_prob),
        )

        # Capacity-bounded slot assignment: position of each token within
        # its expert's queue = exclusive cumsum of the choice one-hots (the
        # k-th choice queues behind all first choices, etc.).
        dispatch = jnp.zeros((t, e, cap), jnp.float32)
        combine = jnp.zeros((t, e, cap), jnp.float32)
        slots_used = jnp.zeros((e,), jnp.float32)
        for i in range(k):
            oh = sel_onehots[i]
            pos = (jnp.cumsum(oh, axis=0) - oh) + slots_used[None, :]  # (T, E)
            slot = jnp.sum(pos * oh, axis=-1)  # (T,) queue position
            keep = (slot < cap).astype(jnp.float32)
            slot_oh = jax.nn.one_hot(
                jnp.minimum(slot, cap - 1).astype(jnp.int32), cap,
                dtype=jnp.float32,
            )  # (T, C)
            d_i = oh[:, :, None] * slot_oh[:, None, :] * keep[:, None, None]
            dispatch = dispatch + d_i
            gate = (sel_gates[i] / denom)[:, None, None]
            combine = combine + gate * d_i
            slots_used = slots_used + jnp.sum(oh, axis=0)

        # Dispatch tokens to expert slots: (E, C, D) — the all_to_all
        # boundary under EP sharding.
        exp_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), xt.astype(self.dtype)
        )
        exp_in = self._constrain(exp_in, P(ep, None, None))

        w_in = self.param(
            "experts_in",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, d, f), jnp.float32,
        ).astype(self.dtype)
        b_in = self.param(
            "experts_in_bias", nn.initializers.zeros, (e, f), jnp.float32
        ).astype(self.dtype)
        w_out = self.param(
            "experts_out",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, f, d), jnp.float32,
        ).astype(self.dtype)
        b_out = self.param(
            "experts_out_bias", nn.initializers.zeros, (e, d), jnp.float32
        ).astype(self.dtype)

        h = jnp.einsum("ecd,edf->ecf", exp_in, w_in) + b_in[:, None, :]
        h = self._constrain(h, P(ep, None, None))
        h = nn.gelu(h)
        exp_out = jnp.einsum("ecf,efd->ecd", h, w_out) + b_out[:, None, :]
        exp_out = self._constrain(exp_out, P(ep, None, None))

        y = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), exp_out
        )
        return y.reshape(b, s, d)


# What :func:`dropless_moe` counts, in the order of its ``stats`` vector.
STATS = ("assignments", "experts_touched", "load_max", "dropped")
# The same of a layer that holds a share of its experts (``held``).
HELD_STATS = STATS + ("held_assignments",)


def sigmoid_topk_route(y, router, bias, *, top_k: int, scale: float,
                       n_group: int = 1, topk_group: int = 1):
    """``y (T, D)`` -> the chosen experts ``(T, top_k)`` int32 and their
    combine weights ``(T, top_k)`` float32: scores ``sigmoid(y W_g)`` in
    float32 at full matmul precision (a near-tie between the last expert
    chosen and the first left out must not turn on the MXU's bf16 passes),
    selection by ``score + bias``, weights the chosen scores normalised to
    sum to 1, times ``scale``. With ``n_group > 1`` the experts are
    ``n_group`` groups of neighbours, a group's score is the sum of its two
    largest ``score + bias``, and the selection is among the experts of the
    ``topk_group`` best groups alone."""
    scores = jax.nn.sigmoid(jnp.matmul(
        y.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    biased = scores + bias.astype(jnp.float32)
    if n_group > 1:
        t, e = biased.shape
        best_two, _ = jax.lax.top_k(biased.reshape(t, n_group, -1), 2)
        _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
        kept = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        biased = jnp.where(jnp.repeat(kept, e // n_group, axis=1), biased,
                           -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), weights


def softmax_topk_route(y, router, *, top_k: int):
    """``y (T, D)`` -> the chosen experts ``(T, top_k)`` int32 and their
    combine weights ``(T, top_k)`` float32: the ``top_k`` largest logits ``y
    W_r`` (float32 at full matmul precision, as in
    :func:`sigmoid_topk_route`) and the softmax over those alone, so the
    weights sum to 1."""
    logits = jnp.matmul(
        y.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    chosen, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def dropless_moe(y, router, bias, gate, up, down, *, top_k: int,
                 scale: float = 1.0, dtype=jnp.bfloat16, count_mask=None,
                 n_group: int = 1, topk_group: int = 1,
                 held: Optional[int] = None, routing=None,
                 act: str = "silu"):
    """``sum_i w_i E_i(y)`` over the ``top_k`` routed experts of every row
    of ``y (T, D)``; experts ``gate, up (E, D, F)``, ``down (E, F, D)``,
    each ``down(act(gate y) * up y)``, ``act`` ``silu`` or ``relu``.
    ``routing``, where given, is ``(idx (T, top_k) int32, weights (T, top_k)
    float32)`` from the caller (``router``, ``bias``, ``scale`` and the
    groups are then not read); otherwise :func:`sigmoid_topk_route`
    decides. Returns ``(out (T, D) dtype, stats
    (4,) int32)``, ``stats`` as :data:`STATS` names them: assignments made,
    experts that got at least one, the largest expert's load, and tokens
    dropped (assignments asked for less assignments computed; 0 by
    construction, counted all the same). ``count_mask (T,)`` bool leaves
    rows (idle decode lanes) out of the counts; they are computed anyway.

    ``held`` says that ``gate``, ``up`` and ``down`` are a share of the
    layer's experts, those from ``held`` on: the router (as wide as the
    whole layer) chooses among all of them, the assignments that fall on an
    expert held here are computed and the others add nothing. ``stats`` is
    then :data:`HELD_STATS`: every assignment made, then the held experts
    touched, the largest held expert's load, the held assignments asked for
    less those computed, and the held assignments."""
    t, _ = y.shape
    e = gate.shape[0]
    if routing is None:
        routing = sigmoid_topk_route(
            y, router, bias, top_k=top_k, scale=scale, n_group=n_group,
            topk_group=topk_group,
        )
    idx, weights = routing
    flat = idx.reshape(-1)
    if held is not None:
        here = (flat >= held) & (flat < held + e)
        # An absent expert sorts after every held one and is no group: its
        # rows lie past the groups' end and are zeroed below.
        flat = jnp.where(here, flat - held, e)
    order = jnp.argsort(flat, stable=True)  # assignments, by expert
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1, mode="drop")
    xs = y.astype(dtype)[order // top_k]  # (T * top_k, D)

    def product(rows, weights):
        return dispatch.grouped_matmul(rows, weights.astype(dtype), sizes)

    h = _GATES[act](product(xs, gate)) * product(xs, up)
    rows = product(h, down)
    rows = rows.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    if held is not None:
        rows = jnp.where(here[order][:, None], rows, 0.0)
    out = jnp.zeros((t * top_k, y.shape[1]), jnp.float32).at[order].set(rows)
    out = out.reshape(t, top_k, -1).sum(axis=1).astype(dtype)

    counted = jnp.ones((t,), bool) if count_mask is None else count_mask
    per_row = jnp.repeat(counted.astype(jnp.int32), top_k)
    load = jnp.zeros((e,), jnp.int32).at[flat].add(per_row, mode="drop")
    made = jnp.sum(per_row)
    asked = made if held is None else jnp.sum(per_row * here)
    stats = [
        made if held is not None else jnp.sum(load),
        jnp.sum(load > 0).astype(jnp.int32), jnp.max(load),
        asked - jnp.sum(load),
    ]
    if held is not None:
        stats.append(jnp.sum(load))
    return out, jnp.stack(stats).astype(jnp.int32)


def total_stats(counts):
    """A decode step's counts from its expert layers' ``stats`` vectors
    (:data:`STATS` or :data:`HELD_STATS`): summed, but ``load_max`` their
    largest."""
    counts = jnp.stack(counts)  # (expert layers, len(STATS))
    peak = STATS.index("load_max")
    return jnp.sum(counts, axis=0).at[peak].set(jnp.max(counts[:, peak]))


def moe_param_spec(path: str, leaf, axis: str = "ep") -> Optional[P]:
    """EP PartitionSpec for MoE params: shard the leading expert dim of
    ``experts_*`` kernels/biases over ``axis``; router replicated. Returns
    None for non-MoE params (caller falls through to its other rules)."""
    if "experts" in path:
        return P(*((axis,) + (None,) * (leaf.ndim - 1)))
    if path.endswith("router"):
        return P()
    return None


def aux_loss(intermediates) -> jax.Array:
    """Sum all sown ``moe_aux_loss`` values (0 when no MoE layers ran)."""
    total = jnp.asarray(0.0, jnp.float32)
    for path, leaves in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if "moe_aux_loss" in jax.tree_util.keystr(path):
            total = total + jnp.sum(leaves)
    return total
