"""JAX-native front ends for compressed data-parallel training.

The reference integrates through a DDP communication hook
(/root/reference/cgx_utils/allreduce_hooks.py — SURVEY.md §2.2); the
TPU-native front door is functional instead:

* :func:`gradient_sync` — drop-in for ``lax.psum`` over gradient pytrees
  inside a user's own ``shard_map``.
* :func:`make_train_step` — wraps a loss function + optax optimizer into a
  jitted SPMD train step: per-device grads -> pre-divide -> quantized
  allreduce -> optimizer update. Replicated outputs are bit-identical across
  devices thanks to the reducers' error-symmetry invariant.
* :func:`compressed_allreduce_transform` — an ``optax`` gradient
  transformation for optimizer chains.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from typing import NamedTuple

from .. import config as cfg_mod
from ..config import TopologyConfig
from ..utils.compat import shard_map as _compat_shard_map
from ..utils.logging import metrics
from . import mesh as mesh_mod
from . import reducers
from . import topology as topo_router
from .allreduce import allreduce_tree


class ErrorFeedbackState(NamedTuple):
    """Per-device residual of the quantized gradient transport.

    HAZARD: this state VARIES across data-parallel devices — under
    shard_map it must be sharded (leading device axis or explicit
    per-device placement), NEVER declared replicated (``in_specs=P()``):
    XLA would then fold the divergent per-device residuals into one
    replica value and silently corrupt the correction. The safe wiring is
    ``make_train_step(..., error_feedback=True)`` +
    :func:`init_error_feedback`, which place the state on the device axis
    for you.
    """

    e: optax.Updates


# cgx-analysis: allow(orphan-memo) — warn-once observability set; staleness only suppresses a duplicate placement warning
_PLACEMENT_WARNED: set = set()

# Per-compressor warning text: each points at ITS OWN safe wiring — the
# EF message told top-k users to call init_error_feedback, a dead end
# (advisor r5 low #2).
_PLACEMENT_MSGS = {
    "ef": (
        "error_feedback=True carries PER-DEVICE residual state: inside "
        "shard_map the ErrorFeedbackState must be sharded over the device "
        "axis, not declared replicated (in_specs=P()), or the residuals "
        "are silently corrupted. Use make_train_step(error_feedback=True) "
        "with init_error_feedback for the safe wiring."
    ),
    "topk": (
        "topk_transform carries PER-DEVICE error-feedback residuals "
        "(TopKState.es): inside shard_map the es leaves must be sharded "
        "over the device axis, not declared replicated, or the residuals "
        "are silently corrupted. Use make_train_step(topk_ratio=...) with "
        "init_topk_state for the safe wiring."
    ),
    "powersgd": (
        "powersgd_transform carries mixed-placement state: the warm-start "
        "factors (qs) are replicated but the residuals (es) are "
        "PER-DEVICE — inside shard_map the es leaves must be sharded over "
        "the device axis or they are silently corrupted. Use "
        "make_train_step(powersgd_rank=...) with init_powersgd_state for "
        "the safe wiring."
    ),
}


def _warn_ef_placement_once(kind: str = "ef"):
    """One-time (per compressor) trace-time reminder that the residual
    state is per-device (the docstring-only hazard promoted to a runtime
    signal — advisor r3; text parameterized per compressor — r5 low #2)."""
    if kind in _PLACEMENT_WARNED:
        return
    _PLACEMENT_WARNED.add(kind)
    import warnings

    warnings.warn(_PLACEMENT_MSGS[kind], stacklevel=3)


def _ef_sync(grads, e, *, mesh, axes, topology, key, divisor):
    """Shared EF recipe (single source for the transform and the train
    step): pre-divide (§8.12 order), add residuals, quantized-sum, and
    measure the new residual against the sync's own stage-1 wire decode.
    Returns ``(reduced_f32, e_new)``."""
    g_eff = jax.tree.map(
        lambda g, ee: g.astype(jnp.float32) / divisor + ee, grads, e
    )
    reduced, rt = allreduce_tree(
        g_eff, mesh=mesh, axes=axes, topology=topology, key=key,
        average=False, return_roundtrip=True,
    )
    e_new = jax.tree.map(lambda g, r: g - r.astype(jnp.float32), g_eff, rt)
    return reduced, e_new


# ---------------------------------------------------------------------------
# Non-finite gradient guard (CGX_NONFINITE_GUARD — docs/ROBUSTNESS.md).
#
# One NaN/Inf on ONE device poisons every max-min bucket range it shares a
# wire chunk with, on EVERY rank — compressed collectives amplify a point
# fault into whole-job divergence. The guard detects it pre-quantization,
# agrees globally (a psum'd flag, so all devices branch identically), and
# degrades gracefully: "skip" drops the step, "exact" reroutes the
# sanitized gradients through an uncompressed psum. Everything is built
# from `where`-selects, not `cond`, so the collective structure of the
# traced program is step-invariant (jit/SPMD-safe) and a no-fault step is
# bit-identical to a guard-off step.
# ---------------------------------------------------------------------------


def _global_nonfinite(grads, axes, mesh):
    """Group-global "any gradient is NaN/Inf" flag (bool scalar, identical
    on every device — psum of the per-device any)."""
    flags = [
        jnp.any(~jnp.isfinite(l))
        for l in jax.tree_util.tree_leaves(grads)
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)
    ]
    local = functools.reduce(jnp.logical_or, flags, jnp.asarray(False))
    f = local.astype(jnp.float32)
    for a in axes:
        if mesh.shape[a] > 1:
            f = jax.lax.psum(f, a)
    return f > 0


def _zero_when(bad, tree):
    """Whole tree -> zeros on a bad step (constant-zero buckets quantize
    exactly, so the compressor path stays structurally live but carries
    nothing); bit-identical pass-through otherwise."""
    return jax.tree.map(
        lambda x: jnp.where(bad, jnp.zeros_like(x), x), tree
    )


def _keep_when(bad, old, new):
    """Elementwise select: the pre-step value on a bad step, the computed
    one otherwise. NaNs confined to the untaken branch do not propagate
    (select, not arithmetic)."""
    return jax.tree.map(lambda o, n: jnp.where(bad, o, n), old, new)


def _sanitize(tree):
    """Zero exactly the non-finite coordinates (identity bits on finite
    ones) — what the "exact" fallback ships."""
    return jax.tree.map(
        lambda x: jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x)), tree
    )


def _count_nonfinite(bad, axes):
    """Execution-time `cgx.nonfinite_steps` bump (the _runtime_count
    pattern): one increment per bad step, reported by the device at
    position 0 on every sync axis."""
    from jax.experimental import io_callback

    is0 = functools.reduce(
        jnp.logical_and,
        [jax.lax.axis_index(a) == 0 for a in axes],
        jnp.asarray(True),
    )

    def _sink(v):
        metrics.add("cgx.nonfinite_steps", float(v))
        if v:
            # Guard trip: black-box the evidence (docs/OBSERVABILITY.md).
            # record() is ring-cheap and runs every trip; the full-ring
            # dump is rate-limited (first trip, then every 32nd) so a
            # diverged run that trips EVERY step doesn't rewrite the
            # dump file ~100 KB/step for its remainder.
            from ..observability import flightrec

            flightrec.record("nonfinite_guard", steps=float(v))
            n = int(metrics.get("cgx.nonfinite_steps"))
            if n == 1 or n % 32 == 0:
                flightrec.dump(reason="nonfinite_guard")

    io_callback(
        _sink,
        None,
        jnp.where(jnp.logical_and(bad, is0), 1.0, 0.0).astype(jnp.float32),
        ordered=False,
    )


def _guard_policy(explicit: Optional[str]) -> str:
    p = explicit if explicit is not None else cfg_mod.nonfinite_guard()
    if p not in cfg_mod.NONFINITE_POLICIES:
        raise ValueError(
            f"nonfinite_guard must be one of {cfg_mod.NONFINITE_POLICIES}, "
            f"got {p!r}"
        )
    return p


def gradient_sync(
    grads,
    *,
    mesh,
    axes: Sequence[str] = (mesh_mod.DP_AXIS,),
    topology: Optional[TopologyConfig] = None,
    key: Optional[jax.Array] = None,
    average: bool = True,
    compress_small: bool = False,
    nonfinite_guard: Optional[str] = None,
):
    """Quantized gradient allreduce (inside shard_map). Averaging divides
    before quantization, matching the hook order (SURVEY.md §8.12).

    ``nonfinite_guard`` (default: ``CGX_NONFINITE_GUARD``, off): with
    "skip" a step whose gradients contain NaN/Inf anywhere in the group
    returns all-zero reduced gradients (the step becomes a no-op for
    SGD-style optimizers; for full parameter/optimizer-state rollback use
    ``make_train_step``, which owns the update); with "exact" the
    sanitized gradients ride an uncompressed psum for that step instead of
    poisoning the quantization buckets. Either way ``cgx.nonfinite_steps``
    counts the event at execution time."""
    policy = _guard_policy(nonfinite_guard)
    if policy == "off":
        return allreduce_tree(
            grads,
            mesh=mesh,
            axes=axes,
            topology=topology,
            key=key,
            average=average,
            compress_small=compress_small,
        )
    axes = tuple(axes)
    bad = _global_nonfinite(grads, axes, mesh)
    _count_nonfinite(bad, axes)
    reduced = allreduce_tree(
        _zero_when(bad, grads),
        mesh=mesh,
        axes=axes,
        topology=topology,
        key=key,
        average=average,
        compress_small=compress_small,
    )
    if policy == "exact":
        ws = int(np.prod([mesh.shape[a] for a in axes]))
        exact = reducers.psum_tree(_sanitize(grads), axes, mesh)
        if average:
            exact = jax.tree.map(lambda x: x / ws, exact)
        reduced = jax.tree.map(
            lambda e, r: jnp.where(bad, e.astype(r.dtype), r), exact, reduced
        )
    return reduced


def compressed_allreduce_transform(
    *,
    mesh,
    axes: Sequence[str] = (mesh_mod.DP_AXIS,),
    topology: Optional[TopologyConfig] = None,
    average: bool = True,
    error_feedback: bool = False,
) -> optax.GradientTransformation:
    """optax transformation performing the quantized allreduce; prepend to an
    optimizer chain running inside shard_map:

        optax.chain(cgx.compressed_allreduce_transform(mesh=mesh), optax.adam(1e-3))

    ``error_feedback=True`` adds EF-style residual accumulation: the exact
    quantization error of this device's wire contribution (the sync's own
    stage-1 round trip, ``allreduce_tree(return_roundtrip=True)``) is
    carried in the optimizer state and added to the next step's gradient —
    the low-bit bias corrector the reference's kernels stub out but never
    wire (cuda_compression_operations.cu:69-84). It pays off when
    per-bucket outliers bias the quantization of small coordinates (see
    tests); at 1-bit it can HURT with the SRA transport — the residuals
    inflate the dynamic range the second-stage requantization must cover.
    The EF state is PER-DEVICE: inside shard_map, shard it (see
    :func:`make_train_step`'s ``error_feedback`` plumbing or manage the
    state placement yourself); declaring it replicated silently corrupts
    the residuals.

    **Ring-transport caveat** (applies to ``make_train_step`` too): with
    ``CGX_INNER_REDUCTION_TYPE=RING`` the measured residual covers the
    FIRST scatter-reduce hop only — later hops requantize accumulated
    partial sums on other devices and are treated as exact, so Ring EF is
    an approximation (it under-counts compounded hop error). SRA (the
    default) measures its wire residual exactly, byte-for-byte against
    the actual fused/chunked stage-1 layout (tested). Prefer SRA when
    running EF.
    """
    ws_total = int(np.prod([mesh.shape[a] for a in axes]))

    def init_fn(params):
        if not error_feedback:
            return optax.EmptyState()
        return ErrorFeedbackState(
            e=jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
        )

    def update_fn(updates, state, params=None):
        del params
        if not error_feedback:
            return (
                gradient_sync(updates, mesh=mesh, axes=axes,
                              topology=topology, average=average),
                state,
            )
        _warn_ef_placement_once()
        reduced, e_new = _ef_sync(
            updates, state.e, mesh=mesh, axes=axes, topology=topology,
            key=None, divisor=ws_total if average else 1,
        )
        reduced = jax.tree.map(
            lambda r, u: r.astype(u.dtype), reduced, updates
        )
        return reduced, ErrorFeedbackState(e=e_new)

    return optax.GradientTransformation(init_fn, update_fn)


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh,
    *,
    axes: Sequence[str] = (mesh_mod.DP_AXIS,),
    sp_axis: Optional[str] = None,
    topology: Optional[TopologyConfig] = None,
    stochastic_seed: Optional[int] = None,
    donate: bool = True,
    error_feedback: bool = False,
    powersgd_rank: Optional[int] = None,
    topk_ratio: Optional[float] = None,
    nonfinite_guard: Optional[str] = None,
    snapshot_every: Optional[int] = None,
    outer: Optional[Any] = None,
):
    """Build a jitted compressed-DP train step.

    ``outer`` (default None): an
    :class:`~torch_cgx_tpu.parallel.async_plane.AsyncPlane` — the PR 13
    asynchronous cross-slice hook. After the jitted call, the plane runs
    host-side on the updated params: every ``CGX_ASYNC_H``-th step it
    posts this slice's compressed parameter delta to the dedicated
    sender thread (never blocking on DCN) and folds arrived peer deltas
    into the outer anchor, which becomes the returned params. Pure
    Python around the jit boundary — the staged program is UNCHANGED
    (the jaxpr pin in tests/test_async_plane.py), and with ``CGX_ASYNC``
    unset (or ``outer=None``) the hook is an identity.

    ``snapshot_every`` (default: ``CGX_SNAPSHOT_EVERY`` env, 0 = off):
    the recovery supervisor's rollback hook. Every N-th step the wrapper
    host-copies the step's *inputs* (params, opt_state, compressor state
    when present) via ``checkpoint.snapshot_in_memory`` — registry
    snapshot included — BEFORE invoking the compiled program, so a
    recovery can roll back to ``step.last_snapshot()`` / ``step.rollback()``
    and deterministically replay. Pure Python around the jit boundary:
    the staged program is unchanged, and with the knob unset nothing is
    copied (docs/ROBUSTNESS.md Recovery).

    ``nonfinite_guard`` (default: ``CGX_NONFINITE_GUARD`` env, off):
    NaN/Inf gradients anywhere in the group are detected pre-quantization
    and the step degrades gracefully — "skip" keeps params, optimizer
    state AND compressor state (EF/PowerSGD/top-k residuals) at their
    pre-step values; "exact" applies the update from an uncompressed psum
    of the sanitized gradients while still freezing the compressor state
    for that step. Both bump the execution-time ``cgx.nonfinite_steps``
    counter and are bit-identical to "off" on fault-free steps (pure
    `where`-selects; the staged collectives never change across steps).
    Costs when enabled: an isfinite sweep + scalar psum + one host
    callback per step, and for "exact" one full uncompressed psum per
    step (the fallback traffic is staged unconditionally — prefer "skip"
    unless you need every step applied).

    ``loss_fn(params, batch) -> scalar loss`` is evaluated per device on its
    batch shard; gradients are synchronized with the quantized allreduce and
    the optimizer update runs replicated. A 3-argument
    ``loss_fn(params, batch, rng)`` also receives a fresh per-step, per-device
    PRNG key (for dropout etc. — pass it to ``model.apply`` as
    ``rngs={"dropout": rng}``); it is derived from ``stochastic_seed`` (or 0)
    folded with the step index and the device's data-parallel position.

    Returns ``step(params, opt_state, batch, step_idx) -> (params, opt_state,
    loss)`` where ``batch`` leaves are sharded on their leading dim over
    ``axes`` and params/opt_state are replicated.

    ``sp_axis``: sequence parallelism — batch leaves of rank >= 2 are
    additionally sharded on their SECOND dim (sequence) over this axis
    (rank-1 leaves such as sample weights have no sequence dim and stay
    replicated over sp), the per-shard
    loss is averaged over it (use a boundary-correct loss such as
    :func:`torch_cgx_tpu.models.gpt2.sp_lm_loss`), and gradients — partial
    sums over sequence shards — join the quantized allreduce over
    ``axes + (sp_axis,)``. Only a single dp axis composes with sp (the
    reducers support at most two allreduce axes).

    ``error_feedback=True`` carries a per-device quantization residual
    (see :func:`compressed_allreduce_transform`): the step signature
    becomes ``step(params, opt_state, ef, batch, step_idx) -> (params,
    opt_state, ef, loss)`` where ``ef`` comes from
    :func:`init_error_feedback` — leaves are ``(ws, *param.shape)``
    f32 sharded over the sync axes on the leading device dim, so every
    device keeps its own residual. NOTE: exact for the default SRA
    transport; with ``CGX_INNER_REDUCTION_TYPE=RING`` the residual
    covers the first scatter-reduce hop only (later hops' compounding
    requantization is treated as exact) — prefer SRA when running EF.

    ``powersgd_rank=r`` replaces the quantized allreduce with PowerSGD
    low-rank compression (:mod:`.powersgd`) at that rank — the SAFE
    wiring of its mixed-placement state: the step signature becomes
    ``step(params, opt_state, psgd, batch, step_idx) -> (params,
    opt_state, psgd, loss)`` with ``psgd`` from
    :func:`.powersgd.init_powersgd_state` (warm-start factors replicated,
    per-device residuals on a leading device axis). Mutually exclusive
    with ``error_feedback`` (PowerSGD carries its own EF).

    ``topk_ratio=r`` replaces the quantized allreduce with top-k
    sparsification (:mod:`.topk`) shipping the ``ceil(r * n)`` largest-
    magnitude coordinates per leaf: ``step(params, opt_state, tk, batch,
    step_idx) -> (params, opt_state, tk, loss)`` with ``tk`` from
    :func:`.topk.init_topk_state`. Mutually exclusive with
    ``error_feedback`` and ``powersgd_rank`` (top-k carries its own EF).
    """
    import inspect

    exclusive = [
        name
        for name, on in (
            ("error_feedback", error_feedback),
            ("powersgd_rank", powersgd_rank is not None),
            ("topk_ratio", topk_ratio is not None),
        )
        if on
    ]
    if len(exclusive) > 1:
        raise ValueError(
            f"make_train_step: {' and '.join(exclusive)} are mutually "
            "exclusive — each compressor carries its own error feedback"
        )
    axes = tuple(axes)
    sync_axes = axes if sp_axis is None else axes + (sp_axis,)
    if len(sync_axes) > 2:
        raise ValueError(
            "make_train_step: at most two gradient-sync axes (got "
            f"{sync_axes!r}); hierarchical dp (cross x intra) cannot also "
            "compose with sp_axis"
        )
    ws_total = int(np.prod([mesh.shape[a] for a in sync_axes]))
    wants_rng = len(inspect.signature(loss_fn).parameters) >= 3
    guard = _guard_policy(nonfinite_guard)
    # Armed nan_grad fault (CGX_FAULTS) — staged into the trace so the
    # poison originates inside the compiled program, upstream of the
    # quantizer, exactly where a real overflow NaN would.
    from ..robustness import guard as _rguard

    nan_spec = _rguard.nan_grad_spec()

    def _batch_leaf_spec(leaf) -> P:
        # sp shards the SECOND (sequence) dim, which rank-1 leaves (sample
        # weights, per-sequence labels) don't have — they stay replicated
        # over sp and shard only over the dp axes.
        if sp_axis is not None and getattr(leaf, "ndim", 0) >= 2:
            return P(axes, sp_axis)
        return P(axes)

    def _grads_and_key(params, batch, step_idx):
        # Producer-fused stash epoch: entries staged by THIS trace's
        # backward are the only ones its allreduce may claim (trace-time
        # Python — nothing staged changes when the plane is off).
        from ..ops import fused_producer as _fp

        _fp.begin_step()
        if wants_rng:
            r = jax.random.fold_in(
                jax.random.PRNGKey(stochastic_seed or 0), step_idx
            )
            # decorrelate dropout masks across data-parallel devices
            for a in sync_axes:
                r = jax.random.fold_in(r, jax.lax.axis_index(a))
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, r)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if nan_spec is not None:
            grads = _rguard.inject_nan(grads, step_idx, sync_axes, nan_spec)
        key = None
        if stochastic_seed is not None:
            key = jax.random.fold_in(jax.random.PRNGKey(stochastic_seed), step_idx)
        return loss, grads, key

    def _guard_pre(grads):
        """(grads-for-the-compressor, bad-flag) — identity/(None) off."""
        if guard == "off":
            return grads, None
        bad = _global_nonfinite(grads, sync_axes, mesh)
        _count_nonfinite(bad, sync_axes)
        return _zero_when(bad, grads), bad

    def _guard_reduced(bad, grads_raw, reduced):
        """"exact" fallback: on a bad step swap in the uncompressed psum
        of the sanitized raw gradients (averaged, like the compressor
        path); `reduced` there is the compressor's output for the zeroed
        tree. Fault-free steps pass `reduced` through bit-identically."""
        if bad is None or guard != "exact":
            return reduced
        exact = reducers.psum_tree(_sanitize(grads_raw), sync_axes, mesh)
        return jax.tree.map(
            lambda e, r: jnp.where(bad, (e / ws_total).astype(r.dtype), r),
            exact,
            reduced,
        )

    def _guard_state(bad, old, new):
        """Compressor state (EF/PowerSGD/top-k residuals) freezes on a bad
        step under BOTH policies: the wire carried zeros, so that step's
        measured residual describes nothing."""
        return new if bad is None else _keep_when(bad, old, new)

    def _guard_update(bad, old_p, old_s, new_p, new_s):
        """"skip": params + optimizer state roll back to pre-step values
        on a bad step. "exact" applies the fallback update as-is."""
        if bad is None or guard != "skip":
            return new_p, new_s
        return _keep_when(bad, old_p, new_p), _keep_when(bad, old_s, new_s)

    def _step(params, opt_state, batch, step_idx):
        loss, grads, key = _grads_and_key(params, batch, step_idx)
        g_c, bad = _guard_pre(grads)
        reduced = gradient_sync(
            g_c, mesh=mesh, axes=sync_axes, topology=topology, key=key,
            average=True, nonfinite_guard="off",
        )
        reduced = _guard_reduced(bad, grads, reduced)
        updates, new_opt = optimizer.update(reduced, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_opt = _guard_update(
            bad, params, opt_state, new_params, new_opt
        )
        loss = jax.lax.psum(loss, sync_axes) / ws_total
        return new_params, new_opt, loss

    if powersgd_rank is not None:
        from .powersgd import PowerSGDState, powersgd_transform

        psgd_tx = powersgd_transform(
            mesh=mesh, axes=sync_axes, rank=powersgd_rank, average=True,
            placement_warning=False,
        )

    if topk_ratio is not None:
        from .topk import TopKState, topk_transform

        topk_tx = topk_transform(
            mesh=mesh, axes=sync_axes, ratio=topk_ratio, average=True,
            placement_warning=False,
        )

    def _step_topk(params, opt_state, tk, batch, step_idx):
        loss, grads, _ = _grads_and_key(params, batch, step_idx)
        local = TopKState(
            es=tuple(None if e is None else jnp.squeeze(e, 0) for e in tk.es)
        )
        g_c, bad = _guard_pre(grads)
        loc_c = local if bad is None else TopKState(
            es=tuple(
                None if e is None else jnp.where(bad, jnp.zeros_like(e), e)
                for e in local.es
            )
        )
        reduced, st = topk_tx.update(g_c, loc_c)
        reduced = _guard_reduced(bad, grads, reduced)
        st = _guard_state(bad, local, st)
        updates, new_opt = optimizer.update(reduced, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_opt = _guard_update(
            bad, params, opt_state, new_params, new_opt
        )
        loss = jax.lax.psum(loss, sync_axes) / ws_total
        out_state = TopKState(
            es=tuple(None if e is None else e[None] for e in st.es)
        )
        return new_params, new_opt, out_state, loss

    def _step_psgd(params, opt_state, psgd, batch, step_idx):
        loss, grads, _ = _grads_and_key(params, batch, step_idx)
        local = PowerSGDState(
            qs=psgd.qs,
            es=tuple(
                None if e is None else jnp.squeeze(e, 0) for e in psgd.es
            ),
        )
        g_c, bad = _guard_pre(grads)
        loc_c = local if bad is None else PowerSGDState(
            qs=local.qs,  # orthonormalization of zeroed grads may NaN; the
            es=tuple(     # whole state is selected back below regardless
                None if e is None else jnp.where(bad, jnp.zeros_like(e), e)
                for e in local.es
            ),
        )
        reduced, st = psgd_tx.update(g_c, loc_c)
        reduced = _guard_reduced(bad, grads, reduced)
        st = _guard_state(bad, local, st)
        updates, new_opt = optimizer.update(reduced, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_opt = _guard_update(
            bad, params, opt_state, new_params, new_opt
        )
        loss = jax.lax.psum(loss, sync_axes) / ws_total
        out_state = PowerSGDState(
            qs=st.qs,
            es=tuple(None if e is None else e[None] for e in st.es),
        )
        return new_params, new_opt, out_state, loss

    def _step_ef(params, opt_state, ef, batch, step_idx):
        loss, grads, key = _grads_and_key(params, batch, step_idx)
        e = jax.tree.map(lambda x: jnp.squeeze(x, 0), ef)
        g_c, bad = _guard_pre(grads)
        e_c = e if bad is None else _zero_when(bad, e)
        reduced, e_new = _ef_sync(
            g_c, e_c, mesh=mesh, axes=sync_axes, topology=topology,
            key=key, divisor=ws_total,
        )
        reduced = _guard_reduced(bad, grads, reduced)
        e_new = _guard_state(bad, e, e_new)
        grads_out = jax.tree.map(
            lambda r, g: r.astype(g.dtype), reduced, grads
        )
        updates, new_opt = optimizer.update(grads_out, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_opt = _guard_update(
            bad, params, opt_state, new_params, new_opt
        )
        loss = jax.lax.psum(loss, sync_axes) / ws_total
        return (
            new_params,
            new_opt,
            jax.tree.map(lambda x: x[None], e_new),
            loss,
        )

    # The batch in_specs depend on per-leaf rank (rank-1 leaves can't carry
    # the sp dim), so the shard_map is built per batch tree-structure and
    # cached — jit retraces on structure change anyway.
    built = {}

    def _build(batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        # Registry version in the key: per-layer configs are baked in at
        # trace time, so a re-registration (adapt_bits, new pattern
        # configs) must produce a fresh trace, not hit the stale one.
        version = cfg_mod.registry_version()
        # Topology-route component: a CGX_XLA_ALLREDUCE flip (or a mesh
        # whose groups reclassify) changes what allreduce_tree stages, so
        # it must produce a fresh trace, never hit one from another
        # routing era — same contract as the registry version.
        xla_route = topo_router.cache_key(mesh, sync_axes)
        # Schedule component: a CGX_SCHEDULE/CGX_SCHED_CHUNKS flip changes
        # the emission (pipelined chunks, reverse-order group dispatch) of
        # the staged program — it must retrace, never serve a trace from
        # another scheduling era.
        from . import schedule as sched_mod

        sched_key = sched_mod.cache_key_component()
        # Step-planner component: a CGX_PLANNER flip or an ADOPTED
        # re-plan (the planner bumps its plan version only when the
        # calibrated model actually moved) must retrace; an unchanged
        # re-plan keeps the key — the no-retrace-storm half of the
        # planner's idempotency contract.
        from . import planner as planner_mod

        planner_key = planner_mod.cache_key_component()
        # Wire-plane component: a CGX_WIRE/CGX_WIRE_BITS flip changes what
        # any routed edge inside loss_fn (ring-attention hops, MoE
        # dispatch) stages — it must retrace, never serve a trace from
        # another wire era. Registered-edge changes ride the registry
        # version above.
        from ..wire import edges as wire_edges

        wire_key = wire_edges.cache_key_component()
        # Producer-fuse component: a CGX_PRODUCER_FUSE flip changes which
        # gradients enter the wire pre-quantized — it must retrace, never
        # serve a program from another producer era. Configuring the
        # producer context happens here too (trace-time state the
        # backward rules read); consumption self-disarms under the
        # nonfinite guard and the stateful compressors because their
        # gradient rewrites break the cotangent-identity match, but the
        # explicit gate keeps the staged payloads from even being built.
        from ..ops import fused_producer as _fp

        _fp.configure(
            mesh, sync_axes, divisor=ws_total,
            active=(
                guard == "off"
                and not error_feedback
                and powersgd_rank is None
                and topk_ratio is None
            ),
        )
        producer_key = _fp.cache_key_component()
        # Env component: every CGX_* knob the traced step bakes in
        # (codec lowering/encode, compression defaults, fusion split,
        # qerr/runtime-metrics staging, the nonfinite guard) — a flip of
        # any of them between calls must retrace, never serve a program
        # from another env era. The registry version above only covers
        # REGISTERED config; this covers the env tier (the analyzer's
        # knob→cache-key pass pins the set — tools/analysis/knobs.py).
        env_key = cfg_mod.trace_knob_fingerprint()
        cache_key = (
            treedef,
            tuple(getattr(l, "ndim", 0) for l in leaves),
            version,
            xla_route,
            sched_key,
            wire_key,
            producer_key,
            planner_key,
            env_key,
        )
        # Evict traces from older registry versions — each holds a full
        # compiled executable and can never be hit again.
        for k in [k for k in built if k[2] != version]:
            del built[k]
        fn = built.get(cache_key)
        if fn is None:
            batch_spec = jax.tree_util.tree_unflatten(
                treedef, [_batch_leaf_spec(l) for l in leaves]
            )
            if powersgd_rank is not None:
                # pytree-prefix spec: replicated warm-start factors,
                # per-device residual rows on the leading device dim
                state_spec = PowerSGDState(qs=P(), es=P(sync_axes))
            elif topk_ratio is not None:
                state_spec = TopKState(es=P(sync_axes))
            else:
                state_spec = P(sync_axes)  # EF residual leaves
            with_state = (
                error_feedback
                or powersgd_rank is not None
                or topk_ratio is not None
            )
            if powersgd_rank is not None:
                body = _step_psgd
                compressor = f"powersgd(rank={powersgd_rank})"
            elif topk_ratio is not None:
                body = _step_topk
                compressor = f"topk(ratio={topk_ratio})"
            elif error_feedback:
                body = _step_ef
                compressor = "quantized+ef"
            else:
                body = _step
                compressor = "quantized"
            # Trace-time event: one per compiled train step (a retrace storm
            # shows up in the flight recorder as a run of these).
            from ..observability import flightrec, timeline

            metrics.add("cgx.trace.train_step_builds")
            if xla_route[0] == topo_router.ROUTE_STAGED:
                metrics.add("cgx.xla.train_steps_staged")
            flightrec.record(
                "train_step_trace",
                compressor=compressor,
                sync_axes=list(sync_axes),
                guard=guard,
                registry_version=version,
                xla_route=list(xla_route),
                schedule=list(sched_key),
                planner=list(planner_key),
            )
            timeline.instant(
                "train_step_trace",
                compressor=compressor,
                guard=guard,
                registry_version=version,
                xla_route=list(xla_route),
                schedule=list(sched_key),
            )
            sharded = _compat_shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    (P(), P(), state_spec, batch_spec, P())
                    if with_state
                    else (P(), P(), batch_spec, P())
                ),
                out_specs=(
                    (P(), P(), state_spec, P())
                    if with_state
                    else (P(), P(), P())
                ),
                # Only the gradient-sync (and sp) axes are manual; any other
                # mesh axis — tp, ep — stays under GSPMD control, so
                # tensor-parallel parameter shardings survive the step
                # instead of being gathered to replicated by in_specs=P()
                # (which speaks only of manual axes).
                axis_names=set(sync_axes),
                # Replication of params is guaranteed by construction (all
                # devices decode identical reduced bytes); the static
                # varying-axis analysis cannot see through the quantized
                # collective composition.
                check_vma=False,
            )
            donate_idx = ()
            if donate:
                # params, opt_state — and the EF/PowerSGD state, which is
                # param-sized f32 and would otherwise double-buffer.
                donate_idx = (0, 1, 2) if with_state else (0, 1)
            fn = jax.jit(sharded, donate_argnums=donate_idx)
            built[cache_key] = fn
        return fn

    # Recovery rollback hook: in-memory snapshots of the step INPUTS at a
    # fixed cadence, taken on the host before the jitted call (donation
    # invalidates the device buffers afterwards, so the copy must happen
    # here). Holder is shared by both signatures below.
    snap_every = (
        snapshot_every if snapshot_every is not None
        else cfg_mod.snapshot_every()
    )
    snap_holder = {"snap": None, "outer": None}

    def _maybe_snapshot(step_idx, tree) -> None:
        if not snap_every:
            return
        idx = int(step_idx)
        if idx % snap_every == 0:
            from .. import checkpoint as ckpt

            snap_holder["snap"] = ckpt.snapshot_in_memory(tree, idx)
            # The async plane's outer state (anchor, EF, momentum,
            # round, generation) is part of the rollback point: a
            # replay against the crash-time anchor would compute wrong
            # deltas and re-post advanced rounds (docs/ROBUSTNESS.md
            # "Async recovery semantics").
            snap_holder["outer"] = (
                outer.export_state() if outer is not None else None
            )
            metrics.add("cgx.recovery.snapshots")

    # Live health plane: step cadence measured host-side, dispatch to
    # dispatch — under steady async pipelining the inter-call gap IS the
    # step time (blocking on the result would serialize the pipeline).
    # The histogram feeds cgx_top's step rate and the health engine's
    # regression detector; pure host bookkeeping, nothing staged changes.
    from ..observability import health as health_mod
    from ..observability import memledger as memledger_mod
    from ..observability import watch as watch_mod

    # process_index, not 0: on the multi-process JAX path this is the
    # authoritative rank, and pinning 0 here would make every process
    # write the same health-rank0 files on a shared metrics dir. A
    # torch-bridge process that builds the step fn before dist init
    # still gets rebound when ProcessGroupCGX passes the real rank.
    _rank_hint = jax.process_index()
    health_mod.maybe_start(_rank_hint)
    memledger_mod.maybe_start(_rank_hint)
    watch_mod.maybe_start_prom(_rank_hint)
    step_clock = {"t": None}

    def _note_step_cadence() -> None:
        t_now = time.perf_counter()
        prev, step_clock["t"] = step_clock["t"], t_now
        if prev is not None:
            dt = t_now - prev
            metrics.observe("cgx.step.time_s", dt)
            health_mod.note_step(dt)
            # Step boundary marker for the critical-path engine: window
            # segmentation prefers these over collective-round ends.
            from ..observability import timeline as timeline_mod

            timeline_mod.instant(
                "step", cat=timeline_mod.CAT_TRACE, dt_s=round(dt, 6)
            )
        metrics.add("cgx.step.count")

    def _apply_outer(step_idx, params):
        """PR 13 outer hook: host-side local-SGD boundary on the updated
        params. The flatten (a full device→host param copy) runs ONLY
        when the plane would actually act this step
        (``AsyncPlane.wants_params`` — knob off, disengaged, and
        non-boundary steps all skip it; non-boundary drains happen
        inside the gate and need no params)."""
        if outer is None or not outer.wants_params(int(step_idx)):
            return params
        from . import async_plane as async_mod

        flat, unflatten = async_mod.flatten_tree(params)
        new_flat = outer.maybe_outer_step(int(step_idx), flat)
        if new_flat is flat:
            return params
        return unflatten(new_flat)

    if error_feedback or powersgd_rank is not None or topk_ratio is not None:

        def step(params, opt_state, state, batch, step_idx):
            _note_step_cadence()
            _maybe_snapshot(step_idx, (params, opt_state, state))
            new_p, new_opt, new_state, loss = _build(batch)(
                params, opt_state, state, batch, step_idx
            )
            return _apply_outer(step_idx, new_p), new_opt, new_state, loss

    else:

        def step(params, opt_state, batch, step_idx):
            _note_step_cadence()
            _maybe_snapshot(step_idx, (params, opt_state))
            new_p, new_opt, loss = _build(batch)(
                params, opt_state, batch, step_idx
            )
            return _apply_outer(step_idx, new_p), new_opt, loss

    def last_snapshot():
        """The most recent in-memory snapshot (``checkpoint.
        MemorySnapshot`` of the step's input tree), or None."""
        return snap_holder["snap"]

    def rollback():
        """(step_idx, input tree) restored from the last snapshot —
        registry snapshot re-installed, and the attached async plane's
        outer state restored alongside (the replay must see the
        snapshot-time anchor/EF/momentum, not the crash-time ones);
        None when no snapshot exists."""
        snap = snap_holder["snap"]
        if snap is None:
            return None
        from .. import checkpoint as ckpt

        if outer is not None:
            outer.restore_state(snap_holder.get("outer"))
        metrics.add("cgx.recovery.rollbacks")
        return snap.step, ckpt.restore_in_memory(snap)

    def lower(*args):
        """``jax.stages.Lowered`` of the jitted step for ``step``'s own
        arguments: what an entry script reads to show what was staged
        (Mosaic custom calls, collectives) — lowering only, nothing is
        compiled, run or donated."""
        return _build(args[-2]).lower(*args)

    step.last_snapshot = last_snapshot
    step.rollback = rollback
    step.lower = lower
    return step


def init_error_feedback(
    params,
    mesh,
    axes: Sequence[str] = (mesh_mod.DP_AXIS,),
    sp_axis: Optional[str] = None,
):
    """Zero-initialized per-device EF residuals for
    :func:`make_train_step` ``(error_feedback=True)``: each leaf is
    ``(ws, *param.shape)`` f32, sharded over the sync axes on the leading
    device dim so every device owns exactly its own residual row."""
    from jax.sharding import NamedSharding

    sync_axes = tuple(axes) if sp_axis is None else tuple(axes) + (sp_axis,)
    ws = int(np.prod([mesh.shape[a] for a in sync_axes]))
    z = jax.tree.map(
        lambda p: jnp.zeros((ws,) + p.shape, jnp.float32), params
    )
    return jax.device_put(z, NamedSharding(mesh, P(sync_axes)))


def replicate(tree, mesh):
    """Place a pytree fully-replicated on the mesh."""
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_batch(
    batch,
    mesh,
    axes: Sequence[str] = (mesh_mod.DP_AXIS,),
    sp_axis: Optional[str] = None,
):
    """Shard batch leaves along their leading dimension over ``axes`` (and,
    with ``sp_axis``, the second — sequence — dimension of rank >= 2 leaves
    over that axis; rank-1 leaves have no sequence dim and replicate over
    sp).

    Multi-host: each process passes its *local* slice and JAX assembles the
    global array (``make_array_from_process_local_data``) — no host ever
    materializes the global batch.
    """
    from jax.sharding import NamedSharding

    axes = tuple(axes)
    ws = int(np.prod([mesh.shape[a] for a in axes]))
    # Multi-host: each process contributes only its local slice, so the
    # divisibility requirement is the per-process device count along the dp
    # axes, not the global extent.
    procs = jax.process_count()
    local_ws = ws // procs if procs > 1 and ws % procs == 0 else ws

    def place(x):
        if hasattr(x, "shape") and x.shape and x.shape[0] % local_ws:
            raise ValueError(
                f"local batch leading dim {x.shape[0]} not divisible by the "
                f"per-process data-parallel extent {local_ws} (global mesh "
                f"{ws}, {procs} processes; drop or pad the remainder "
                "batch; see data.iterate_batches(drop_remainder=True))"
            )
        # Rank-1 leaves (sample weights, per-sequence labels) have no
        # sequence dim — they shard over dp only and replicate over sp.
        sp = sp_axis if getattr(x, "ndim", 0) >= 2 else None
        sharding = NamedSharding(mesh, P(axes) if sp is None else P(axes, sp))
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x)
            )
        return jax.device_put(x, sharding)

    return jax.tree.map(place, batch)
