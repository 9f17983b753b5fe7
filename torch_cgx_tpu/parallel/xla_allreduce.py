"""In-XLA single-program quantized allreduce (EQuARX-style) with a
compiled-program cache (GC3-style).

The production compressed-allreduce path used to stage every gradient
through the host bridge (shm/store) even when all ranks live on the same
slice — host round-trips XLA can neither schedule nor overlap. For
intra-slice traffic this module compiles the WHOLE compressed allreduce

    Pallas quantize  ->  ``lax.all_to_all`` chunk exchange (SRA; the
    ``ppermute``-ring for the RING variant)  ->  fused
    dequant-accumulate-requantize epilogue (PR 4)  ->  ``lax.all_gather``
    + decode

into **one staged XLA program** under ``shard_map`` on the ICI mesh: no
``io_callback``, no bridge hop, nothing the XLA scheduler cannot see.
EQuARX (arxiv 2506.17615) measures a quantized allreduce expressed
natively inside XLA at ~2x at no quality loss; GC3 (arxiv 2201.11840)
motivates treating the result as a compiled, cacheable program — hence
the bounded program LRU here, keyed on (payload, dtype, config, mesh,
route), mirroring the layout LRU of ``allreduce.py``.

Which traffic comes here is decided by the topology router
(``parallel/topology.py``): intra-slice groups -> the staged program;
cross-slice groups -> the existing compressed DCN/bridge path (the
bridge's end-state role); mixed groups -> the reference's two-level
scheme (uncompressed ICI intra via ``lax.psum_scatter``/``all_gather``,
compressed cross-slice exchange via the slice leaders —
``reducers.hierarchical_allreduce`` + ``topology.two_level_config``).

Observability: staged calls never cross the host, so the bridge's
timeline spans vanish for them. The module instead emits a trace-time
``CAT_COLLECTIVE`` instant per compiled program plus ``cgx.xla.*``
counters (programs built, cache hits/misses, eager calls, routed slices)
so ``cgx_trace``/``cgx_top`` attribution stays truthful.

**Staged purity contract**: this module and everything it lists in
:data:`STAGED_PURE` must never import ``io_callback``/``pure_callback``
— a host callback inside the staged program would silently reintroduce
the host hop this module exists to remove. ``tools/lint.py`` enforces
the list; ``tests/test_xla_allreduce.py`` additionally walks the built
jaxpr asserting zero callback primitives and exactly one
quantize/epilogue kernel pair per shard.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import config as cfg_mod
from ..config import CompressionConfig
from ..observability import timeline
from ..utils.compat import shard_map as _compat_shard_map
from ..utils.logging import metrics
from . import mesh as mesh_mod
from . import reducers, topology

# Modules that must stay free of host-callback machinery (tools/lint.py
# parses this list — do not rename). Paths are repo-relative; the linter
# matches by trailing path components so tmp-tree test fixtures work.
STAGED_PURE = (
    "torch_cgx_tpu/parallel/xla_allreduce.py",
    "torch_cgx_tpu/parallel/topology.py",
    "torch_cgx_tpu/parallel/schedule.py",
)


# ---------------------------------------------------------------------------
# Shard-level staged bodies (usable inside any caller's shard_map — this is
# what allreduce.py routes intra-slice fusion slices to).
# ---------------------------------------------------------------------------


def _note_staged_slice(
    n: int, ws: int, cc: CompressionConfig, reduction: str, route: str
) -> None:
    """Trace-time accounting for one staged slice: counters + a
    CAT_COLLECTIVE instant. Runs while the program is being TRACED (once
    per compiled program), never at execution time — runtime hooks would
    need a host callback, which the staged program must not contain."""
    metrics.add("cgx.xla.staged_slices")
    metrics.add("cgx.xla.staged_elems", float(n))
    timeline.instant(
        "xla_allreduce",
        cat=timeline.CAT_COLLECTIVE,
        route=route,
        elems=int(n),
        ws=int(ws),
        bits=int(cc.bits),
        bucket=int(cc.bucket_size),
        reduction=reduction,
    )


def staged_quantized_allreduce(
    x: jax.Array,
    axis_name: str,
    ws: int,
    cc: CompressionConfig,
    reduction: str = cfg_mod.REDUCTION_SRA,
    key: Optional[jax.Array] = None,
    pre=None,
) -> jax.Array:
    """The staged single-program body for one intra-slice fusion slice
    (inside shard_map): the same quantize -> exchange -> fused epilogue ->
    all_gather composition as ``reducers.quantized_allreduce`` — wire
    bytes and results are bit-identical, which is what lets the router
    flip traffic onto this path without changing a single gradient — plus
    the trace-time ``cgx.xla.*`` accounting the bridge spans no longer
    cover."""
    _note_staged_slice(x.shape[0], ws, cc, reduction, topology.ROUTE_STAGED)
    return reducers.quantized_allreduce(
        x, axis_name, ws, cc, reduction, key, pre
    )


def staged_quantized_allreduce_with_wire(
    x: jax.Array,
    axis_name: str,
    ws: int,
    cc: CompressionConfig,
    reduction: str = cfg_mod.REDUCTION_SRA,
    key: Optional[jax.Array] = None,
    pre=None,
):
    """Error-feedback sibling of :func:`staged_quantized_allreduce`:
    ``(reduced, wire_decode)`` from one staged program (the wire decode
    shares the stage-1 payload — quantize-once, like the reducer it
    wraps)."""
    _note_staged_slice(x.shape[0], ws, cc, reduction, topology.ROUTE_STAGED)
    return reducers.quantized_allreduce_with_wire(
        x, axis_name, ws, cc, reduction, key, pre
    )


def staged_pipelined_allreduce(
    x: jax.Array,
    axis_name: str,
    ws: int,
    cc: CompressionConfig,
    reduction: str = cfg_mod.REDUCTION_SRA,
    key: Optional[jax.Array] = None,
    sched=None,
    pre=None,
):
    """Schedule-compiled sibling of :func:`staged_quantized_allreduce`:
    the fusion slice runs as a chunked software pipeline compiled into
    the same single staged program (``parallel/schedule.py`` — chunk k+1
    quantizes while chunk k is on the wire and chunk k-1 runs the fused
    epilogue). Same ``cgx.xla.*`` trace accounting plus the schedule's
    own ``cgx.sched.*`` counters. ``pre``: producer-staged per-block
    payloads (table pre-verified by the consumer)."""
    from . import schedule as sched_mod

    _note_staged_slice(x.shape[0], ws, cc, reduction, topology.ROUTE_STAGED)
    return sched_mod.pipelined_quantized_allreduce(
        x, axis_name, ws, cc, reduction, key, sched, pre=pre
    )


def staged_pipelined_allreduce_with_wire(
    x: jax.Array,
    axis_name: str,
    ws: int,
    cc: CompressionConfig,
    reduction: str = cfg_mod.REDUCTION_SRA,
    key: Optional[jax.Array] = None,
    sched=None,
    pre=None,
):
    """Error-feedback sibling of :func:`staged_pipelined_allreduce`:
    ``(reduced, wire_decode)``, the per-chunk wire decodes concatenated
    (quantize-once — each chunk's decode shares its stage-1 payload)."""
    from . import schedule as sched_mod

    _note_staged_slice(x.shape[0], ws, cc, reduction, topology.ROUTE_STAGED)
    return sched_mod.pipelined_quantized_allreduce(
        x, axis_name, ws, cc, reduction, key, sched, with_wire=True, pre=pre
    )


# ---------------------------------------------------------------------------
# The compiled-program cache + eager entry point.
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: "OrderedDict" = OrderedDict()
_PROGRAM_CACHE_MAX = 32
_PROGRAM_STATS = {"hits": 0, "misses": 0}


def program_cache_stats() -> Dict[str, int]:
    return dict(_PROGRAM_STATS)


def program_cache_clear() -> None:
    _PROGRAM_CACHE.clear()
    _PROGRAM_STATS.update(hits=0, misses=0)


def invalidate_program_cache(reason: str = "reconfigure") -> None:
    """World-shrink invalidation entry point, cascaded from
    ``allreduce.invalidate_layout_cache`` (and therefore
    ``supervisor.invalidate_trace_caches``). Entries keyed on the dead
    world's registry version can never hit again — but each holds a
    fully COMPILED executable, the most expensive artifact any of the
    staged caches pins, so they are dropped outright instead of aging
    out of the LRU while holding device programs live (ISSUE 14's
    invalidation-cascade pass caught this cache missing from the
    ladder its layout/schedule/plan siblings already ride)."""
    program_cache_clear()
    metrics.add("cgx.xla.program_cache_invalidations")


def _mesh_fingerprint(mesh) -> tuple:
    devs = np.asarray(mesh.devices)
    # Grid shape is part of the identity: transposed meshes over the same
    # raveled device list have different per-axis world sizes, and a
    # program compiled for one must not serve the other.
    return (
        tuple(mesh.axis_names),
        devs.shape,
        tuple(getattr(d, "id", i) for i, d in enumerate(devs.ravel())),
    )


def _trace_env_fingerprint() -> tuple:
    """Every env knob the staged body bakes in at TRACE time (codec
    lowering, encode strategy, epilogue selection, accumulation domain,
    kernel tiling/packing, debug modes): a flip of any of these between
    eager calls must compile a fresh program, never serve a stale one —
    the same discipline as allreduce's layout LRU. The PR 11 kernel knobs
    (``CGX_SRA_ACCUM``/``CGX_PALLAS_PACK``/``CGX_PALLAS_TILE_CHUNKS``)
    joined with ISSUE 14's knob→cache-key pass, which caught them
    lowering into the program body without re-keying it."""
    from ..ops import codec_pallas
    from ..utils import env as _env

    return (
        cfg_mod.codec_impl(),
        codec_pallas._encode_strategy(),
        cfg_mod.sra_epilogue(),
        cfg_mod.sra_epilogue_min_elems(),
        cfg_mod.dummy_compression(),
        cfg_mod.force_codec(),
        cfg_mod.minimal_size(),
        cfg_mod.sra_accum(),
        _env.get_optional_str_env(cfg_mod.PALLAS_PACK),
        _env.get_optional_str_env(cfg_mod.PALLAS_TILE_CHUNKS),
    )


def _program_key(
    mesh, axis, n, dtype, cc, reduction, route, with_key, kind, topo=None
):
    # ``topo``: the env-derived TopologyConfig a two-level program bakes
    # in at build time — keyed alongside the shared trace-time knobs of
    # ``_trace_env_fingerprint``.
    return (
        kind,
        _mesh_fingerprint(mesh),
        axis,
        int(n),
        np.dtype(dtype).str,
        cc,
        reduction,
        route,
        bool(with_key),
        topo,
        _trace_env_fingerprint(),
        cfg_mod.registry_version(),
    )


def _cache_get(key):
    hit = _PROGRAM_CACHE.get(key)
    if hit is not None:
        _PROGRAM_CACHE.move_to_end(key)
        _PROGRAM_STATS["hits"] += 1
        metrics.add("cgx.xla.program_cache_hits")
    return hit


def _cache_put(key, fn) -> None:
    _PROGRAM_STATS["misses"] += 1
    metrics.add("cgx.xla.program_cache_misses")
    metrics.add("cgx.xla.staged_programs")
    _PROGRAM_CACHE[key] = fn
    if len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
        _PROGRAM_CACHE.popitem(last=False)


def _build_flat_program(
    mesh, axis, ws, cc, reduction, with_key, route, sched=None, donate=False
):
    """One staged program: shard_map over ``axis``, body = the staged
    quantize -> exchange -> epilogue -> all_gather composition — the
    schedule-pipelined body when ``sched`` is given (the planner plane).
    ``donate=True`` donates the input stack (the planner's donated-buffer
    contract: the plan owns its step buffer, so the reduced output reuses
    it instead of double-buffering ws*n floats)."""

    def body(x, key):
        _note_staged_slice(x.shape[1], ws, cc, reduction, route)
        if sched is not None:
            from . import schedule as sched_mod

            return sched_mod.pipelined_quantized_allreduce(
                x[0], axis, ws, cc, reduction, key, sched
            )[None]
        return reducers.quantized_allreduce(
            x[0], axis, ws, cc, reduction, key
        )[None]

    sharded = _compat_shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,  # pallas_call has no shard_map replication rule
    )
    donate_args = (0,) if donate else ()
    if not with_key:
        return jax.jit(lambda x: sharded(x, None), donate_argnums=donate_args)
    return jax.jit(sharded, donate_argnums=donate_args)


def _two_level_permutation(flat_devices, tl_mesh) -> np.ndarray:
    """Row permutation mapping the caller's flat device order into the
    (cross, intra) grid of ``tl_mesh`` (and back via argsort)."""
    pos = {d: i for i, d in enumerate(flat_devices)}
    grid = np.asarray(tl_mesh.devices)
    return np.asarray(
        [[pos[d] for d in row] for row in grid], dtype=np.int64
    )


def _build_two_level_program(tl_mesh, ws_cross, ws_intra, cc, with_key, topo):
    """The reference two-level program for a MIXED group: uncompressed
    ICI reduce inside each slice (``lax.psum_scatter`` under the leader
    scheme), compressed cross-slice exchange between the slice leaders,
    ICI ``all_gather`` back — ``hierarchical_allreduce`` with
    ``topology.two_level_config``'s override (``topo``, resolved by the
    caller and part of the program-cache key)."""

    def body(x, key):
        n = x.shape[-1]
        metrics.add("cgx.xla.two_level_slices")
        timeline.instant(
            "xla_allreduce",
            cat=timeline.CAT_COLLECTIVE,
            route=topology.ROUTE_TWO_LEVEL,
            elems=int(n),
            ws=int(ws_cross * ws_intra),
            bits=int(cc.bits),
            bucket=int(cc.bucket_size),
            reduction=topo.cross_reduction,
        )
        out = reducers.hierarchical_allreduce(
            x[0, 0],
            intra_axis=mesh_mod.INTRA_AXIS,
            cross_axis=mesh_mod.CROSS_AXIS,
            ws_intra=ws_intra,
            ws_cross=ws_cross,
            cc=cc,
            topology=topo,
            key=key,
        )
        return out[None, None]

    sharded = _compat_shard_map(
        body,
        mesh=tl_mesh,
        in_specs=(P(mesh_mod.CROSS_AXIS, mesh_mod.INTRA_AXIS), P()),
        out_specs=P(mesh_mod.CROSS_AXIS, mesh_mod.INTRA_AXIS),
        check_vma=False,
    )
    if not with_key:
        return jax.jit(lambda x: sharded(x, None))
    return jax.jit(sharded)


def staged_allreduce(
    per_rank,
    *,
    mesh=None,
    axis: Optional[str] = None,
    cc: Optional[CompressionConfig] = None,
    reduction: Optional[str] = None,
    key: Optional[jax.Array] = None,
):
    """Eager entry point: quantized-allreduce ``per_rank`` — a
    ``(ws, n)`` stack, row r = device r's local contribution in the
    mesh's device order — through ONE compiled staged XLA program, and
    return the ``(ws, n)`` reduced stack (rows identical, the reducers'
    error-symmetry invariant).

    The topology router picks the program shape per group:

    * intra-slice -> the flat staged program (quantize -> exchange ->
      fused epilogue -> all_gather, one ``jit``);
    * mixed -> the two-level program over a (cross, intra) mesh derived
      from the devices' slice ids (uncompressed ICI + compressed cross);
    * cross-slice -> in a bridge deployment this traffic stays on the
      host bridge; a pure-JAX caller has no bridge, so the flat staged
      program runs as the fallback (counted ``cgx.xla.routed_bridge`` so
      the misrouting is visible, never silent).

    Programs are cached in a bounded LRU keyed on (payload, dtype,
    config, mesh, route) — the GC3 compiled-collective discipline; reuse
    is visible in ``cgx.xla.program_cache_hits``.
    """
    mesh = mesh if mesh is not None else mesh_mod.flat_mesh()
    axis = axis or mesh.axis_names[0]
    cc = cc or cfg_mod.default_compression_config()
    reduction = reduction or cfg_mod.topology_from_env().intra_reduction
    decision = topology.route(mesh, (axis,), allow_remesh=True)
    metrics.add("cgx.xla.staged_calls")
    metrics.add(f"cgx.xla.routed_{decision.route}")
    per_rank = jnp.asarray(per_rank)
    ws = mesh.shape[axis]
    n = per_rank.shape[-1]

    if decision.route == topology.ROUTE_TWO_LEVEL:
        flat_devices = list(np.asarray(mesh.devices).ravel())
        tl_mesh = topology.two_level_mesh(flat_devices)
        perm = _two_level_permutation(flat_devices, tl_mesh)
        tl_topo = topology.two_level_config()
        kp = _program_key(
            tl_mesh, mesh_mod.INTRA_AXIS, n, per_rank.dtype, cc,
            reduction, decision.route, key is not None, "two_level",
            topo=tl_topo,
        )
        fn = _cache_get(kp)
        if fn is None:
            fn = _build_two_level_program(
                tl_mesh, perm.shape[0], perm.shape[1], cc, key is not None,
                tl_topo,
            )
            _cache_put(kp, fn)
        arr = jnp.asarray(per_rank)[perm.reshape(-1)].reshape(
            perm.shape + (n,)
        )
        arr = jax.device_put(
            arr,
            NamedSharding(
                tl_mesh, P(mesh_mod.CROSS_AXIS, mesh_mod.INTRA_AXIS)
            ),
        )
        out = fn(arr, key) if key is not None else fn(arr)
        inv = np.argsort(perm.reshape(-1))
        return jnp.asarray(out).reshape(ws, n)[inv]

    kp = _program_key(
        mesh, axis, n, per_rank.dtype, cc, reduction, decision.route,
        key is not None, "flat",
    )
    fn = _cache_get(kp)
    if fn is None:
        fn = _build_flat_program(
            mesh, axis, ws, cc, reduction, key is not None, decision.route
        )
        _cache_put(kp, fn)
    arr = jax.device_put(per_rank, NamedSharding(mesh, P(axis)))
    return fn(arr, key) if key is not None else fn(arr)


def staged_allreduce_planned(
    per_rank,
    *,
    mesh=None,
    axis: Optional[str] = None,
    cc: Optional[CompressionConfig] = None,
    reduction: Optional[str] = None,
    key: Optional[jax.Array] = None,
):
    """Planner-staged sibling of :func:`staged_allreduce` (the
    ``planner.planned_allreduce`` entry point): the step plan's
    (chunks, bits) decision for the whole ``(ws, n)`` payload applied as
    ONE donated-buffer XLA program — the schedule-pipelined staged body
    at the plan's depth, input stack donated. Falls back to
    :func:`staged_allreduce` whenever nothing plans (planner disengaged,
    raw config, non-SRA reduction, a payload too small to split), so the
    call is always answerable. Programs ride the same bounded LRU under
    a ``"planned"`` key kind that folds in the planner's cache-key
    component — an adopted re-plan compiles a fresh program, an
    unchanged one hits."""
    from . import planner as planner_mod
    from . import schedule as sched_mod

    mesh = mesh if mesh is not None else mesh_mod.flat_mesh()
    axis = axis or mesh.axis_names[0]
    cc = cc or cfg_mod.default_compression_config()
    reduction = reduction or cfg_mod.topology_from_env().intra_reduction
    per_rank = jnp.asarray(per_rank)
    ws = mesh.shape[axis]
    n = per_rank.shape[-1]
    decision = topology.route(mesh, (axis,), allow_remesh=True)
    dec = planner_mod.decide_slice(
        n, ws, cc, reduction, route=decision.route
    )
    if dec is None:
        return staged_allreduce(
            per_rank, mesh=mesh, axis=axis, cc=cc, reduction=reduction,
            key=key,
        )
    cc_s = cc
    if cc.enabled and 1 <= dec.bits <= cfg_mod.MAX_BITS and dec.bits != cc.bits:
        cc_s = dataclasses.replace(cc, bits=dec.bits)
    sched = sched_mod.compiled_schedule(
        n, ws, cc_s, reduction=reduction,
        dtype=np.dtype(per_rank.dtype).str, route=decision.route,
        chunks=dec.chunks,
    )
    metrics.add("cgx.plan.staged_calls")
    kp = _program_key(
        mesh, axis, n, per_rank.dtype, cc_s, reduction, decision.route,
        key is not None, "planned",
        topo=(dec.chunks, planner_mod.cache_key_component()),
    )
    fn = _cache_get(kp)
    if fn is None:
        fn = _build_flat_program(
            mesh, axis, ws, cc_s, reduction, key is not None,
            decision.route, sched=sched, donate=True,
        )
        _cache_put(kp, fn)
        metrics.add("cgx.plan.staged_programs")
    arr = jax.device_put(per_rank, NamedSharding(mesh, P(axis)))
    return fn(arr, key) if key is not None else fn(arr)


def staged_wire_frames(
    per_rank,
    *,
    mesh=None,
    axis: Optional[str] = None,
    cc: Optional[CompressionConfig] = None,
    key: Optional[jax.Array] = None,
):
    """Introspection sibling of :func:`staged_allreduce` (SRA only): run
    the staged program with its wire payloads threaded out. Returns
    ``(out, q1_packed, q1_meta, q2_packed, q2_meta)`` stacked per rank —
    ``q1_*`` the (ws, chunk) stage-1 exchange payload each rank SENT,
    ``q2_*`` its requantized stage-2 allgather chunk. The parity suite
    compares these bytes against the host bridge's SRA frames
    (bit-identical on the deterministic ``div`` encode — the
    staged<->bridge wire contract, docs/COMPRESSION_GUIDE.md)."""
    mesh = mesh if mesh is not None else mesh_mod.flat_mesh()
    axis = axis or mesh.axis_names[0]
    cc = cc or cfg_mod.default_compression_config()
    per_rank = jnp.asarray(per_rank)
    ws = mesh.shape[axis]

    # Same bounded cache as the staged programs: jax.jit caches by
    # function identity, so a fresh closure per call would retrace and
    # recompile on every invocation (the parity suite and bench byte
    # pre-flights call this repeatedly on the same shapes).
    kp = _program_key(
        mesh, axis, per_rank.shape[-1], per_rank.dtype, cc,
        cfg_mod.REDUCTION_SRA, "wire", key is not None, "wire",
    )
    fn = _cache_get(kp)
    if fn is None:

        def body(x, k):
            out, q1, q2 = reducers.sra_wire_frames(x[0], axis, ws, cc, k)
            return (
                out[None], q1.packed[None], q1.meta[None],
                q2.packed[None], q2.meta[None],
            )

        sharded = _compat_shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=(P(axis),) * 5,
            check_vma=False,
        )
        fn = jax.jit(sharded)
        _cache_put(kp, fn)
    arr = jax.device_put(per_rank, NamedSharding(mesh, P(axis)))
    return fn(arr, key)
