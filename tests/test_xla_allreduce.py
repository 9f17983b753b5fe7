"""In-XLA single-program quantized allreduce + topology router (ISSUE 8).

Covers the staged-program entry (``parallel/xla_allreduce.py``), the
topology router (``parallel/topology.py``), the staged<->bridge wire
parity contract (stage-1 frames bit-identical on any data; the full
exchange bit-identical on decode-exact data — the residual random-data
stage-2 gap is the documented host-vs-XLA decode ulp, codec_host.py), the
staged-purity jaxpr guard (zero host callbacks, exactly one
quantize/epilogue kernel pair per shard), the size-aware fused-epilogue
selection, and the routing components of the layout/trace caches.
"""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_cgx_tpu import config as cgx_config
from torch_cgx_tpu.config import CompressionConfig
from torch_cgx_tpu.ops import codec as codec_mod
from torch_cgx_tpu.ops import dispatch
from torch_cgx_tpu.parallel import mesh as mesh_mod
from torch_cgx_tpu.parallel import reducers, topology, xla_allreduce
from torch_cgx_tpu.utils.compat import shard_map

WS = 8


def _flat_mesh():
    return mesh_mod.flat_mesh()


def run_flat(per_rank: np.ndarray, fn, ws=WS):
    mesh = Mesh(np.asarray(jax.devices()[:ws]), ("dp",))
    body = shard_map(
        lambda x: fn(x[0])[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )
    arr = jax.device_put(
        jnp.asarray(per_rank), NamedSharding(mesh, P("dp"))
    )
    return np.asarray(jax.jit(body)(arr))


# ---------------------------------------------------------------------------
# Topology classification + routing.
# ---------------------------------------------------------------------------


def test_classify_slice_ids_taxonomy():
    c = topology.classify_slice_ids
    assert c([0]).kind == topology.TOPO_SINGLE
    assert c([3, 3, 3, 3]).kind == topology.TOPO_INTRA
    assert c([0, 1, 2, 3]).kind == topology.TOPO_CROSS
    t = c([0, 0, 1, 1, 1])
    assert t.kind == topology.TOPO_MIXED
    assert t.n_slices == 2 and t.max_per_slice == 3 and t.ws == 5


def test_classify_hosts_matches_bridge_classifier():
    """The bridge keeps a dependency-light duplicate of the router's
    taxonomy (it must not import the parallel package into every rank
    process); the two classifiers must agree on every host map."""
    from torch_cgx_tpu.torch_backend import backend as be

    cases = [
        ["a"], ["a", "a"], ["a", "b"], ["a", "a", "b"],
        ["a", "b", "c"], ["x", "y", "x", "y"], ["h"] * 6,
        ["a", "b", "b", "c", "c", "c"],
    ]
    for hosts in cases:
        assert be._host_topology(hosts) == topology.classify_hosts(hosts).kind, hosts


def _stub_mesh(slice_ids, axis_names=("dp",)):
    devs = np.asarray(
        [SimpleNamespace(slice_index=s, process_index=0, id=i)
         for i, s in enumerate(slice_ids)],
        dtype=object,
    )
    return SimpleNamespace(
        devices=devs.reshape([len(slice_ids)]), axis_names=axis_names
    )


def test_classify_mesh_axes_stub_devices():
    m = _stub_mesh([0, 0, 0, 0])
    assert topology.classify_mesh_axes(m, ("dp",)).kind == topology.TOPO_INTRA
    m = _stub_mesh([0, 1, 2, 3])
    assert topology.classify_mesh_axes(m, ("dp",)).kind == topology.TOPO_CROSS
    m = _stub_mesh([0, 0, 1, 1])
    t = topology.classify_mesh_axes(m, ("dp",))
    assert t.kind == topology.TOPO_MIXED and t.n_slices == 2
    # 2-axis mesh: the intra axis groups are intra-slice
    devs = np.asarray(
        [[SimpleNamespace(slice_index=r, process_index=0, id=r * 2 + c)
          for c in range(2)] for r in range(2)],
        dtype=object,
    )
    m2 = SimpleNamespace(devices=devs, axis_names=("cross", "intra"))
    assert (
        topology.classify_mesh_axes(m2, ("intra",)).kind == topology.TOPO_INTRA
    )
    assert (
        topology.classify_mesh_axes(m2, ("cross",)).kind == topology.TOPO_CROSS
    )
    assert (
        topology.classify_mesh_axes(m2, ("cross", "intra")).kind
        == topology.TOPO_MIXED
    )


def test_route_knob_gates(monkeypatch):
    m = _stub_mesh([0, 0, 0, 0])
    # default (auto) on the CPU backend: inert — UNROUTED
    d = topology.route(m, ("dp",))
    assert d.route == topology.ROUTE_UNROUTED
    # off: never routed, even "on TPU"
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "off")
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    assert topology.route(m, ("dp",)).route == topology.ROUTE_UNROUTED
    # auto + TPU backend: staged for intra-slice
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "auto")
    assert topology.route(m, ("dp",)).route == topology.ROUTE_STAGED
    # on: staged anywhere
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: False)
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    assert topology.route(m, ("dp",)).route == topology.ROUTE_STAGED
    # cross-slice stays on the bridge path
    assert (
        topology.route(_stub_mesh([0, 1, 2, 3]), ("dp",)).route
        == topology.ROUTE_BRIDGE
    )


def test_route_mixed_two_level_requires_on(monkeypatch):
    m = _stub_mesh([0, 0, 1, 1])
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "auto")
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    # auto promises bit-identity -> mixed stays unrouted
    assert topology.route(m, ("dp",)).route == topology.ROUTE_UNROUTED
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    # a 1-axis caller inside shard_map cannot build the (cross, intra)
    # grid -> UNROUTED so telemetry/cache keys report the path that runs;
    # only a re-meshing caller (eager staged_allreduce) engages two-level
    d = topology.route(m, ("dp",))
    assert d.route == topology.ROUTE_UNROUTED and "re-mesh" in d.reason
    assert (
        topology.route(m, ("dp",), allow_remesh=True).route
        == topology.ROUTE_TWO_LEVEL
    )
    # a 2-axis (cross, intra) call engages it in-program
    devs = np.asarray(
        [[SimpleNamespace(slice_index=r, process_index=0, id=r * 2 + c)
          for c in range(2)] for r in range(2)],
        dtype=object,
    )
    m2 = SimpleNamespace(devices=devs, axis_names=("cross", "intra"))
    assert (
        topology.route(m2, ("cross", "intra")).route
        == topology.ROUTE_TWO_LEVEL
    )


def test_two_level_config_override():
    base = cgx_config.TopologyConfig(
        intra_reduction="SRA", cross_reduction="RING",
        intra_broadcast=False, intra_compress=True, cross_compress=True,
    )
    tl = topology.two_level_config(base)
    assert not tl.intra_compress  # ICI rides uncompressed
    assert tl.cross_compress  # only the cross exchange is quantized
    assert tl.intra_broadcast  # the leader scheme (psum_scatter form)
    assert tl.cross_reduction == "RING"


# ---------------------------------------------------------------------------
# Staged program: results, cache, purity.
# ---------------------------------------------------------------------------


def test_staged_allreduce_matches_flat_reducer(monkeypatch):
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    cc = CompressionConfig(bits=4, bucket_size=512)
    n = 4096
    rng = np.random.default_rng(7)
    per = rng.standard_normal((WS, n)).astype(np.float32)
    ref = run_flat(
        per, lambda x: reducers.quantized_allreduce(x, "dp", WS, cc, "SRA")
    )
    out = np.asarray(
        xla_allreduce.staged_allreduce(per, mesh=_flat_mesh(), cc=cc)
    )
    np.testing.assert_array_equal(out, ref)
    # error symmetry: every row identical
    assert np.unique(out, axis=0).shape[0] == 1


def test_staged_allreduce_constant_exact(monkeypatch):
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.stack(
        [np.full((1000,), r + 1, np.float32) for r in range(WS)]
    )
    out = np.asarray(
        xla_allreduce.staged_allreduce(per, mesh=_flat_mesh(), cc=cc)
    )
    np.testing.assert_array_equal(
        out[0], np.full((1000,), WS * (WS + 1) // 2, np.float32)
    )


def test_staged_allreduce_program_cache(monkeypatch):
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    xla_allreduce.program_cache_clear()
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.ones((WS, 2048), np.float32)
    xla_allreduce.staged_allreduce(per, mesh=_flat_mesh(), cc=cc)
    assert xla_allreduce.program_cache_stats() == {"hits": 0, "misses": 1}
    xla_allreduce.staged_allreduce(per, mesh=_flat_mesh(), cc=cc)
    assert xla_allreduce.program_cache_stats() == {"hits": 1, "misses": 1}
    # a different payload shape is a different compiled program
    xla_allreduce.staged_allreduce(
        np.ones((WS, 4096), np.float32), mesh=_flat_mesh(), cc=cc
    )
    assert xla_allreduce.program_cache_stats() == {"hits": 1, "misses": 2}


def test_program_cache_env_flip_compiles_fresh(monkeypatch):
    """A trace-time env knob flip between eager calls must MISS the
    program cache — the compiled program baked the old knob in, and
    serving it would silently run the pre-flip configuration."""
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    xla_allreduce.program_cache_clear()
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.asarray(
        np.random.default_rng(3).standard_normal((WS, 2048)), np.float32
    )
    m = _flat_mesh()
    a = np.asarray(xla_allreduce.staged_allreduce(per, mesh=m, cc=cc))
    monkeypatch.setenv("CGX_DEBUG_DUMMY_COMPRESSION", "1")
    b = np.asarray(xla_allreduce.staged_allreduce(per, mesh=m, cc=cc))
    assert xla_allreduce.program_cache_stats()["misses"] == 2
    exact = per.sum(axis=0)
    np.testing.assert_allclose(b[0], exact, atol=1e-4)  # dummy: exact wire
    assert not np.allclose(a[0], exact, atol=1e-4)  # 4-bit wire differs
    # flip back: the original program's key hits again, bit-identical
    monkeypatch.delenv("CGX_DEBUG_DUMMY_COMPRESSION")
    c = np.asarray(xla_allreduce.staged_allreduce(per, mesh=m, cc=cc))
    stats = xla_allreduce.program_cache_stats()
    assert stats["misses"] == 2 and stats["hits"] >= 1
    np.testing.assert_array_equal(a, c)


def test_staged_wire_frames_program_cached(monkeypatch):
    """staged_wire_frames rides the same bounded program cache — a second
    identical call must not retrace/recompile."""
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    xla_allreduce.program_cache_clear()
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.ones((WS, 2048), np.float32)
    m = _flat_mesh()
    first = xla_allreduce.staged_wire_frames(per, mesh=m, cc=cc)
    assert xla_allreduce.program_cache_stats()["misses"] == 1
    second = xla_allreduce.staged_wire_frames(per, mesh=m, cc=cc)
    stats = xla_allreduce.program_cache_stats()
    assert stats == {"hits": 1, "misses": 1}
    for x, y in zip(first, second):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_staged_two_level_mixed_executes(monkeypatch):
    """A MIXED group under CGX_XLA_ALLREDUCE=on runs the reference
    two-level program (uncompressed ICI intra + compressed cross) on the
    real virtual devices — slice ids faked by id parity."""
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    monkeypatch.setattr(
        topology, "device_slice_id", lambda d: getattr(d, "id", 0) % 2
    )
    xla_allreduce.program_cache_clear()
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.stack(
        [np.full((2048,), r + 1, np.float32) for r in range(WS)]
    )
    m = _flat_mesh()
    assert (
        topology.route(m, ("dp",), allow_remesh=True).route
        == topology.ROUTE_TWO_LEVEL
    )
    out = np.asarray(xla_allreduce.staged_allreduce(per, mesh=m, cc=cc))
    np.testing.assert_array_equal(
        out, np.full((WS, 2048), WS * (WS + 1) // 2, np.float32)
    )


def _walk_jaxpr(jx, visit):
    for eqn in jx.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for item in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(item, jax.extend.core.ClosedJaxpr):
                    _walk_jaxpr(item.jaxpr, visit)
                elif isinstance(item, jax.extend.core.Jaxpr):
                    _walk_jaxpr(item, visit)


def _staged_jaxpr(ws, n, cc):
    mesh = Mesh(np.asarray(jax.devices()[:ws]), ("dp",))
    body = shard_map(
        lambda x: xla_allreduce.staged_quantized_allreduce(
            x[0], "dp", ws, cc
        )[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )
    return jax.make_jaxpr(body)(jnp.zeros((ws, n), jnp.float32)).jaxpr


def test_staged_program_zero_host_callbacks(monkeypatch):
    """The staged-purity acceptance guard: even with every runtime
    observability knob armed, the staged program stages NO host callback
    — the host hop is exactly what it exists to remove."""
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    monkeypatch.setenv("CGX_METRICS_RUNTIME", "1")
    monkeypatch.setenv("CGX_QERR_STATS", "1")
    cc = CompressionConfig(bits=4, bucket_size=512)
    prims = set()
    _walk_jaxpr(
        _staged_jaxpr(4, 4096, cc), lambda e: prims.add(e.primitive.name)
    )
    bad = [p for p in prims if "callback" in p]
    assert not bad, f"host callbacks staged into the pure program: {bad}"


def test_staged_program_one_kernel_pair_per_shard(monkeypatch):
    """Exactly ONE quantize kernel + ONE fused epilogue kernel per shard
    (plus the single allgather decode) — the PR 4 codec-invocation
    contract holds through the staged entry point."""
    from collections import Counter

    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "fused")
    ws, b = 4, 128
    n = ws * 2 * codec_mod.CHUNK_BUCKETS * b
    cc = CompressionConfig(bits=4, bucket_size=b)
    counts = Counter()

    def visit(eqn):
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1

    _walk_jaxpr(_staged_jaxpr(ws, n, cc), visit)
    assert counts.get("cgx_quantize_flat", 0) == 1, counts
    assert counts.get("cgx_sra_epilogue", 0) == 1, counts
    assert counts.get("cgx_dequantize_flat", 0) == 1, counts
    assert sum(counts.values()) == 3, counts


# ---------------------------------------------------------------------------
# Staged <-> bridge wire parity (the compressed-exchange contract).
# ---------------------------------------------------------------------------


def _bridge_sra(per_rank: np.ndarray, cc: CompressionConfig):
    """The host bridge's SRA data path on ``per_rank`` inputs, executed
    in-process through the backend's OWN frame/fold functions (the same
    code a live ProcessGroupCGX rank runs, minus the shm/store hop).
    Returns (outputs (ws, n), stage1 frames {(src, dst): bytes},
    stage2 frames [bytes per rank])."""
    from torch_cgx_tpu.torch_backend import backend as be

    ws, n = per_rank.shape
    layers = [(0, n, cc)]
    sizes, offs = be._chunk_split(n, ws, layers)
    segs = [
        be._segments_in(layers, offs[r], offs[r] + sizes[r])
        for r in range(ws)
    ]
    fused = [per_rank[r].copy() for r in range(ws)]
    stage1 = {
        (s, d): be._compress_frames(fused[s], segs[d], False, None)
        for s in range(ws) for d in range(ws) if s != d
    }
    for r in range(ws):
        frames = {
            j: np.frombuffer(stage1[(j, r)], np.uint8)
            for j in range(ws) if j != r
        }
        be._sra_fold_chunk(
            fused[r], offs[r], offs[r] + sizes[r], segs[r], frames, r, ws,
            False,
        )
    stage2 = [
        be._requantize_frames(fused[r], segs[r], False, None)
        for r in range(ws)
    ]
    for r in range(ws):
        for j in range(ws):
            if j != r:
                be._decompress_frames(
                    np.frombuffer(stage2[j], np.uint8), segs[j], fused[r],
                    False, add=False,
                )
    return np.stack(fused), stage1, stage2


def _staged_frames(per_rank, cc, ws):
    mesh = Mesh(np.asarray(jax.devices()[:ws]), ("dp",))
    out, p1, m1, p2, m2 = xla_allreduce.staged_wire_frames(
        per_rank, mesh=mesh, cc=cc
    )
    return tuple(
        np.ascontiguousarray(np.asarray(a)) for a in (out, p1, m1, p2, m2)
    )


def _frame_bytes(meta, packed):
    return np.concatenate([
        np.ascontiguousarray(meta).reshape(-1).view(np.uint8),
        np.ascontiguousarray(packed).reshape(-1).view(np.uint8),
    ])


def test_staged_vs_bridge_full_wire_parity_exact_grid():
    """On decode-exact data (integer grid: unit and min exact, decode
    free of the host-vs-XLA fma ulp) EVERY wire byte of the compressed
    exchange — all ws*(ws-1) stage-1 frames and all ws stage-2 frames —
    is bit-identical between the staged program and the bridge SRA path,
    and the outputs agree bit-exactly end to end."""
    ws, bucket = 4, 512
    n = ws * 2048
    cc = CompressionConfig(bits=4, bucket_size=bucket)
    per = np.stack(
        [np.float32((np.arange(n) * (r + 3)) % 16) for r in range(ws)]
    )
    bridge_out, stage1, stage2 = _bridge_sra(per, cc)
    out, p1, m1, p2, m2 = _staged_frames(per, cc, ws)
    for s in range(ws):
        for d in range(ws):
            if s == d:
                continue
            np.testing.assert_array_equal(
                np.frombuffer(stage1[(s, d)], np.uint8),
                _frame_bytes(m1[s, d], p1[s, d]),
                err_msg=f"stage-1 frame {s}->{d}",
            )
    for r in range(ws):
        np.testing.assert_array_equal(
            np.frombuffer(stage2[r], np.uint8),
            _frame_bytes(m2[r], p2[r]),
            err_msg=f"stage-2 frame of rank {r}",
        )
    np.testing.assert_array_equal(out, bridge_out)


def test_staged_vs_bridge_stage1_parity_random():
    """On arbitrary data the stage-1 exchange (quantize of RAW chunks —
    no accumulate in the way) is bit-identical; end-to-end results agree
    within the documented host-vs-XLA decode ulp (codec_host.py: the
    host codec rounds unit*level before adding, XLA may fuse the fma —
    which can shift a requantized stage-2 byte by one level)."""
    ws, bucket = 4, 512
    n = ws * 2048
    cc = CompressionConfig(bits=4, bucket_size=bucket)
    per = np.random.default_rng(3).standard_normal((ws, n)).astype(
        np.float32
    )
    bridge_out, stage1, _ = _bridge_sra(per, cc)
    out, p1, m1, _, _ = _staged_frames(per, cc, ws)
    for s in range(ws):
        for d in range(ws):
            if s == d:
                continue
            np.testing.assert_array_equal(
                np.frombuffer(stage1[(s, d)], np.uint8),
                _frame_bytes(m1[s, d], p1[s, d]),
                err_msg=f"stage-1 frame {s}->{d}",
            )
    np.testing.assert_allclose(out, bridge_out, atol=2e-5, rtol=1e-5)


def test_bridge_fold_order_pinned():
    """The bridge's stage-1 accumulate association is the dispatcher's
    ``ordered_rowsum`` fold (v0 + v1 + ... ascending, raw own chunk at
    its rank position) — NOT the old own-chunk-first in-place add, which
    differs by a last ulp for me >= 2. Uses association-sensitive values
    through the dummy (exact-decode) codec so ONLY the fold order is
    measured."""
    from torch_cgx_tpu.torch_backend import backend as be

    n, ws, me = 32, 4, 2
    big = np.float32(2.0 ** 24)
    rows = np.stack([
        np.full((n,), big, np.float32),
        np.full((n,), 1.0, np.float32),
        np.full((n,), -big, np.float32),  # the raw own chunk
        np.full((n,), 1.0, np.float32),
    ])
    segs = [be._Segment(0, n, 4, 512)]
    frames = {
        j: np.ascontiguousarray(rows[j]).view(np.uint8)
        for j in range(ws) if j != me
    }
    fused = rows[me].copy()
    be._sra_fold_chunk(fused, 0, n, segs, frames, me, ws, dummy=True)
    # ascending fold: ((big + 1) + -big) + 1 = 1.0 (big+1 rounds to big)
    expect = np.asarray(
        dispatch.ordered_rowsum(jnp.asarray(rows))
    )
    np.testing.assert_array_equal(fused, expect)
    np.testing.assert_array_equal(fused, np.full((n,), 1.0, np.float32))
    # the OLD own-first association would have produced 2.0 — the fold
    # orders are genuinely distinguishable on this data
    own_first = rows[me].copy()
    for j in range(ws):
        if j != me:
            own_first = own_first + rows[j]
    np.testing.assert_array_equal(own_first, np.full((n,), 2.0, np.float32))


# ---------------------------------------------------------------------------
# Size-aware fused-epilogue selection (the BENCH_LOG small-chunk fix).
# ---------------------------------------------------------------------------


def _reduce_capable_q(rows: int, chunks: int = 2, bucket: int = 128):
    n = chunks * codec_mod.CHUNK_BUCKETS * bucket
    cc = CompressionConfig(bits=4, bucket_size=bucket)
    xs = jnp.asarray(
        np.random.default_rng(0).standard_normal((rows, n)), jnp.float32
    )
    return dispatch.quantize_batch(xs, cc, None)


def test_fused_epilogue_size_threshold(monkeypatch):
    from torch_cgx_tpu.ops import codec_pallas

    q = _reduce_capable_q(rows=4)  # 4 * 8192 = 32768 decoded elements
    assert codec_pallas.supports_reduce(q)
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    # auto + payload below the default 2^20 floor -> staged
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "auto")
    assert not dispatch.fused_epilogue_would_run(q)
    # floor lowered below the payload -> fused
    monkeypatch.setenv("CGX_SRA_EPILOGUE_MIN_ELEMS", "1024")
    assert dispatch.fused_epilogue_would_run(q)
    # floor raised above it -> staged again (the crossover knob)
    monkeypatch.setenv("CGX_SRA_EPILOGUE_MIN_ELEMS", str(1 << 22))
    assert not dispatch.fused_epilogue_would_run(q)
    # "fused" forces the kernel at ANY size (test/bench knob)
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "fused")
    assert dispatch.fused_epilogue_would_run(q)
    # "staged" forces it off at any size
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "staged")
    monkeypatch.setenv("CGX_SRA_EPILOGUE_MIN_ELEMS", "1")
    assert not dispatch.fused_epilogue_would_run(q)


def test_fused_epilogue_threshold_default_covers_bench_regression(
    monkeypatch,
):
    """The exact BENCH_LOG regression shape (1 MB payload over 8 ranks =
    2^18 decoded elements, fused 6.5 ms vs staged 1.0 ms) now selects
    STAGED under auto; the 512 MB winner shape still selects fused."""
    small = _reduce_capable_q(rows=8, chunks=8)  # 8 * 32768 = 2^18
    big = _reduce_capable_q(rows=8, chunks=64)  # 8 * 2^18 = 2^21
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "auto")
    assert small.batch_rows * small.numel == 1 << 18
    assert not dispatch.fused_epilogue_would_run(small)
    assert dispatch.fused_epilogue_would_run(big)


# ---------------------------------------------------------------------------
# Cache keys + grad_sync integration + observability.
# ---------------------------------------------------------------------------


def test_layout_cache_keys_on_route(monkeypatch):
    from torch_cgx_tpu.parallel import allreduce as ar

    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _flat_mesh()
    tree = {"w": np.ones((WS, 64, 8), np.float32)}

    def _sync(t):
        reduced = ar.allreduce_tree(
            jax.tree.map(lambda l: l[0], t), mesh=mesh, axes=("dp",)
        )
        return jax.tree.map(lambda l: l[None], reduced)

    def trace():
        body = shard_map(
            _sync, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        jax.make_jaxpr(body)(tree)

    ar.layout_cache_clear()
    trace()
    trace()
    stats = ar.layout_cache_stats()
    assert stats == {"hits": 1, "misses": 1}
    # flipping the routing knob must derive a fresh plan, not hit stale
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    trace()
    stats = ar.layout_cache_stats()
    assert stats["misses"] == 2, stats


def test_grad_sync_bit_identical_with_knob_on(monkeypatch):
    """CGX_XLA_ALLREDUCE=on re-routes intra-slice slices through the
    staged wrappers — same composition, same wire bytes: the synced
    gradients are bit-identical to the knob-unset run (the acceptance
    'results matching the bridge path' at the gradient level)."""
    from torch_cgx_tpu.parallel import gradient_sync

    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _flat_mesh()
    rng = np.random.default_rng(11)
    grads = {
        "w": rng.standard_normal((WS, 32, 16)).astype(np.float32),
        "b": rng.standard_normal((WS, 40)).astype(np.float32),
    }

    def run():
        body = shard_map(
            lambda t: jax.tree.map(
                lambda l: l[None],
                gradient_sync(
                    jax.tree.map(lambda l: l[0], t), mesh=mesh, axes=("dp",)
                ),
            ),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        )
        arr = jax.device_put(
            jax.tree.map(jnp.asarray, grads),
            NamedSharding(mesh, P("dp")),
        )
        return jax.tree.map(np.asarray, jax.jit(body)(arr))

    base = run()
    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    routed = run()
    jax.tree.map(np.testing.assert_array_equal, base, routed)


def test_staged_observability(monkeypatch, tmp_path):
    """Staged calls emit the CAT_COLLECTIVE trace instant + cgx.xla.*
    counters (the bridge's timeline spans vanish for staged traffic —
    this is what keeps cgx_trace/cgx_top attribution truthful)."""
    from torch_cgx_tpu.observability import timeline
    from torch_cgx_tpu.utils.logging import metrics

    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.reset()
    xla_allreduce.program_cache_clear()
    before = metrics.get("cgx.xla.staged_calls")
    cc = CompressionConfig(bits=4, bucket_size=512)
    per = np.ones((WS, 2048), np.float32)
    xla_allreduce.staged_allreduce(per, mesh=_flat_mesh(), cc=cc)
    assert metrics.get("cgx.xla.staged_calls") == before + 1
    assert metrics.get("cgx.xla.staged_programs") >= 1
    timeline.flush()
    spans = [
        json.loads(line)
        for p in tmp_path.glob("spans-rank*.jsonl")
        for line in p.read_text().splitlines()
    ]
    inst = [
        e for e in spans
        if e.get("name") == "xla_allreduce" and e.get("kind") == "instant"
    ]
    assert inst, "no CAT_COLLECTIVE instant for the staged program"
    assert inst[0]["cat"] == timeline.CAT_COLLECTIVE
    assert inst[0]["route"] == topology.ROUTE_STAGED
    timeline.reset()
