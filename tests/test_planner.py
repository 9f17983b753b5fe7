"""Whole-step mega-schedule planner (ISSUE 12 — ``parallel/planner.py``).

Covers the cost model (calibration from synthetic span files, prediction
shape), the joint solve (production solver == brute force on small
instances), the plan LRU (keying, hit/miss accounting, invalidation
through BOTH ``allreduce.invalidate_layout_cache`` and
``supervisor.invalidate_trace_caches``), knob-off inertness (jaxpr- and
value-identity with ``CGX_PLANNER`` unset/off), idempotent re-planning
(unchanged telemetry => no version bump, no retrace), and the e2e
2-device contract: the planner's staged program is bit-equal (and
jaxpr-equal) to the equivalent static-knob run, on both the tree plane
and the eager donated-buffer plane.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_cgx_tpu import config as cgx_config
from torch_cgx_tpu.config import CompressionConfig
from torch_cgx_tpu.parallel import planner, schedule
from torch_cgx_tpu.parallel.allreduce import (
    allreduce_tree,
    invalidate_layout_cache,
)
from torch_cgx_tpu.utils.compat import shard_map

BUCKET = 512


@pytest.fixture(autouse=True)
def _fresh_planner_state():
    planner.set_cost_model(None)
    planner._PLAN_VERSION = 0
    planner.plan_cache_clear()
    schedule.schedule_cache_clear()
    yield
    planner.set_cost_model(None)
    planner._PLAN_VERSION = 0
    planner.plan_cache_clear()
    schedule.schedule_cache_clear()


def _cc(bits=4):
    return CompressionConfig(bits=bits, bucket_size=BUCKET)


# ---------------------------------------------------------------------------
# Cost model: calibration + prediction shape.
# ---------------------------------------------------------------------------


def test_cost_model_calibrates_from_synthetic_spans(tmp_path):
    """Codec spans set the rates in f32-INPUT-byte units (from their
    ``elems`` f32 counts — their ``bytes`` field is wire bytes, ~bits/32
    of the input, and must not set the rate), wire spans the link rate,
    wait spans the per-chunk overhead, and the collective/compute
    interval overlap sets overlap_frac — the same measurement cgx_trace
    attribution reports."""
    rows = [
        {"kind": "meta", "rank": 0},
        # 5e8 f32 elems in 1 s => 2.0 GB/s of f32 input; the wire-byte
        # field is ~8x smaller and must be ignored for the rate.
        {"kind": "span", "name": "codec.compress", "cat": "quantize",
         "t_mono": 0.0, "dur_s": 1.0, "elems": 5e8, "bytes": 2.5e8},
        {"kind": "span", "name": "codec.decompress", "cat": "quantize",
         "t_mono": 1.0, "dur_s": 0.5, "elems": 5e8, "bytes": 2.5e8},
        # the fused epilogue pair is not attributable to either rate
        {"kind": "span", "name": "codec.sra_epilogue", "cat": "quantize",
         "t_mono": 2.0, "dur_s": 9.0, "elems": 9e9, "bytes": 9e9},
        {"kind": "span", "name": "shm.put", "cat": "wire",
         "t_mono": 1.0, "dur_s": 1.0, "bytes": 5e8},
        {"kind": "span", "name": "shm.take.wait", "cat": "wait",
         "t_mono": 2.0, "dur_s": 0.01},
        {"kind": "span", "name": "allreduce", "cat": "collective",
         "t_mono": 0.0, "dur_s": 1.0},
        {"kind": "span", "name": "backward", "cat": "span",
         "t_mono": 0.5, "dur_s": 1.0},
        {"kind": "instant", "name": "noise", "cat": "trace",
         "t_mono": 0.1},
    ]
    path = tmp_path / "spans-rank0.jsonl"
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
        f.write('{"kind": "span", "torn tail')  # killed writer
    m = planner.CostModel.from_spans(str(tmp_path))
    assert m.quantize_gbps == pytest.approx(2.0)
    assert m.dequantize_gbps == pytest.approx(4.0)
    assert m.wire_gbps == pytest.approx(0.5)
    # mean WAIT-span duration (wire spans are rate-bearing, not overhead)
    assert m.chunk_overhead_s == pytest.approx(0.01)
    # collective [0,1) overlaps compute [0.5,1.5) for 0.5 of 1.0
    assert m.overlap_frac == pytest.approx(0.5)
    assert "codec" in m.source and "overlap" in m.source


def test_cost_model_overlap_is_per_rank(tmp_path):
    """Overlap is measured PER RANK then averaged — pooling would let
    rank B's concurrent compute blanket rank A's collectives (SPMD ranks
    share the clock, so pooled overlap is ~always ~1.0)."""
    # rank 0: collective [0,1), own compute [10,11) — zero overlap
    with open(tmp_path / "spans-rank0.jsonl", "w") as f:
        f.write(json.dumps({"kind": "span", "name": "ar", "cat": "collective",
                            "t_mono": 0.0, "dur_s": 1.0}) + "\n")
        f.write(json.dumps({"kind": "span", "name": "c", "cat": "span",
                            "t_mono": 10.0, "dur_s": 1.0}) + "\n")
    # rank 1: compute [0,1) — would fully blanket rank 0's collective
    # if intervals were pooled across ranks
    with open(tmp_path / "spans-rank1.jsonl", "w") as f:
        f.write(json.dumps({"kind": "span", "name": "c", "cat": "span",
                            "t_mono": 0.0, "dur_s": 1.0}) + "\n")
    m = planner.CostModel.from_spans(str(tmp_path))
    assert m.overlap_frac == pytest.approx(0.0)


def test_cost_model_empty_dir_keeps_defaults(tmp_path):
    m = planner.CostModel.from_spans(str(tmp_path))
    assert m == dataclasses.replace(
        planner.CostModel.default(), source=m.source
    )


def test_from_telemetry_with_nothing_to_read_is_the_default(monkeypatch):
    """No span directory, no step histogram, no async gauge."""
    from torch_cgx_tpu.utils.logging import metrics

    monkeypatch.delenv("CGX_METRICS_DIR", raising=False)
    metrics.reset()
    m = planner.CostModel.from_telemetry()
    assert m == planner.CostModel.default() and m.source == "default"


def test_from_telemetry_names_the_step_histogram_alone(monkeypatch):
    """A step histogram alone moves ``compute_s`` and nothing else."""
    from torch_cgx_tpu.utils.logging import metrics

    monkeypatch.delenv("CGX_METRICS_DIR", raising=False)
    metrics.reset()
    metrics.observe("cgx.step.time_s", 0.25)
    m = planner.CostModel.from_telemetry()
    assert m.source == "default+step_p50"
    assert m == dataclasses.replace(
        planner.CostModel.default(), compute_s=0.25, source=m.source
    )
    assert not m.calibrated


def test_predict_slice_shape():
    m = planner.CostModel.default()
    n = 1 << 22
    t1 = m.predict_slice(n, 4, 4, BUCKET, chunks=1)
    t4 = m.predict_slice(n, 4, 4, BUCKET, chunks=4)
    # pipelining a large slice hides the non-bottleneck stage
    assert t4 < t1
    # a tiny slice only pays the per-chunk overhead
    assert m.predict_slice(4096, 4, 4, BUCKET, chunks=4) > \
        m.predict_slice(4096, 4, 4, BUCKET, chunks=1)
    # raw (32-bit) slices carry no codec cost but full wire bytes
    raw = m.predict_slice(n, 4, 32, BUCKET, chunks=1)
    assert raw > 0
    assert m.wire_bytes(n, 32, BUCKET) == 4.0 * n
    assert m.wire_bytes(n, 4, BUCKET) < 4.0 * n
    # ws=1 has no wire at all
    assert m.predict_slice(n, 1, 32, BUCKET) == 0.0


def test_predict_step_overlap_credit():
    m = dataclasses.replace(
        planner.CostModel.default(), overlap_frac=0.5, compute_s=1.0
    )
    coll = [0.4, 0.2]
    assert m.predict_step(coll) == pytest.approx(1.0 + 0.6 - 0.5 * 0.6)
    assert m.predict_step(coll, reverse_order=False) == pytest.approx(1.6)


# ---------------------------------------------------------------------------
# Joint solve == brute force on small instances.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overhead_us", [5, 100, 2000])
def test_solve_matches_bruteforce(overhead_us):
    model = dataclasses.replace(
        planner.CostModel.default(), chunk_overhead_s=overhead_us * 1e-6
    )
    slices = [
        (1 << 22, _cc(4)),
        (1 << 18, _cc(8)),
        (4096, _cc(4)),
        (1 << 20, CompressionConfig(bits=32)),  # raw: never pipelines
    ]
    got = planner.solve(slices, 4, model=model)
    ref = planner.solve_bruteforce(slices, 4, model=model)
    assert [(d.chunks, d.bits) for d in got] == [
        (d.chunks, d.bits) for d in ref
    ]
    # raw slice pinned to depth 1
    assert got[3].chunks == 1
    # predicted costs agree too
    for a, b in zip(got, ref):
        assert a.predicted_s == pytest.approx(b.predicted_s)


def test_solve_bit_budget_reallocates():
    """CGX_PLANNER_AVG_BITS: the payload-weighted marginal allocation
    (the WireController's solver, planner-driven) gives big slices fewer
    bits and small slices more, averaging to the budget."""
    model = planner.CostModel.default()
    slices = [(1 << 22, _cc(4)), (1 << 14, _cc(4))]
    decs = planner.solve(slices, 4, model=model, avg_bits=4)
    total = sum(d.n for d in decs)
    avg = sum(d.bits * d.n for d in decs) / total
    assert avg <= 4 + 1e-6
    assert all(
        planner.BITS_RANGE[0] <= d.bits <= planner.BITS_RANGE[1]
        for d in decs
    )


# ---------------------------------------------------------------------------
# Plan LRU: keying + invalidation through both entry points.
# ---------------------------------------------------------------------------


def _groups(n=1 << 22, bits=4):
    return [planner._OneGroup(cc=_cc(bits), slices=((0, n),))]


def test_plan_lru_hits_and_registry_keying(monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "on")
    g = _groups()
    p1 = planner.plan_for_layout(g, 4, route="staged", reduction="SRA")
    assert p1 is not None
    p2 = planner.plan_for_layout(g, 4, route="staged", reduction="SRA")
    assert p2 is p1
    stats = planner.plan_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    # a registry bump (re-registration) must re-derive, never hit stale
    cgx_config.set_layer_pattern_config(".*", _cc(4))
    planner.plan_for_layout(g, 4, route="staged", reduction="SRA")
    assert planner.plan_cache_stats()["misses"] == 2


def test_plan_gates(monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "on")
    assert planner.plan_for_layout(_groups(), 1, route="staged",
                                   reduction="SRA") is None
    assert planner.plan_for_layout(_groups(), 4, route="staged",
                                   reduction="RING") is None
    raw = [planner._OneGroup(cc=CompressionConfig(bits=32),
                             slices=((0, 4096),))]
    assert planner.plan_for_layout(raw, 4, route="staged",
                                   reduction="SRA") is None
    monkeypatch.setenv("CGX_DEBUG_DUMMY_COMPRESSION", "1")
    assert planner.plan_for_layout(_groups(), 4, route="staged",
                                   reduction="SRA") is None


def test_invalidation_through_layout_cache(monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "on")
    planner.plan_for_layout(_groups(), 4, route="staged", reduction="SRA")
    assert len(planner._PLAN_CACHE) == 1
    invalidate_layout_cache("test")
    assert len(planner._PLAN_CACHE) == 0


def test_invalidation_through_supervisor(monkeypatch):
    from torch_cgx_tpu.robustness import supervisor

    monkeypatch.setenv("CGX_PLANNER", "on")
    planner.plan_for_layout(_groups(), 4, route="staged", reduction="SRA")
    assert len(planner._PLAN_CACHE) == 1
    supervisor.invalidate_trace_caches()
    assert len(planner._PLAN_CACHE) == 0


def test_decide_slice_respects_engagement(monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "off")
    assert planner.decide_slice(1 << 22, 4, _cc(), "SRA") is None
    monkeypatch.delenv("CGX_PLANNER", raising=False)
    if jax.default_backend() != "tpu":  # auto = TPU only
        assert planner.decide_slice(1 << 22, 4, _cc(), "SRA") is None
    monkeypatch.setenv("CGX_PLANNER", "on")
    dec = planner.decide_slice(1 << 22, 4, _cc(), "SRA")
    assert dec is not None and dec.chunks >= 2


def test_auto_on_tpu_needs_a_calibrated_model(monkeypatch):
    """auto never plans from the built-in host-bridge constants: on a TPU
    with the default model the static schedule runs and is counted."""
    from torch_cgx_tpu.utils.logging import metrics

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    metrics.reset()
    default = planner.CostModel.default()
    assert not planner.cost_model().calibrated
    assert not planner.engaged()
    assert metrics.get("cgx.plan.uncalibrated_static") == 1
    try:
        # provenance alone, or the step clock alone, calibrates nothing
        planner.set_cost_model(dataclasses.replace(
            default, source="default+step_p50", compute_s=0.05
        ))
        assert not planner.engaged()
        planner.set_cost_model(dataclasses.replace(
            default, quantize_gbps=250.0, wire_gbps=90.0, source="file"
        ))
        assert planner.engaged()
    finally:
        planner.set_cost_model(None)


def test_backend_bridge_mirror_matches_planner(monkeypatch):
    """The bridge keeps a dependency-light duplicate of the DEFAULT-model
    depth argmin (``backend._plan_bridge_chunks`` — a pure-bridge rank
    must derive the same depth as a JAX-side rank, or mixed groups frame
    the collective differently and wedge); pinned here like the
    ``_sched_chunk_table`` duplicate."""
    from torch_cgx_tpu.torch_backend import backend as be

    monkeypatch.setenv("CGX_PLANNER", "on")
    for width in (0, 4096, 1 << 18, 1 << 20, 1 << 23):
        for ws in (1, 2, 4, 8):
            for bits in (2, 4, 8, 32):
                assert be._plan_bridge_chunks(
                    width, BUCKET, ws, bits
                ) == planner.bridge_chunks(
                    width, BUCKET, ws, bits, default=0
                ) or (width <= 0 or ws <= 1), (width, ws, bits)


def test_cost_model_file_resolution(tmp_path, monkeypatch):
    """CGX_PLANNER_MODEL: the persisted calibrated model wins over the
    default (but not over an in-process install), re-reads on file
    change, and a bad file falls back to default instead of crashing a
    decision site."""
    m = dataclasses.replace(
        planner.CostModel.default(), quantize_gbps=3.5, source="cal"
    )
    path = tmp_path / "model.json"
    m.save(str(path))
    monkeypatch.setenv("CGX_PLANNER_MODEL", str(path))
    assert planner.cost_model().quantize_gbps == 3.5
    # in-process install wins
    planner.set_cost_model(planner.CostModel.default())
    assert planner.cost_model().quantize_gbps == planner.CostModel.quantize_gbps
    planner.set_cost_model(None)
    # bad file: fall back, never raise
    path.write_text("{not json")
    # (stat cache keys on mtime; a rewrite is a new key)
    assert planner.cost_model() == planner.CostModel.default()


def test_backend_mirror_honors_model_file(tmp_path, monkeypatch):
    """The bridge mirror reads the SAME CGX_PLANNER_MODEL bytes the
    JAX-side planner loads — calibrated depth decisions stay
    group-consistent between pure-bridge and JAX-side ranks."""
    from torch_cgx_tpu.torch_backend import backend as be

    monkeypatch.setenv("CGX_PLANNER", "on")
    # a model with brutal per-chunk overhead must force depth 1 on both
    m = dataclasses.replace(
        planner.CostModel.default(), chunk_overhead_s=10.0, source="cal"
    )
    path = tmp_path / "model.json"
    m.save(str(path))
    monkeypatch.setenv("CGX_PLANNER_MODEL", str(path))
    width = 1 << 21
    assert be._plan_bridge_chunks(width, BUCKET, 4, 4) == 1
    assert planner.bridge_chunks(width, BUCKET, 4, 4, default=0) == 1
    # and without the file the default model pipelines this width
    monkeypatch.delenv("CGX_PLANNER_MODEL")
    assert be._plan_bridge_chunks(width, BUCKET, 4, 4) > 1


def test_bridge_chunks_engagement(monkeypatch):
    # bridge plane honors explicit "on" only (host plane: auto-means-TPU
    # cannot apply) and falls back to the caller's default otherwise
    monkeypatch.setenv("CGX_PLANNER", "on")
    c = planner.bridge_chunks(1 << 20, BUCKET, 4, 4, default=7)
    assert c >= 1 and c != 7
    monkeypatch.delenv("CGX_PLANNER", raising=False)
    assert planner.bridge_chunks(1 << 20, BUCKET, 4, 4, default=7) == 7
    monkeypatch.setenv("CGX_PLANNER", "off")
    assert planner.bridge_chunks(1 << 20, BUCKET, 4, 4, default=7) == 7


# ---------------------------------------------------------------------------
# Idempotent re-plan.
# ---------------------------------------------------------------------------


def test_replan_idempotent_and_adopts_on_change(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "on")
    plr = planner.StepPlanner(every=2, spans_dir=str(tmp_path))
    # no telemetry at all: recalibration yields the default model — the
    # FIRST update is already a no-op (no version bump, no cache drop)
    v0 = planner._PLAN_VERSION
    planner.plan_for_layout(_groups(), 4, route="staged", reduction="SRA")
    assert plr.update() is False
    assert planner._PLAN_VERSION == v0
    assert len(planner._PLAN_CACHE) == 1  # no retrace storm
    # telemetry appears: adopt ONCE, then no-op again
    with open(tmp_path / "spans-rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "kind": "span", "name": "codec.compress", "cat": "quantize",
            "t_mono": 0.0, "dur_s": 1.0, "elems": 7.5e8,
        }) + "\n")
    assert plr.update() is True
    assert planner._PLAN_VERSION == v0 + 1
    assert len(planner._PLAN_CACHE) == 0
    assert plr.update() is False
    assert planner._PLAN_VERSION == v0 + 1
    # step() cadence: every 2nd call updates
    assert plr.step() is False
    assert plr.step() is True


def test_cache_key_component_tracks_mode_and_version(monkeypatch):
    monkeypatch.setenv("CGX_PLANNER", "on")
    k1 = planner.cache_key_component()
    monkeypatch.setenv("CGX_PLANNER", "off")
    k2 = planner.cache_key_component()
    assert k1 != k2
    monkeypatch.setenv("CGX_PLANNER", "on")
    planner._PLAN_VERSION += 1
    assert planner.cache_key_component() != k1


# ---------------------------------------------------------------------------
# Inertness + e2e bit-equality (2-device run).
# ---------------------------------------------------------------------------

WS = 2
N = 1 << 21  # large enough that the default model picks depth > 1


def _mesh(ws=WS):
    return Mesh(np.asarray(jax.devices()[:ws]), ("dp",))


def _make_sm(mesh):
    def body(t):
        return allreduce_tree(
            {"a": t["a"][0].reshape(1024, -1)}, mesh=mesh, axes=("dp",)
        )["a"]

    return shard_map(
        body, mesh=mesh, in_specs=({"a": P("dp")},), out_specs=P(),
        check_vma=False,
    )


def _tree(mesh):
    rng = np.random.default_rng(0)
    return {
        "a": jax.device_put(
            jnp.asarray(rng.normal(size=(WS, N)), jnp.float32),
            NamedSharding(mesh, P("dp")),
        )
    }


def test_planner_unset_and_off_stage_identical_program(monkeypatch):
    """CGX_PLANNER unset ⇒ jaxpr-identical to off (and therefore to
    HEAD): the planner's inertness contract on every CPU/CI path."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _mesh()
    tree = _tree(mesh)
    j_unset = str(jax.make_jaxpr(_make_sm(mesh))(tree))
    monkeypatch.setenv("CGX_PLANNER", "off")
    j_off = str(jax.make_jaxpr(_make_sm(mesh))(tree))
    assert j_unset == j_off


def test_planner_e2e_bit_equal_to_static_knobs(monkeypatch):
    """The acceptance pin: the planner's staged program (tree plane) is
    jaxpr-equal AND bit-equal to the static-knob run at the planner's
    own chosen depth — the planner picks knobs, never changes bytes."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _mesh()
    tree = _tree(mesh)
    monkeypatch.setenv("CGX_PLANNER", "on")
    dec = planner.decide_slice(N, WS, _cc(), "SRA")
    assert dec is not None and dec.chunks >= 2
    j_plan = str(jax.make_jaxpr(_make_sm(mesh))(tree))
    out_plan = np.asarray(jax.jit(_make_sm(mesh))(tree))
    monkeypatch.delenv("CGX_PLANNER")
    j_base = str(jax.make_jaxpr(_make_sm(mesh))(tree))
    assert j_plan != j_base  # the plan actually pipelined
    monkeypatch.setenv("CGX_SCHEDULE", "on")
    monkeypatch.setenv("CGX_SCHED_CHUNKS", str(dec.chunks))
    schedule.schedule_cache_clear()
    j_static = str(jax.make_jaxpr(_make_sm(mesh))(tree))
    out_static = np.asarray(jax.jit(_make_sm(mesh))(tree))
    assert j_plan == j_static
    np.testing.assert_array_equal(out_plan, out_static)


def test_planned_eager_program_bit_equal_and_donates(monkeypatch):
    """The eager donated-buffer plane: ``planned_allreduce`` output is
    bit-equal to ``staged_allreduce`` under the equivalent static knobs,
    and the planner program really donates its input stack."""
    from torch_cgx_tpu.parallel import xla_allreduce as xm

    monkeypatch.setenv("CGX_XLA_ALLREDUCE", "on")
    mesh = _mesh()
    rng = np.random.default_rng(1)
    per = np.asarray(rng.normal(size=(WS, N)), np.float32)
    monkeypatch.setenv("CGX_PLANNER", "on")
    dec = planner.decide_slice(N, WS, _cc(), "SRA")
    assert dec is not None
    arr = jax.device_put(per, NamedSharding(mesh, P("dp")))
    out_plan = np.asarray(
        planner.planned_allreduce(arr, mesh=mesh, axis="dp", cc=_cc())
    )
    # donated: the input buffer was consumed by the planner program
    assert arr.is_deleted()
    monkeypatch.delenv("CGX_PLANNER")
    monkeypatch.setenv("CGX_SCHEDULE", "on")
    monkeypatch.setenv("CGX_SCHED_CHUNKS", str(dec.chunks))
    schedule.schedule_cache_clear()
    out_static = np.asarray(
        xm.staged_allreduce(per, mesh=mesh, axis="dp", cc=_cc())
    )
    np.testing.assert_array_equal(out_plan, out_static)


def test_planner_values_invariant_under_engagement(monkeypatch):
    """Values are schedule-invariant by the bit-equality contract: the
    planner on vs fully off produces identical reduced bytes (the
    deterministic encode)."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _mesh()
    tree = _tree(mesh)
    out_base = np.asarray(jax.jit(_make_sm(mesh))(tree))
    monkeypatch.setenv("CGX_PLANNER", "on")
    out_plan = np.asarray(jax.jit(_make_sm(mesh))(tree))
    np.testing.assert_array_equal(out_base, out_plan)


def test_train_step_cache_keys_planner(monkeypatch):
    """make_train_step's build cache keys the planner component: a mode
    flip or an adopted re-plan retraces; nothing else does."""
    import optax

    from torch_cgx_tpu.parallel.grad_sync import make_train_step
    from torch_cgx_tpu.utils.logging import metrics

    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    mesh = _mesh()

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    params = {"w": jnp.ones((8, 4), jnp.float32)}
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    batch = {"x": jnp.ones((WS * 2, 8), jnp.float32)}
    before = metrics.get("cgx.trace.train_step_builds")
    step(params, opt_state, batch, 0)
    mid = metrics.get("cgx.trace.train_step_builds")
    assert mid == before + 1
    step(params, opt_state, batch, 1)
    assert metrics.get("cgx.trace.train_step_builds") == mid
    monkeypatch.setenv("CGX_PLANNER", "on")
    step(params, opt_state, batch, 2)
    assert metrics.get("cgx.trace.train_step_builds") == mid + 1
