"""Whole-step mega-schedule: the step-level plan compiler.

PR 9 compiles per-slice chunk pipelines and PR 10 routes every wire edge
through one codec dispatcher, but each fusion slice and wire edge still
lowers independently with STATIC knobs — ``CGX_SCHED_CHUNKS`` picks one
pipeline depth for every slice, per-edge bits come from registrations,
and nothing optimizes the step *globally* (ROADMAP item 2). GC3 (arxiv
2201.11840) argues collective schedules should be compiled against a
cost model rather than hand-tuned per primitive; "Fused
Computation-Collective Operations" (arxiv 2305.06942) motivates emitting
the whole compute+communication step as one fused program. This module
is that compiler for the step tier:

* a :class:`CostModel` calibrated ONLINE from live telemetry — the
  ``cgx_trace`` span files (per-phase byte rates + the ``overlap_frac``
  attribution) and the WireController's trace-time (numel, bits) side
  tables;
* a **joint solve** over ALL fusion slices of a train step at once:
  (pipeline depth per slice, bit-width per slice, emission order)
  against the model — per-slice costs are additive, so the exact argmin
  decomposes per slice (``tests/test_planner.py`` pins the production
  solver against brute force on small instances);
* a :class:`StepPlan` staged as ONE donated-buffer XLA program per step
  behind a bounded plan LRU (:func:`planned_allreduce` on the eager
  plane; ``grad_sync.make_train_step``'s jitted step consumes plans at
  trace time through ``allreduce_tree``), with the bridge's pipelined
  worker loop consuming the same depth decision through
  :func:`bridge_chunks`.

This absorbs the three existing decision registries — the layout LRU in
``allreduce.py``, the schedule LRU in ``schedule.py`` and the
WireController's bit solver in ``wire/controller.py`` — behind one
``StepPlan`` surface: the planner decides, the registries execute, and
``tools/lint.py`` rejects new registry writers outside this module. Every
future perf lever becomes a cost-model change instead of a new
subsystem.

**Inertness contract** (the ``CGX_SCHEDULE``/``CGX_WIRE`` discipline):
``CGX_PLANNER`` unset ("auto") engages only on a real TPU backend with a
calibrated cost model (the built-in defaults are host-bridge rates — an
uncalibrated TPU run keeps the static schedule and bumps
``cgx.plan.uncalibrated_static``); on every CPU/CI path
:func:`engaged` is False, no plan is derived, and
staged programs, store keys and wire bytes are bit-identical to the
pre-planner code (jaxpr-pinned in tests/test_planner.py). ``on`` engages
anywhere (the CPU test/bench configuration — and the only mode the
bridge hint honors, since the bridge is a host plane where "auto means
TPU" cannot apply); ``off`` never.

**Invalidation** rides the existing path: ``allreduce.
invalidate_layout_cache`` (and therefore ``supervisor.
invalidate_trace_caches``) cascades into :func:`invalidate_plan_cache` —
a recovery reconfigure re-plans at the shrunk world exactly as it
re-derives layouts. **Re-planning is idempotent**: :meth:`StepPlanner.
update` recalibrates the model and bumps the plan version (one retrace)
only when the model actually moved; unchanged telemetry is a no-op — no
registry bump, no retrace storm.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from .. import config as cfg_mod
from ..config import CompressionConfig
from ..utils.logging import metrics
from . import reducers
from . import schedule as sched_mod

# Candidate pipeline depths the solver considers per slice. Matches the
# depths the schedule compiler can realize (feasibility is re-checked
# against the slice's aligned width); 1 = monolithic.
CHUNK_CANDIDATES = (1, 2, 4, 8, 16)

# Bit-widths the joint solve may assign when an average-bits budget is
# set (the solver's range mirrors wire/controller.py's default).
BITS_RANGE = (2, 8)

# Async cross-slice plane (PR 13): candidate outer cadences the H solve
# considers, and the modeled convergence cost of one extra inner step of
# cross-slice drift (fraction of a step per unit H — the term that keeps
# the solve from always picking the largest H).
ASYNC_H_CANDIDATES = (2, 4, 8, 16, 32, 64)
ASYNC_DRIFT_FRAC = 0.01

# Serving plane (PR 15): candidate page sizes / shipping depths the
# serve solve considers (``CGX_KV_PAGE_TOKENS=0`` / ``CGX_KV_SHIP_DEPTH=0``
# let the planner pick). Page meta overhead pulls page size UP; pool
# fragmentation on ragged sequence tails pulls it down — modeled as half
# a page of wasted pool per sequence.
SERVE_PAGE_CANDIDATES = (8, 16, 32, 64)
SERVE_DEPTH_CANDIDATES = (1, 2, 4, 8)


# ---------------------------------------------------------------------------
# The cost model.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Throughput/overhead terms the planner predicts step time from.

    Rates are decimal GB/s; ``quantize_gbps`` is per byte of f32 INPUT,
    ``dequantize_gbps`` per byte of f32 OUTPUT (the qbench/BASELINE
    convention), ``wire_gbps`` the per-rank effective link bandwidth.
    ``overlap_frac`` is the measured share of collective wall time hidden
    under concurrent compute (the ``cgx_trace`` attribution number) —
    applied only when the plan emits groups in reverse-layer order.
    ``chunk_overhead_s`` is the fixed per-pipelined-chunk cost (dispatch,
    pipeline fill, per-chunk store keys on the bridge). ``compute_s`` is
    the step's non-collective compute time when known (0 = unknown;
    slice predictions don't need it)."""

    quantize_gbps: float = 8.0
    dequantize_gbps: float = 16.0
    wire_gbps: float = 1.0
    overlap_frac: float = 0.0
    chunk_overhead_s: float = 100e-6
    compute_s: float = 0.0
    # Cross-slice (DCN) effective link bandwidth — the slow tier the
    # async plane (PR 13) exists to take off the critical path. Distinct
    # from ``wire_gbps`` (the intra/bridge rate): the sync-vs-async route
    # decision compares the SAME payload over the two tiers. Calibrated
    # live from the sender thread's ``cgx.async.wire_gbps`` gauge.
    dcn_gbps: float = 0.25
    source: str = "default"

    # -- calibration -------------------------------------------------------

    @classmethod
    def default(cls) -> "CostModel":
        return cls()

    @property
    def calibrated(self) -> bool:
        """Whether the two rates a depth decision weighs against each
        other — codec and wire — both come from measurement rather than
        from the built-in constants (host-bridge rates, never measured on
        a TPU). ``source`` is provenance only: a model that learned just
        its ``compute_s`` from the step clock is still uncalibrated."""
        return (
            self.quantize_gbps != CostModel.quantize_gbps
            and self.wire_gbps != CostModel.wire_gbps
        )

    @classmethod
    def from_spans(cls, directory: str) -> "CostModel":
        """Calibrate from a ``CGX_METRICS_DIR``'s ``spans-rank*.jsonl``
        files (the cgx_trace source data): per-phase byte rates from the
        quantize/wire span categories (each span carries ``bytes`` +
        ``dur_s``), ``overlap_frac`` from the interval-union overlap of
        collective spans with concurrent ``CAT_SPAN`` compute — the same
        measurement ``tools/cgx_trace.py attribution`` reports. Phases
        with no byte-carrying spans keep the defaults (``source`` says
        which fields calibrated)."""
        q_bytes = q_s = d_bytes = d_s = w_bytes = w_s = wait_s = 0.0
        n_waits = 0
        # Overlap is a PER-RANK measurement (cgx_trace.attribution's
        # convention): pooling ranks' intervals would let rank B's
        # compute blanket rank A's collectives — concurrent SPMD ranks
        # share the clock, so cross-rank overlap is ~always ~1.0 and
        # meaningless. Rates pool fine (they are ratios of sums).
        overlaps: List[float] = []
        for path in sorted(glob.glob(os.path.join(directory, "spans-rank*.jsonl"))):
            try:
                with open(path) as f:
                    lines = f.readlines()
            except OSError:
                continue
            coll_iv: List[Tuple[float, float]] = []
            comp_iv: List[Tuple[float, float]] = []
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail
                if ev.get("kind") != "span":
                    continue
                dur = float(ev.get("dur_s", 0.0))
                t0 = float(ev.get("t_mono", 0.0))
                cat = ev.get("cat")
                if cat == "quantize":
                    # Rates are per f32 byte (the qbench unit
                    # predict_slice divides by), so calibrate from the
                    # span's `elems` f32 count — its `bytes` field is
                    # WIRE bytes (~bits/32 of the input). Split by span
                    # name: codec.compress is the quantize direction,
                    # codec.decompress the dequantize one; the fused
                    # codec.sra_epilogue pair is not attributable to
                    # either rate and is skipped.
                    elems = float(ev.get("elems", 0.0))
                    if ev.get("name") == "codec.compress":
                        q_bytes += 4.0 * elems
                        q_s += dur
                    elif ev.get("name") == "codec.decompress":
                        d_bytes += 4.0 * elems
                        d_s += dur
                elif cat == "wire":
                    w_bytes += float(ev.get("bytes", 0.0))
                    w_s += dur
                elif cat == "wait":
                    wait_s += dur
                    n_waits += 1
                elif cat == "collective":
                    coll_iv.append((t0, t0 + dur))
                elif cat == "span":
                    comp_iv.append((t0, t0 + dur))
            coll_u = _merge_intervals(coll_iv)
            coll_total = sum(e - s for s, e in coll_u)
            if coll_total > 0:
                overlaps.append(
                    min(
                        _overlap_len(coll_u, _merge_intervals(comp_iv))
                        / coll_total,
                        1.0,
                    )
                )
        kw: Dict[str, float] = {}
        fields = []
        if q_bytes and q_s:
            kw["quantize_gbps"] = q_bytes / q_s / 1e9
            # decompress spans set the dequantize rate directly; with
            # only compress evidence keep the default 2:1 shape.
            kw["dequantize_gbps"] = (
                d_bytes / d_s / 1e9
                if d_bytes and d_s
                else 2.0 * q_bytes / q_s / 1e9
            )
            fields.append("codec")
        if w_bytes and w_s:
            kw["wire_gbps"] = w_bytes / w_s / 1e9
            fields.append("wire")
        if n_waits and wait_s:
            # mean wait-span duration: the queue-wait cost each pipelined
            # chunk pays (wire spans are rate-bearing, not overhead —
            # counting them in the denominator understated this ~3x)
            kw["chunk_overhead_s"] = wait_s / n_waits
            fields.append("overhead")
        if overlaps:
            kw["overlap_frac"] = sum(overlaps) / len(overlaps)
            fields.append("overlap")
        return cls(source=f"spans:{'+'.join(fields) or 'none'}", **kw)

    @classmethod
    def from_telemetry(cls, spans_dir: Optional[str] = None) -> "CostModel":
        """The live-calibration entry point :meth:`StepPlanner.update`
        drives: span files when a metrics dir is available (argument or
        ``CGX_METRICS_DIR``) and the ``cgx.step.time_s`` histogram's p50
        as the compute baseline."""
        base = (
            cls.from_spans(spans_dir or cfg_mod.metrics_dir() or "")
            if (spans_dir or cfg_mod.metrics_dir())
            else cls.default()
        )
        kw: Dict[str, float] = {}
        fields = [base.source]
        try:
            hist = metrics.snapshot_typed()["histograms"].get("cgx.step.time_s")
        except Exception:
            hist = None
        if hist and hist.get("p50"):
            kw["compute_s"] = float(hist["p50"])
            fields.append("step_p50")
        # Async-plane calibration: the sender thread gauges its measured
        # DCN put throughput per shipped round (``cgx.async.wire_gbps``)
        # — the live number the sync-vs-async route curves divide by.
        try:
            agbps = float(metrics.get("cgx.async.wire_gbps"))
        except Exception:
            agbps = 0.0
        if agbps > 0:
            kw["dcn_gbps"] = agbps
            fields.append("async")
        if not kw:
            return base
        return dataclasses.replace(base, source="+".join(fields), **kw)

    # -- prediction --------------------------------------------------------

    def wire_bytes(self, n: int, bits: int, bucket: int) -> float:
        """Stage-1 wire bytes of an ``n``-element slice at ``bits`` — THE
        codec's own layout formula (``ops.codec.wire_bytes``: packed
        bit-plane words + per-bucket meta), so the cost model can never
        drift from what the wire actually ships; raw f32 when
        compression is off. ``backend._plan_bridge_chunks`` keeps the
        sanctioned dependency-light duplicate."""
        if not 1 <= bits <= cfg_mod.MAX_BITS:
            return 4.0 * n
        from ..ops import codec

        return float(codec.wire_bytes(n, bits, max(1, bucket), 4))

    def predict_slice(
        self,
        n: int,
        ws: int,
        bits: int,
        bucket: int,
        chunks: int = 1,
        route: str = "staged",
    ) -> float:
        """Predicted seconds for one fusion slice's allreduce under a
        (bits, chunks) decision: per-rank SRA codec work (quantize
        ``n(1+1/ws)`` elems, dequantize ``n(2-1/ws)`` — the
        ``CGX_DEBUG_FORCE_CODEC`` accounting) plus the standard
        ``2(ws-1)/ws`` wire factor, software-pipelined at depth
        ``chunks``: the non-bottleneck stage's exposure amortizes as
        ``1/chunks`` (only the pipeline fill remains exposed) while each
        chunk pays the fixed ``chunk_overhead_s``."""
        del route  # both planes share the stage structure; rates differ
        n = int(n)
        ws = max(1, int(ws))
        if n <= 0 or ws == 1:
            return 0.0  # no collective at all on a 1-device axis
        compressed = 1 <= bits <= cfg_mod.MAX_BITS
        t_codec = 0.0
        if compressed:
            t_codec = (
                4.0 * n * (1 + 1 / ws) / (self.quantize_gbps * 1e9)
                + 4.0 * n * (2 - 1 / ws) / (self.dequantize_gbps * 1e9)
            )
        factor = 2.0 * (ws - 1) / ws
        t_wire = factor * self.wire_bytes(n, bits, bucket) / (self.wire_gbps * 1e9)
        c = max(1, int(chunks))
        bottleneck = max(t_codec, t_wire)
        exposed = (t_codec + t_wire - bottleneck) / c
        return bottleneck + exposed + c * self.chunk_overhead_s

    def predict_slice_components(
        self,
        n: int,
        ws: int,
        bits: int,
        bucket: int,
        chunks: int = 1,
        route: str = "staged",
    ) -> Dict[str, float]:
        """:meth:`predict_slice`'s decomposition, for the critical-path
        drift loop (ISSUE 17): ``{"quantize", "wire", "overhead"}``
        seconds summing exactly to the scalar prediction. The pipelined
        exposure is charged to the NON-bottleneck stage (that is the
        stage whose time amortizes as ``1/chunks``); the bottleneck
        stage keeps its full cost. ``PlanDriftMonitor`` compares these
        against the measured critical-path components, so a mis-modeled
        rate names the component that drifted, not just "the step"."""
        del route
        n = int(n)
        ws = max(1, int(ws))
        if n <= 0 or ws == 1:
            return {"quantize": 0.0, "wire": 0.0, "overhead": 0.0}
        compressed = 1 <= bits <= cfg_mod.MAX_BITS
        t_codec = 0.0
        if compressed:
            t_codec = (
                4.0 * n * (1 + 1 / ws) / (self.quantize_gbps * 1e9)
                + 4.0 * n * (2 - 1 / ws) / (self.dequantize_gbps * 1e9)
            )
        factor = 2.0 * (ws - 1) / ws
        t_wire = factor * self.wire_bytes(n, bits, bucket) / (self.wire_gbps * 1e9)
        c = max(1, int(chunks))
        if t_codec >= t_wire:
            q, w = t_codec, t_wire / c
        else:
            q, w = t_codec / c, t_wire
        return {"quantize": q, "wire": w, "overhead": c * self.chunk_overhead_s}

    def memory_envelope(
        self,
        n: int,
        ws: int,
        bits: int,
        bucket: int,
        chunks: int = 1,
    ) -> Dict[str, float]:
        """Predicted peak staging bytes of one fusion slice's allreduce
        under a (bits, chunks) decision — the memory-side twin of
        :meth:`predict_slice` (GC3's footprint-as-compiler-input idea:
        the planner should reject a plan that won't fit BEFORE the
        arena's pressure path discovers it at runtime).

        * ``fusion_bytes`` — the 4n f32 fusion buffer the slice
          reduces (device-resident, chunk-independent).
        * ``frame_bytes`` — the largest single arena put: one pipeline
          chunk's wire frame, ``wire_bytes / chunks``.
        * ``staging_bytes`` — host-arena bytes resident at the pipeline
          steady state: double-buffered frames on both SRA stages
          (``2 × frame_bytes`` per stage — one being filled, one
          awaiting acks), so a deeper pipeline holds the same wire
          bytes in smaller, sooner-reclaimed frames.
        * ``total_bytes`` — fusion + staging: what one slice adds to
          the rank's envelope while its collective is in flight.
        """
        n = int(n)
        ws = max(1, int(ws))
        if n <= 0 or ws == 1:
            return {
                "fusion_bytes": 0.0, "frame_bytes": 0.0,
                "staging_bytes": 0.0, "total_bytes": 0.0,
            }
        c = max(1, int(chunks))
        wire = self.wire_bytes(n, bits, bucket)
        frame = wire / c
        staging = 2.0 * 2.0 * frame
        fusion = 4.0 * n
        return {
            "fusion_bytes": fusion,
            "frame_bytes": frame,
            "staging_bytes": staging,
            "total_bytes": fusion + staging,
        }

    # -- persistence (the CGX_PLANNER_MODEL group-consistency channel) --

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "CostModel":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def save(self, path: str) -> None:
        """Persist for ``CGX_PLANNER_MODEL``: every rank of a group loads
        the SAME bytes, so calibrated depth decisions cannot diverge
        (the bridge's dependency-light mirror reads the same file)."""
        with open(path, "w") as f:
            json.dump(self.as_dict(), f)

    def predict_step(
        self,
        slice_times: Sequence[float],
        *,
        compute_s: Optional[float] = None,
        reverse_order: bool = True,
    ) -> float:
        """Predicted step seconds: compute + collective, with the
        measured ``overlap_frac`` share of the smaller term hidden when
        groups emit in reverse-layer order (the PR 9 emission trick the
        overlap measurement was taken under)."""
        coll = float(sum(slice_times))
        comp = self.compute_s if compute_s is None else float(compute_s)
        ov = self.overlap_frac if reverse_order else 0.0
        return comp + coll - ov * min(comp, coll)

    def predict_outer(
        self,
        n: int,
        n_slices: int,
        bits: int,
        bucket: int,
        h: int,
        *,
        step_s: Optional[float] = None,
    ) -> float:
        """Amortized per-inner-step critical-path seconds of the ASYNC
        cross-slice exchange at cadence ``h`` (the PR 13 outer loop):

        * boundary codec work — quantize the ``n``-element delta once,
          decode ``n_slices`` deltas at the fold — amortizes as ``1/h``;
        * the DCN wire itself rides the sender thread OFF the critical
          path; only the backlog past the cadence window leaks back:
          ``max(0, t_wire - h*step) / h`` (a round must ship within the
          ``h`` inner steps it has before the next one, or lag grows
          until the staleness bound trips);
        * staleness drift — each extra inner step between
          reconciliations costs convergence; modeled as
          ``ASYNC_DRIFT_FRAC`` of a step per unit H, the term that gives
          the H solve its interior optimum (pure speed would always pick
          the largest H and let quality pay).

        ``step_s`` defaults to the calibrated ``compute_s`` (the
        ``cgx.step.time_s`` p50); with neither known the cadence-window
        term is skipped (codec + drift still rank H sensibly)."""
        n = int(n)
        h = max(1, int(h))
        if n <= 0 or n_slices <= 1:
            return 0.0
        t_codec = (
            4.0 * n / (self.quantize_gbps * 1e9)
            + 4.0 * n * n_slices / (self.dequantize_gbps * 1e9)
        )
        t_wire = self.wire_bytes(n, bits, bucket) / (self.dcn_gbps * 1e9)
        step = float(step_s) if step_s else self.compute_s
        if step <= 0:
            # no step-time evidence: assume a cadence where the default H
            # just keeps the wire fed — the codec and drift terms still
            # rank candidate Hs sensibly
            step = t_wire / cfg_mod.DEFAULT_ASYNC_H
        exposed = max(0.0, t_wire - h * step) / h
        drift = ASYNC_DRIFT_FRAC * step * h
        return t_codec / h + exposed + drift


    def predict_serve(
        self,
        prompt_tokens: int,
        kv_token_bytes: int,
        n_layers: int,
        bits: int,
        bucket: int,
        page_tokens: int,
        depth: int,
        state_lane_bytes: int = 0,
    ) -> Tuple[float, float]:
        """(predicted TTFT seconds, predicted per-page wire seconds) of
        the disaggregated prefill→decode hop (PR 15):

        * each page's payload is ``page_tokens * kv_token_bytes /
          n_layers / 2`` f32 values per (layer, K|V) — ``2 * n_layers``
          frames per page — priced by the codec's own wire-layout
          formula at ``bits`` (raw f16 when uncompressed);
        * pages pipeline at shipping depth ``depth``: quantize overlaps
          the wire like a chunked collective (``predict_slice``'s
          exposure model), each frame paying the fixed per-message
          ``chunk_overhead_s``;
        * TTFT is the full prompt's page stream through that pipe —
          admission waits for the LAST page, so the stream is the
          latency term the SLO controller's bit budget moves;
        * ``state_lane_bytes`` of recurrent state (a state-space model's
          lanes hold a fixed-size state beside their pages) would cross
          once, raw, whatever the page size: a term that moves no choice
          and keeps the predicted TTFT honest.
        """
        page_tokens = max(1, int(page_tokens))
        depth = max(1, int(depth))
        n_pages = max(1, -(-int(prompt_tokens) // page_tokens))
        per_payload = page_tokens * kv_token_bytes / (2 * n_layers) / 4
        frames = 2 * n_layers * n_pages
        if 1 <= bits <= cfg_mod.MAX_BITS:
            frame_b = self.wire_bytes(int(per_payload), bits, max(1, bucket))
            t_codec = 4.0 * per_payload / (self.quantize_gbps * 1e9)
        else:
            frame_b = 2.0 * per_payload  # raw f16 shipping
            t_codec = 0.0
        t_wire_frame = frame_b / (self.wire_gbps * 1e9)
        bottleneck = max(t_codec, t_wire_frame)
        exposed = (t_codec + t_wire_frame - bottleneck) / depth
        per_frame = bottleneck + exposed + self.chunk_overhead_s
        # Half a page of pool waste per sequence, priced as the time to
        # ship those bytes — the fragmentation term that keeps the solve
        # from always picking the largest page.
        waste = 0.5 * page_tokens / max(1, prompt_tokens)
        ttft = frames * per_frame * (1.0 + waste)
        ttft += state_lane_bytes / (self.wire_gbps * 1e9)
        return ttft, per_frame


def _merge_intervals(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap_len(
    a: List[Tuple[float, float]], b: List[Tuple[float, float]]
) -> float:
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


# ---------------------------------------------------------------------------
# Engagement + the active model.
# ---------------------------------------------------------------------------


_MODEL: Optional[CostModel] = None  # None = file/default resolution
_PLAN_VERSION = 0  # bumped when an adopted re-plan changes decisions

# CGX_PLANNER_MODEL file cache: (path, mtime_ns) -> CostModel. Re-read
# only when the file changes; a bad/missing file falls back to default
# (never crashes a decision site).
# cgx-analysis: allow(orphan-memo) — keyed by (path, mtime_ns, size): a changed file can never serve a stale model, and recovery moves no file
_MODEL_FILE_CACHE: Dict[Tuple[str, int], CostModel] = {}


def _model_from_file() -> Optional[CostModel]:
    path = cfg_mod.planner_model_path()
    if not path:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    # (mtime, size), not mtime alone: filesystem mtime granularity can be
    # coarser than two consecutive writes, and a rewrite landing in the
    # same tick must not serve the previous file's model.
    key = (path, st.st_mtime_ns, st.st_size)
    hit = _MODEL_FILE_CACHE.get(key)
    if hit is not None:
        return hit
    try:
        with open(path) as f:
            model = CostModel.from_dict(json.load(f))
    except (OSError, ValueError, TypeError):
        return None
    _MODEL_FILE_CACHE.clear()
    _MODEL_FILE_CACHE[key] = model
    return model


def cost_model() -> CostModel:
    """The active model: an in-process install (``set_cost_model`` /
    StepPlanner adoption) wins, then the ``CGX_PLANNER_MODEL`` file
    (group-consistent calibrated bytes), then the built-in default."""
    if _MODEL is not None:
        return _MODEL
    from_file = _model_from_file()
    return from_file if from_file is not None else CostModel.default()


def set_cost_model(model: Optional[CostModel]) -> None:
    """Install (or clear, with None) the calibrated model and drop plans
    derived under the old one. Prefer :class:`StepPlanner`, which only
    adopts a model that actually moved (idempotent re-plan)."""
    global _MODEL
    _MODEL = model
    plan_cache_clear()


def engaged(route_staged: bool = True) -> bool:
    """Whether the planner may decide for JAX-plane slices under the
    current mode/backend: "on" anywhere, "auto" only on a real TPU
    backend (inert on every CPU/CI path — the ``CGX_SCHEDULE`` gate
    discipline) AND only from a calibrated model, "off" never.

    The built-in :class:`CostModel` defaults are host-bridge rates
    (8/16/1 GB/s); they were never measured on any TPU, so under "auto"
    they are no basis for a decision: an uncalibrated TPU run keeps the
    static schedule (``CGX_SCHED_CHUNKS``) and counts that it did
    (``cgx.plan.uncalibrated_static``). A :attr:`CostModel.calibrated`
    model — installed through ``CGX_PLANNER_MODEL``, ``set_cost_model``
    or :class:`StepPlanner` adoption — engages it."""
    del route_staged  # the topology router already picked the plane
    mode = cfg_mod.planner_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    if jax.default_backend() != "tpu":
        return False
    if not cost_model().calibrated:
        metrics.add("cgx.plan.uncalibrated_static")
        return False
    return True


def engaged_bridge() -> bool:
    """Bridge-plane engagement: explicit "on" only. The bridge is a host
    plane on every deployment, so "auto means TPU" cannot apply — and a
    silently-engaging default would change store keys under CI."""
    return cfg_mod.planner_mode() == "on"


def cache_key_component() -> Tuple:
    """The planner's contribution to trace-cache keys
    (``make_train_step._build``): mode, the adopted plan version, the
    solve budget, AND the active model's fingerprint — a model swapped
    in through ``set_cost_model`` or a changed ``CGX_PLANNER_MODEL``
    file alters plan decisions without touching the version counter, so
    the fingerprint must retrace or the jitted step would keep
    executing a stale plan while the gauges report the new one. An
    UNCHANGED re-plan keeps the key, so no retrace storm."""
    return (
        cfg_mod.planner_mode(),
        _PLAN_VERSION,
        cfg_mod.planner_avg_bits(),
        _model_fingerprint(cost_model()),
        # Staging budget (ISSUE 18): the memory envelope gate changes
        # which pipeline depths the solve may pick, so toggling
        # CGX_MEMLEDGER (or resizing CGX_SHM_MAX_MB under it) must
        # retrace. None when the ledger is off keeps unset bit-identical.
        _staging_budget(),
    )


# ---------------------------------------------------------------------------
# Decisions + the joint solve.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SliceDecision:
    """One fusion slice's plan: pipeline depth, wire width, route."""

    n: int
    ws: int
    bits: int
    chunks: int
    route: str
    predicted_s: float


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """One train step's compiled plan: per-(group, fusion-slice)
    decisions in layout order, the group emission order, and the model's
    step-time prediction (collective portion).

    ``pred_components`` is the prediction's decomposition recorded at
    solve time — ``(("compute", s), ("overhead", s), ("quantize", s),
    ("wire", s))`` — the per-phase baseline the critical-path drift
    loop (``health.PlanDriftMonitor``) compares measured components
    against."""

    decisions: Tuple[Tuple[SliceDecision, ...], ...]
    order: Tuple[int, ...]
    predicted_s: float
    version: int
    pred_components: Tuple[Tuple[str, float], ...] = ()

    def components(self) -> Dict[str, float]:
        return dict(self.pred_components)


def chunk_candidates(n: int, ws: int, bucket: int) -> Tuple[int, ...]:
    """Feasible pipeline depths for an ``n``-element slice at world size
    ``ws``: the candidate set clipped to what ``schedule.chunk_table``
    can realize (a depth needs one aligned column unit per chunk)."""
    if ws <= 1 or n <= 0:
        return (1,)
    width = reducers.chunk_layout(n, ws)[0]
    units = width // sched_mod.chunk_alignment(bucket)
    return tuple(c for c in CHUNK_CANDIDATES if c <= max(1, units))


def _slice_candidates(
    n: int, ws: int, cc: CompressionConfig
) -> Tuple[int, ...]:
    """Depth candidates for one slice: raw (uncompressed) slices never
    pipeline — the schedule compiler gates on ``cc.enabled``, so a plan
    assigning them depth would describe a program that cannot exist."""
    if not cc.enabled:
        return (1,)
    return chunk_candidates(n, ws, cc.bucket_size)


def _best_chunks(
    model: CostModel,
    n: int,
    ws: int,
    bits: int,
    cc: CompressionConfig,
    route: str,
    staging_budget: Optional[int] = None,
) -> Tuple[int, float]:
    """argmin over feasible depths (ties prefer the shallower pipeline —
    fewer store keys / smaller programs for the same predicted time).

    With a ``staging_budget`` (the memory-envelope filter, active only
    under ``CGX_MEMLEDGER`` — the knob rides in the plan key), depths
    whose predicted steady-state staging bytes exceed the budget are
    rejected before the time argmin; when EVERY depth violates it, the
    depth minimizing staging wins (the deepest pipeline — smallest
    frames, soonest reclaim) so the solver still returns a plan and the
    arena's pressure path stays the backstop, not the plan."""
    best_c, best_t = 1, float("inf")
    fallback_c, fallback_m = 1, float("inf")
    any_feasible = False
    for c in _slice_candidates(n, ws, cc):
        if staging_budget is not None:
            env = model.memory_envelope(
                n, ws, bits, cc.bucket_size, chunks=c
            )
            if env["staging_bytes"] < fallback_m - 1e-9:
                fallback_c, fallback_m = c, env["staging_bytes"]
            if env["staging_bytes"] > staging_budget:
                continue
        any_feasible = True
        t = model.predict_slice(
            n, ws, bits, cc.bucket_size, chunks=c, route=route
        )
        if t < best_t - 1e-15:
            best_c, best_t = c, t
    if staging_budget is not None and not any_feasible:
        return fallback_c, model.predict_slice(
            n, ws, bits, cc.bucket_size, chunks=fallback_c, route=route
        )
    return best_c, best_t


def solve(
    slices: Sequence[Tuple[int, CompressionConfig]],
    ws: int,
    *,
    model: Optional[CostModel] = None,
    route: str = "staged",
    avg_bits: float = 0.0,
    staging_budget: Optional[int] = None,
) -> List[SliceDecision]:
    """The joint solve over all fusion slices of a step: per slice a
    (chunks, bits) pair minimizing the model's predicted step time.

    Slice costs are additive and the bit budget (when ``avg_bits`` > 0)
    is the only coupling, so the exact optimum decomposes: bits come from
    the payload-weighted marginal allocation (``adaptive.
    solve_bit_allocation`` — the same solver the WireController drives,
    now driven by the planner), then each slice's depth is an independent
    argmin. ``tests/test_planner.py`` pins this against brute force."""
    model = model or cost_model()
    bits_by_idx: Dict[int, int] = {}
    if avg_bits:
        from .adaptive import LayerStat, solve_bit_allocation

        stats = {
            str(i): LayerStat(numel=int(n), mean_sq_range=1.0)
            for i, (n, cc) in enumerate(slices)
            if cc.enabled and n > 0
        }
        if stats:
            alloc = solve_bit_allocation(stats, avg_bits, bits_range=BITS_RANGE)
            bits_by_idx = {int(k): int(v) for k, v in alloc.items()}
    out: List[SliceDecision] = []
    for i, (n, cc) in enumerate(slices):
        # raw slices price (and report) as 32-bit — the brute-force
        # solver's convention, pinned equal by test
        bits = bits_by_idx.get(i, cc.bits) if cc.enabled else 32
        chunks, t = _best_chunks(
            model, n, ws, bits, cc, route, staging_budget=staging_budget
        )
        out.append(
            SliceDecision(
                n=int(n), ws=int(ws), bits=int(bits), chunks=int(chunks),
                route=route, predicted_s=t,
            )
        )
    return out


def solve_bruteforce(
    slices: Sequence[Tuple[int, CompressionConfig]],
    ws: int,
    *,
    model: Optional[CostModel] = None,
    route: str = "staged",
) -> List[SliceDecision]:
    """Exhaustive reference solver (no bit budget): enumerate every
    depth assignment across slices and take the global argmin of the
    summed predictions. Exponential — test-sized instances only; the
    production :func:`solve` must match it exactly (the per-slice
    decomposition argument, verified rather than assumed)."""
    import itertools

    model = model or cost_model()
    cands = [_slice_candidates(n, ws, cc) for (n, cc) in slices]
    best: Optional[Tuple[float, Tuple[int, ...]]] = None
    for combo in itertools.product(*cands) if cands else [()]:
        total = 0.0
        for (n, cc), c in zip(slices, combo):
            total += model.predict_slice(
                n, ws, cc.bits if cc.enabled else 32, cc.bucket_size,
                chunks=c, route=route,
            )
        if best is None or total < best[0] - 1e-15:
            best = (total, combo)
    assert best is not None
    return [
        SliceDecision(
            n=int(n), ws=int(ws),
            bits=int(cc.bits if cc.enabled else 32), chunks=int(c),
            route=route,
            predicted_s=model.predict_slice(
                n, ws, cc.bits if cc.enabled else 32, cc.bucket_size,
                chunks=c, route=route,
            ),
        )
        for (n, cc), c in zip(slices, best[1])
    ]


# ---------------------------------------------------------------------------
# The plan LRU (sibling of the layout/schedule/program LRUs it unifies).
# ---------------------------------------------------------------------------


_PLAN_CACHE: "OrderedDict" = OrderedDict()
_PLAN_CACHE_MAX = 32
_PLAN_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> Dict[str, int]:
    return dict(_PLAN_STATS)


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    _PLAN_STATS.update(hits=0, misses=0)


def invalidate_plan_cache(reason: str = "reconfigure") -> None:
    """Invalidation entry point — cascaded from
    ``allreduce.invalidate_layout_cache`` (and therefore
    ``supervisor.invalidate_trace_caches``): a plan derived for the dead
    world's layouts can never be valid at the shrunk world size."""
    plan_cache_clear()
    metrics.add("cgx.plan.cache_invalidations")
    from ..utils.logging import get_logger

    get_logger().info("step-plan cache invalidated (%s)", reason)


def note_membership(generation: int, world_size: int) -> None:
    """Elastic membership hook (``robustness/elastic.py``): the group
    just reshaped to ``world_size`` members at ``generation`` — grow or
    shrink. Plans solved for any other world are dead; the first
    post-reshape step re-derives its plan (and the bandwidth split /
    chunk geometry underneath it) at the bumped generation. Distinct
    from the eviction cascade only in attribution: the metric and log
    line name the membership event so a grow's re-plan cost is
    distinguishable from a failure's."""
    invalidate_plan_cache(f"membership g{generation} ws{world_size}")
    metrics.add("cgx.plan.membership_replans")


def _model_fingerprint(model: CostModel) -> Tuple:
    return dataclasses.astuple(model)


def _plan_key(group_sig, ws, route, reduction) -> Tuple:
    return (
        group_sig,
        int(ws),
        route,
        reduction,
        cfg_mod.planner_mode(),
        cfg_mod.planner_avg_bits(),
        sched_mod._chip_fingerprint(),
        cfg_mod.registry_version(),
        _model_fingerprint(cost_model()),
        # The memory-envelope staging budget (ISSUE 18): active only
        # under CGX_MEMLEDGER, where it can veto pipeline depths — both
        # the gate and the budget itself must key the cache, or a
        # budget-filtered plan would be served to an unfiltered config
        # (and vice versa). None when the ledger is off keeps unset
        # bit-identical to the pre-ledger key.
        _staging_budget(),
        _PLAN_VERSION,
    )


def _staging_budget() -> Optional[int]:
    """Per-slice host staging budget for the solver's envelope filter:
    the arena cap (``CGX_SHM_MAX_MB``), the hard wall the pressure path
    enforces at runtime. None = filter off (``CGX_MEMLEDGER`` unset)."""
    if not cfg_mod.memledger_enabled():
        return None
    return cfg_mod.shm_max_mb() << 20


def plan_for_layout(
    groups: Sequence, ws: int, *, route: str, reduction: str
) -> Optional[StepPlan]:
    """The step plan for one allreduce_tree layout (its ``_GroupLayout``
    rows, duck-typed: ``cc``/``slices`` per group) — from the plan LRU,
    solving on miss. None when nothing plans (ws == 1, a non-SRA
    reduction, or no compressed slice): the caller then runs the legacy
    path unchanged. Trace-time Python only — nothing here stages into
    the program beyond the knobs the decisions set."""
    if ws <= 1 or reduction != cfg_mod.REDUCTION_SRA:
        return None
    if cfg_mod.dummy_compression() or cfg_mod.fake_ratio() is not None:
        return None
    if not any(g.cc.enabled for g in groups):
        return None
    group_sig = tuple(
        (g.cc, tuple(g.slices)) for g in groups
    )
    key = _plan_key(group_sig, ws, route, reduction)
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_CACHE.move_to_end(key)
        _PLAN_STATS["hits"] += 1
        metrics.add("cgx.plan.cache_hits")
        return hit
    _PLAN_STATS["misses"] += 1
    metrics.add("cgx.plan.cache_misses")
    model = cost_model()
    avg_bits = cfg_mod.planner_avg_bits()
    flat: List[Tuple[int, CompressionConfig]] = []
    spans: List[Tuple[int, int]] = []  # (group idx, n slices)
    for gi, g in enumerate(groups):
        spans.append((gi, len(g.slices)))
        for (_off, ln) in g.slices:
            flat.append((ln, g.cc))
    decs = solve(
        flat, ws, model=model, route=route, avg_bits=avg_bits,
        staging_budget=_staging_budget(),
    )
    per_group: List[Tuple[SliceDecision, ...]] = []
    pos = 0
    for _gi, n_s in spans:
        per_group.append(tuple(decs[pos:pos + n_s]))
        pos += n_s
    # Reverse-layer emission: backward produces tail groups first, so
    # their collectives overlap earlier layers' compute (the PR 9 trick
    # — the cost model's overlap_frac term assumes it).
    order = tuple(reversed(range(len(groups))))
    predicted = model.predict_step(
        [d.predicted_s for d in decs], reverse_order=True
    )
    # Per-phase decomposition at solve time: the predicted baseline the
    # PlanDriftMonitor holds measured critical-path components against.
    comp_tot = {"quantize": 0.0, "wire": 0.0, "overhead": 0.0}
    for (n_el, cc), d in zip(flat, decs):
        parts = model.predict_slice_components(
            d.n, ws, d.bits, cc.bucket_size, chunks=d.chunks, route=route
        )
        for k, v in parts.items():
            comp_tot[k] += v
    comp_tot["compute"] = float(model.compute_s)
    pred_components = tuple(sorted(comp_tot.items()))
    plan = StepPlan(
        decisions=tuple(per_group),
        order=order,
        predicted_s=predicted,
        version=_PLAN_VERSION,
        pred_components=pred_components,
    )
    _PLAN_CACHE[key] = plan
    if len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    metrics.add("cgx.plan.compiled")
    metrics.set("cgx.plan.predicted_step_s", float(predicted))
    for comp, secs in pred_components:
        metrics.set(f"cgx.plan.pred_component.{comp}", float(secs))
    for gi, gdecs in enumerate(per_group):
        for si, d in enumerate(gdecs):
            label = f"g{gi}s{si}"
            metrics.set(f"cgx.plan.slice_chunks.{label}", float(d.chunks))
            metrics.set(f"cgx.plan.slice_bits.{label}", float(d.bits))
    from ..observability import flightrec, timeline

    rec = dict(
        groups=len(groups),
        slices=len(decs),
        ws=int(ws),
        route=route,
        predicted_ms=round(predicted * 1e3, 3),
        pred_components={
            k: round(v * 1e3, 4) for k, v in pred_components
        },
        version=_PLAN_VERSION,
        model=cost_model().source,
        decisions=[
            {"n": d.n, "bits": d.bits, "chunks": d.chunks}
            for d in decs[:16]
        ],
    )
    flightrec.record("step_plan", **rec)
    timeline.instant("step_plan", cat=timeline.CAT_TRACE, **rec)
    return plan


def decide_slice(
    n: int,
    ws: int,
    cc: CompressionConfig,
    reduction: str,
    *,
    route: str = "staged",
) -> Optional[SliceDecision]:
    """Single-slice convenience (the eager ``xla_allreduce`` plane): the
    plan for a one-group/one-slice layout. Gated on :func:`engaged`
    itself — eager callers have no allreduce_tree front door to gate
    for them."""
    if not engaged():
        return None
    g = _OneGroup(cc=cc, slices=((0, int(n)),))
    plan = plan_for_layout([g], ws, route=route, reduction=reduction)
    if plan is None:
        return None
    return plan.decisions[0][0]


@dataclasses.dataclass(frozen=True)
class _OneGroup:
    cc: CompressionConfig
    slices: Tuple[Tuple[int, int], ...]


def bridge_chunks(
    width: int, bucket: int, ws: int, bits: int, default: int
) -> int:
    """The bridge worker loop's depth decision (``backend._sched_tables``
    consults this through ``sys.modules`` — the bridge must not import
    the parallel package; a process that never loaded the planner runs
    ``backend._plan_bridge_chunks``, the dependency-light DEFAULT-model
    mirror pinned equal to this function): predicted-cost argmin over
    the feasible depths of one rank-chunk. Host plane → bridge
    engagement rules (:func:`engaged_bridge`, env-only). Installing a
    CALIBRATED model changes this decision, so it must be installed
    group-wide from identical bytes (``bench.py --planner`` builds it
    from the shared span files) — the group-consistency contract every
    CGX_* knob already carries."""
    if not engaged_bridge() or width <= 0 or ws <= 1:
        return default
    model = cost_model()
    best_c, best_t = 1, float("inf")
    units = width // max(1, bucket)
    for c in CHUNK_CANDIDATES:
        if c > max(1, units):
            continue
        t = model.predict_slice(
            width * ws, ws, bits, bucket, chunks=c, route="bridge"
        )
        if t < best_t - 1e-15:
            best_c, best_t = c, t
    metrics.add("cgx.plan.bridge_hints")
    metrics.set("cgx.plan.bridge_chunks", float(best_c))
    return best_c


# ---------------------------------------------------------------------------
# The async route (PR 13): sync two-level vs async-H cost curves.
# ---------------------------------------------------------------------------


def solve_async_h(
    n: int,
    n_slices: int,
    bits: int,
    bucket: int,
    *,
    model: Optional[CostModel] = None,
    step_s: Optional[float] = None,
) -> Tuple[int, float]:
    """(best H, predicted per-inner-step seconds) over
    ``ASYNC_H_CANDIDATES`` — argmin of :meth:`CostModel.predict_outer`.
    Slower DCN pushes H up (the cadence-window term), the drift term
    pulls it back down; ties prefer the SMALLER H (tighter coupling for
    the same predicted time)."""
    model = model or cost_model()
    best_h, best_t = ASYNC_H_CANDIDATES[0], float("inf")
    for h in ASYNC_H_CANDIDATES:
        t = model.predict_outer(
            n, n_slices, bits, bucket, h, step_s=step_s
        )
        if t < best_t - 1e-15:
            best_h, best_t = h, t
    return best_h, best_t


def async_route(
    n: int,
    n_slices: int,
    bits: int,
    bucket: int,
    *,
    model: Optional[CostModel] = None,
    step_s: Optional[float] = None,
) -> Tuple[str, int]:
    """The ``CGX_ASYNC=auto`` decision: ("async" | "sync", H).

    Sync arm: the synchronous two-level cross exchange — the SAME
    payload priced by :meth:`CostModel.predict_slice` with the wire rate
    swapped to the calibrated DCN tier (``dcn_gbps``), paid EVERY inner
    step. Async arm: the best-H outer loop
    (:func:`solve_async_h`). The curves cross where DCN gets slow enough
    that amortizing it over H steps (and taking it off the critical
    path) beats compressing harder — exactly the regime the ROADMAP's
    "many slices across DCs" tier lives in. Gauged
    (``cgx.async.route_pred_ratio``) so drift between the two
    predictions is visible in cgx_top/cgx_report."""
    model = model or cost_model()
    dcn_model = dataclasses.replace(model, wire_gbps=model.dcn_gbps)
    t_sync = dcn_model.predict_slice(
        n, max(2, n_slices), bits, bucket, chunks=1, route="bridge"
    )
    h_best, t_async = solve_async_h(
        n, n_slices, bits, bucket, model=model, step_s=step_s
    )
    route = "async" if t_async < t_sync else "sync"
    metrics.set("cgx.async.route_h", float(h_best))
    if t_sync > 0:
        metrics.set(
            "cgx.async.route_pred_ratio", round(t_async / t_sync, 6)
        )
    from ..observability import flightrec

    flightrec.record(
        "async_route",
        route=route,
        h=h_best,
        predicted_async_ms=round(t_async * 1e3, 6),
        predicted_sync_ms=round(t_sync * 1e3, 6),
        n=int(n),
        n_slices=int(n_slices),
        model=model.source,
    )
    return route, h_best


# ---------------------------------------------------------------------------
# The serve plan (PR 15): page size + shipping depth from the cost model.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """The serving plane's planner decision."""

    page_tokens: int
    ship_depth: int
    predicted_ttft_s: float
    predicted_page_s: float


def solve_serve_plan(
    prompt_tokens: int,
    kv_token_bytes: int,
    n_layers: int,
    bits: int,
    bucket: int,
    *,
    state_lane_bytes: int = 0,
    model: Optional[CostModel] = None,
) -> ServePlan:
    """argmin of :meth:`CostModel.predict_serve` over the candidate
    (page size, shipping depth) grid — the ``CGX_KV_PAGE_TOKENS=0`` /
    ``CGX_KV_SHIP_DEPTH=0`` decision (``serving/adapter.py
    ServeConfig.from_env``). Ties prefer the smaller page and the
    shallower depth (less pool fragmentation / fewer in-flight frames
    for the same predicted TTFT). Host-side trace-time Python — nothing
    here stages into a program beyond the shapes the decision sets (and
    those shapes re-key the decode-program cache through the serving
    knob fingerprint)."""
    model = model or cost_model()
    best: Optional[Tuple[float, int, int, float]] = None
    for pt in SERVE_PAGE_CANDIDATES:
        for depth in SERVE_DEPTH_CANDIDATES:
            ttft, per_frame = model.predict_serve(
                prompt_tokens, kv_token_bytes, n_layers, bits, bucket,
                pt, depth, state_lane_bytes,
            )
            if best is None or ttft < best[0] - 1e-15:
                best = (ttft, pt, depth, per_frame)
    assert best is not None
    ttft, pt, depth, per_frame = best
    metrics.set("cgx.plan.serve_page_tokens", float(pt))
    metrics.set("cgx.plan.serve_ship_depth", float(depth))
    metrics.set("cgx.plan.serve_pred_ttft_s", float(ttft))
    from ..observability import flightrec

    flightrec.record(
        "serve_plan",
        page_tokens=pt,
        ship_depth=depth,
        predicted_ttft_ms=round(ttft * 1e3, 3),
        bits=int(bits),
        prompt_tokens=int(prompt_tokens),
        state_lane_bytes=int(state_lane_bytes),
        model=model.source,
    )
    return ServePlan(
        page_tokens=pt, ship_depth=depth,
        predicted_ttft_s=ttft, predicted_page_s=per_frame,
    )


# ---------------------------------------------------------------------------
# The eager donated-buffer program plane (bench / parity harnesses).
# ---------------------------------------------------------------------------


def planned_allreduce(
    per_rank,
    *,
    mesh=None,
    axis: Optional[str] = None,
    cc: Optional[CompressionConfig] = None,
    reduction: Optional[str] = None,
    key=None,
):
    """Planner-staged sibling of ``xla_allreduce.staged_allreduce``: the
    plan's (chunks, bits) decision applied to the whole ``(ws, n)``
    payload and staged as ONE donated-buffer XLA program (the input
    stack is donated — the planner plane owns its buffer, so the reduced
    output reuses it instead of double-buffering ``n*ws`` floats). The
    program rides ``xla_allreduce``'s bounded LRU under a planner-keyed
    entry; bit-equal to ``staged_allreduce`` under the equivalent static
    knobs (``CGX_SCHEDULE=on`` + ``CGX_SCHED_CHUNKS=<plan>`` — pinned in
    tests/test_planner.py)."""
    from . import mesh as mesh_mod
    from . import xla_allreduce as xla_mod

    mesh = mesh if mesh is not None else mesh_mod.flat_mesh()
    axis = axis or mesh.axis_names[0]
    cc = cc or cfg_mod.default_compression_config()
    reduction = reduction or cfg_mod.topology_from_env().intra_reduction
    return xla_mod.staged_allreduce_planned(
        per_rank, mesh=mesh, axis=axis, cc=cc, reduction=reduction, key=key
    )


# ---------------------------------------------------------------------------
# The host-side driver (the WireController's planner-era superset).
# ---------------------------------------------------------------------------


class StepPlanner:
    """Drive the calibrate → re-solve → restage loop from the training
    loop, host-side::

        plr = StepPlanner(every=500, avg_bits=4)
        for step in range(n_steps):
            params, opt_state, loss = train_step(...)
            plr.step()   # every 500 steps: recalibrate + re-plan

    ``avg_bits`` — optional payload-weighted average-width budget; when
    set the planner also drives the WireController's closed-loop bit
    re-solve (the registry write the lint ownership rule sanctions only
    through this module). ``spans_dir`` — where to calibrate span rates
    from (default ``CGX_METRICS_DIR``).

    **Idempotent re-plan**: :meth:`update` adopts a recalibrated model
    (dropping plans + bumping the plan version, i.e. ONE retrace) only
    when the model actually changed; unchanged telemetry is a counted
    no-op — no registry bump, no retrace storm.

    **Multi-process hazard (the EF-placement class of warning)**: with
    ``CGX_PLANNER_MODEL`` set, :meth:`update` adopts from THAT file —
    identical bytes on every rank, so SPMD processes always plan (and
    retrace) together; write a new calibration with
    :meth:`calibrate_to` (one writer — rank 0 or an operator). WITHOUT
    the file, :meth:`update` calibrates from process-local telemetry:
    correct single-process, but two processes adopting different local
    models would stage divergent programs and hang the step — on
    multi-process runs always set ``CGX_PLANNER_MODEL``."""

    def __init__(
        self,
        *,
        every: int = 500,
        avg_bits: Optional[float] = None,
        spans_dir: Optional[str] = None,
    ):
        if every < 0:
            raise ValueError(f"every must be >= 0, got {every}")
        self.every = every
        self.avg_bits = avg_bits
        self.spans_dir = spans_dir
        self.updates = 0
        self._count = 0
        self._controller = None
        if avg_bits:
            from ..wire.controller import WireController

            self._controller = WireController(avg_bits, every=0)

    def step(self) -> bool:
        """Note one training step; every ``every``-th call re-plans.
        Returns True when an update ran (adopted or no-op)."""
        self._count += 1
        if self.every and self._count % self.every == 0:
            self.update()
            return True
        return False

    def calibrate_to(self, path: str) -> CostModel:
        """Recalibrate from live telemetry and persist to ``path`` — the
        one-writer side of the ``CGX_PLANNER_MODEL`` group-consistency
        channel (every rank's :meth:`update` then adopts the same
        bytes)."""
        model = CostModel.from_telemetry(self.spans_dir)
        model.save(path)
        return model

    def update(self) -> bool:
        """Re-resolve the model now (the ``CGX_PLANNER_MODEL`` file when
        set — group-consistent bytes; process-local telemetry
        otherwise); adopt only on change. Returns True when a new model
        (or bit allocation) was adopted."""
        global _MODEL, _PLAN_VERSION
        if cfg_mod.planner_model_path():
            model = _model_from_file() or CostModel.default()
        else:
            model = CostModel.from_telemetry(self.spans_dir)
        # source is provenance, not a model term: a recalibration that
        # reproduces the same numbers from different evidence is a no-op.
        changed = dataclasses.replace(model, source="") != dataclasses.replace(
            cost_model(), source=""
        )
        if changed:
            _MODEL = model
            _PLAN_VERSION += 1
            plan_cache_clear()
            metrics.add("cgx.plan.replans")
        else:
            metrics.add("cgx.plan.replan_noops")
        if self._controller is not None:
            # The absorbed bit solver: same gather → solve → write-back
            # loop, idempotent by the controller's own contract.
            alloc = self._controller.update()
            changed = changed or bool(
                alloc and alloc != getattr(self, "_last_alloc", None)
            )
            self._last_alloc = dict(alloc) if alloc else None
        self.updates += 1
        # Predicted-vs-measured gauge for the report/top tooling.
        try:
            hist = metrics.snapshot_typed()["histograms"].get("cgx.step.time_s")
        except Exception:
            hist = None
        pred = metrics.get("cgx.plan.predicted_step_s")
        if hist and hist.get("p50") and pred:
            metrics.set("cgx.plan.pred_ratio", float(pred) / float(hist["p50"]))
        from ..observability import flightrec

        flightrec.record(
            "step_planner",
            adopted=changed,
            version=_PLAN_VERSION,
            model=cost_model().source,
        )
        return changed
