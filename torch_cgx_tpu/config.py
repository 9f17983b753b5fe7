"""Configuration system for the TPU-native CGX rebuild.

Reproduces the reference's three-tier config surface
(/root/reference/src/common/common.h:24-41, compressor.h:34-43,
compressor.cc:39-60 — see SURVEY.md §5.6):

1. ``CGX_*`` environment variables, re-read on every allreduce call
   (the reference re-reads env per DDP bucket,
   mpi_allreduce_operations.cc:238; tests mutate env between calls).
2. A per-layer registry keyed by ``(bucket_idx, layer_idx)`` — numeric, for
   torch-bridge parity with ``torch_cgx.register_layer``
   (ProcessGroupCGX.cc:837-857) — plus a JAX-idiomatic name-pattern registry
   for pytree leaves.
3. Static defaults (compile-time flags in the reference become plain
   defaults here).

Everything that influences traced shapes (bits, bucket_size, reduction
algorithm, world sizes) is hashable/static so jit caches per config.
"""

from __future__ import annotations

import dataclasses
import re
import threading
from typing import Dict, Hashable, Optional, Tuple

from .utils import env as _env

# ---------------------------------------------------------------------------
# Env var names — parity with reference src/common/common.h:24-41.
# ---------------------------------------------------------------------------

COMPRESSION_QUANTIZATION_BITS = "CGX_COMPRESSION_QUANTIZATION_BITS"
COMPRESSION_BUCKET_SIZE = "CGX_COMPRESSION_BUCKET_SIZE"
COMPRESSION_MINIMAL_SIZE = "CGX_COMPRESSION_MINIMAL_SIZE"
COMPRESSION_SKIP_INCOMPLETE_BUCKETS = "CGX_COMPRESSION_SKIP_INCOMPLETE_BUCKETS"
COMPRESSION_FAKE_RATIO = "CGX_COMPRESSION_FAKE_RATIO"
FUSION_BUFFER_SIZE_MB = "CGX_FUSION_BUFFER_SIZE_MB"
INNER_COMMUNICATOR_TYPE = "CGX_INNER_COMMUNICATOR_TYPE"
CROSS_COMMUNICATOR_TYPE = "CGX_CROSS_COMMUNICATOR_TYPE"
INNER_REDUCTION_TYPE = "CGX_INNER_REDUCTION_TYPE"
CROSS_REDUCTION_TYPE = "CGX_CROSS_REDUCTION_TYPE"
INTRA_BROADCAST = "CGX_INTRA_BROADCAST"
INTRA_COMPRESS = "CGX_INTRA_COMPRESS"
REMOTE_BUF_COMPRESSION = "CGX_REMOTE_BUF_COMPRESSION"
DEBUG_DUMMY_COMPRESSION = "CGX_DEBUG_DUMMY_COMPRESSION"
DEBUG_ALL_TO_ALL_REDUCTION = "CGX_DEBUG_ALL_TO_ALL_REDUCTION"
DEBUG_FORCE_CODEC = "CGX_DEBUG_FORCE_CODEC"
STANDALONE_LAYER_ELEMS = "CGX_STANDALONE_LAYER_ELEMS"
# TPU-only additions (no reference analogue):
SHM = "CGX_SHM"  # bridge same-host data plane (shm_communicator.cc role)
LAYER_ALIGNED_SPLIT = "CGX_LAYER_ALIGNED_SPLIT"  # greedy split, .cc:265-299
SHM_DIR = "CGX_SHM_DIR"  # override /dev/shm
SHM_HOST_ID = "CGX_SHM_HOST_ID"  # override host fingerprint (test hook)
FSDP_ALLGATHER_BITS = "CGX_FSDP_ALLGATHER_BITS"  # 0 (off, default) | 2..8
STOCHASTIC_ROUNDING = "CGX_STOCHASTIC_ROUNDING"  # QSGD_DETERMENISTIC inverse
CODEC_IMPL = "CGX_CODEC_IMPL"  # "xla" | "pallas" | "auto"
CODEC_ENCODE = "CGX_CODEC_ENCODE"  # "div" (byte-identical) | "mul" (fast)
METRICS_RUNTIME = "CGX_METRICS_RUNTIME"  # per-execution wire counters
BRIDGE_DEVICE_CODEC = "CGX_BRIDGE_DEVICE_CODEC"  # "auto" | "on" | "off"
BRIDGE_DEVICE_MIN_NUMEL = "CGX_BRIDGE_DEVICE_MIN_NUMEL"
SEED = "CGX_SEED"
LOG_LEVEL = "CGX_LOG_LEVEL"
# Robustness layer (fault harness + hardened data plane — docs/ROBUSTNESS.md):
BRIDGE_TIMEOUT_MS = "CGX_BRIDGE_TIMEOUT_MS"  # bounded bridge waits
WIRE_CHECKSUM = "CGX_WIRE_CHECKSUM"  # shm payload integrity check
SHM_MAX_MB = "CGX_SHM_MAX_MB"  # arena growth cap before pressure errors
NONFINITE_GUARD = "CGX_NONFINITE_GUARD"  # off | skip | exact
FAULTS = "CGX_FAULTS"  # fault-injection spec (robustness/faults.py grammar)
FAULTS_SEED = "CGX_FAULTS_SEED"
# Self-healing recovery supervisor (robustness/supervisor.py — PR 5):
RECOVERY_RETRIES = "CGX_RECOVERY_RETRIES"  # bounded wait retries (rung 1)
RECOVERY_BACKOFF_MS = "CGX_RECOVERY_BACKOFF_MS"  # retry backoff base
RECOVERY_CORRUPT_THRESHOLD = "CGX_RECOVERY_CORRUPT_THRESHOLD"  # rung 2 gate
SNAPSHOT_EVERY = "CGX_SNAPSHOT_EVERY"  # in-memory step snapshot cadence
# Observability layer (docs/OBSERVABILITY.md):
METRICS_DIR = "CGX_METRICS_DIR"  # flight-recorder dumps + metric exports
METRICS_FLUSH_S = "CGX_METRICS_FLUSH_S"  # periodic exporter interval
QERR_STATS = "CGX_QERR_STATS"  # per-layer relative-L2 quantization error
FLIGHTREC_CAP = "CGX_FLIGHTREC_CAP"  # flight-recorder ring capacity
# In-XLA single-program allreduce + topology router (parallel/topology.py,
# parallel/xla_allreduce.py — PR 7):
XLA_ALLREDUCE = "CGX_XLA_ALLREDUCE"  # auto | on | off — staged-program routing
SRA_EPILOGUE_MIN_ELEMS = "CGX_SRA_EPILOGUE_MIN_ELEMS"  # fused-epilogue floor
# Compiled collective schedules (parallel/schedule.py — PR 9):
SCHEDULE = "CGX_SCHEDULE"  # auto | on | off — chunked pipelined collectives
SCHED_CHUNKS = "CGX_SCHED_CHUNKS"  # pipeline depth (chunks per fusion slice)
# Unified wire plane (wire/edges.py + wire/dispatch.py — per-edge
# compression for MoE all-to-all, ring-attention K/V hops, pipeline
# activations and PowerSGD factors):
WIRE = "CGX_WIRE"  # auto | on | off — edge-dispatcher engagement
WIRE_BITS = "CGX_WIRE_BITS"  # env-default bits for unregistered edges
# Whole-step mega-schedule planner (parallel/planner.py — PR 12):
PLANNER = "CGX_PLANNER"  # auto | on | off — step-level plan compiler
PLANNER_AVG_BITS = "CGX_PLANNER_AVG_BITS"  # joint-solve bit budget (0 = off)
PLANNER_MODEL = "CGX_PLANNER_MODEL"  # calibrated CostModel json (group-wide)
# Codec roofline round 2 (ops/codec_pallas.py + ops/fused_producer.py —
# PR 11):
PALLAS_PACK = "CGX_PALLAS_PACK"  # sum | butterfly — bit-plane pack lowering
PALLAS_TILE_CHUNKS = "CGX_PALLAS_TILE_CHUNKS"  # explicit tile override
SRA_ACCUM = "CGX_SRA_ACCUM"  # exact | int8 — epilogue accumulation domain
PRODUCER_FUSE = "CGX_PRODUCER_FUSE"  # auto | on | off — fused grad quantize
# Asynchronous cross-slice plane (parallel/async_plane.py +
# torch_backend/async_bridge.py — PR 13): decoupled DCN exchange with
# hierarchical local-SGD, bounded staleness and planner-aware H.
ASYNC = "CGX_ASYNC"  # off | on | auto — decoupled cross-slice outer loop
ASYNC_H = "CGX_ASYNC_H"  # inner steps per outer round (0 = planner decides)
ASYNC_MAX_LAG = "CGX_ASYNC_MAX_LAG"  # bounded staleness, in outer rounds
ASYNC_OUTER = "CGX_ASYNC_OUTER"  # outer optimizer: sgd | nesterov
ASYNC_OUTER_LR = "CGX_ASYNC_OUTER_LR"  # outer learning rate
ASYNC_OUTER_MOMENTUM = "CGX_ASYNC_OUTER_MOMENTUM"  # nesterov momentum
# Serving data plane (torch_cgx_tpu/serving/ — PR 15): paged quantized
# KV-cache wire for disaggregated prefill/decode with continuous batching.
KV_BITS = "CGX_KV_BITS"  # kv_page wire width (0 = raw f16 shipping)
KV_PAGE_TOKENS = "CGX_KV_PAGE_TOKENS"  # tokens per KV page (0 = planner)
KV_SHIP_DEPTH = "CGX_KV_SHIP_DEPTH"  # prefill pages in flight (0 = planner)
SERVE_MAX_BATCH = "CGX_SERVE_MAX_BATCH"  # decode lanes (continuous batching)
SERVE_MAX_PAGES = "CGX_SERVE_MAX_PAGES"  # KV block-pool capacity, in pages
SERVE_MAX_SEQ = "CGX_SERVE_MAX_SEQ"  # per-sequence KV capacity, in tokens
SERVE_PREFILL_TIMEOUT_MS = "CGX_SERVE_PREFILL_TIMEOUT_MS"  # failover bound
SERVE_TTFT_SLO_MS = "CGX_SERVE_TTFT_SLO_MS"  # SLO controller: TTFT target
SERVE_TPS_SLO = "CGX_SERVE_TPS_SLO"  # SLO controller: tokens/s target
# Elastic membership (robustness/elastic.py — PR 16): checkpoint-free
# rank join with snapshot-page state transfer over the kv transport.
ELASTIC = "CGX_ELASTIC"  # master enable for the elastic join plane
JOIN_TIMEOUT_MS = "CGX_JOIN_TIMEOUT_MS"  # bound on every join-path wait
JOIN_DONORS = "CGX_JOIN_DONORS"  # snapshot-page donor fan-out
# Live health plane (observability/health.py + watch.py — PR 6):
HEALTH = "CGX_HEALTH"  # master enable for the streaming health engine
HEALTH_INTERVAL_S = "CGX_HEALTH_INTERVAL_S"  # evaluator sample interval
HEALTH_STRAGGLER_FACTOR = "CGX_HEALTH_STRAGGLER_FACTOR"  # skew score gate
HEALTH_STEP_FACTOR = "CGX_HEALTH_STEP_FACTOR"  # step-time regression gate
HEALTH_PLAN_DRIFT_FACTOR = "CGX_HEALTH_PLAN_DRIFT_FACTOR"  # drift-loop gate
HEALTH_QERR_SLO = "CGX_HEALTH_QERR_SLO"  # compression-quality SLO (rel-L2)
MEMLEDGER = "CGX_MEMLEDGER"  # master enable for the per-rank memory ledger
MEM_FLUSH_S = "CGX_MEM_FLUSH_S"  # ledger sample/flush interval (seconds)
MEM_LEAK_WINDOW = "CGX_MEM_LEAK_WINDOW"  # sliding-window samples for leak/OOM calls
PROM_PORT = "CGX_PROM_PORT"  # Prometheus text exposition endpoint
# Supervised socket data plane (torch_backend/transport.py — PR 20):
TRANSPORT = "CGX_TRANSPORT"  # "" | auto | socket | store | shm
TRANSPORT_RETRIES = "CGX_TRANSPORT_RETRIES"  # reconnects before degrade
TRANSPORT_BACKOFF_MS = "CGX_TRANSPORT_BACKOFF_MS"  # reconnect backoff base
TRANSPORT_IO_TIMEOUT_MS = "CGX_TRANSPORT_IO_TIMEOUT_MS"  # per-op socket bound
TRANSPORT_PING_MS = "CGX_TRANSPORT_PING_MS"  # idle-link ping cadence
TRANSPORT_RING = "CGX_TRANSPORT_RING"  # un-acked resend ring capacity
TRANSPORT_HOST = "CGX_TRANSPORT_HOST"  # advertised listener address

# Defaults — reference values (common.h:24-41, compressor.h:32,
# mpi_allreduce_operations.h:32).
DEFAULT_BITS = 32  # 32 == compression off
DEFAULT_BUCKET_SIZE = 512
DEFAULT_MINIMAL_SIZE = 16  # MIN_LAYER_SIZE: tiny tensors bypass compression
DEFAULT_FUSION_MB = 64
MIN_FUSION_SIZE = 2048
MAX_BITS = 8  # compression active iff bits <= 8

# Reduction algorithms (utils.h ReductionType; SRA default intra, Ring default
# cross — mpi_allreduce_operations.cc:74-115).
REDUCTION_SRA = "SRA"
REDUCTION_RING = "RING"
REDUCTION_ALLTOALL = "ALLTOALL"  # CGX_DEBUG_ALL_TO_ALL_REDUCTION analogue
REDUCTION_PSUM = "PSUM"  # XLA-native fallback (uncompressed)

_VALID_REDUCTIONS = (REDUCTION_SRA, REDUCTION_RING, REDUCTION_ALLTOALL, REDUCTION_PSUM)


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Per-layer compression parameters.

    Mirror of the reference ``CompressionLayerConfig`` (compressor.h:34-43):
    ``bits`` (1-8 active, >8 = off), quantization ``bucket_size``, and the
    skip-incomplete-buckets toggle (residual tail sent raw).
    """

    bits: int = DEFAULT_BITS
    bucket_size: int = DEFAULT_BUCKET_SIZE
    skip_incomplete_buckets: bool = False
    stochastic: bool = False

    def __post_init__(self):
        # 0 is the "unset — inherit env default at lookup" sentinel, matching
        # the reference's zero-backfill (compressor.cc:47-60).
        if self.bits < 0:
            raise ValueError(f"bits must be >= 0, got {self.bits}")
        if self.bucket_size < 0:
            raise ValueError(f"bucket_size must be >= 0, got {self.bucket_size}")

    @property
    def enabled(self) -> bool:
        """Compression eligibility on bits alone (compressor.cc:421-425);
        0 = unset."""
        return 1 <= self.bits <= MAX_BITS

    def merged_with_default(self, default: "CompressionConfig") -> "CompressionConfig":
        """Back-fill unset (zero/None) fields from the default config.

        The reference back-fills zeros from env defaults at lookup time
        (compressor.cc:47-60).
        """
        return CompressionConfig(
            bits=self.bits if self.bits else default.bits,
            bucket_size=self.bucket_size if self.bucket_size else default.bucket_size,
            skip_incomplete_buckets=self.skip_incomplete_buckets
            or default.skip_incomplete_buckets,
            stochastic=self.stochastic or default.stochastic,
        )


def default_compression_config() -> CompressionConfig:
    """Read the env-default config (re-read on every call, like
    ``ResetParamsFromEnv`` compressor.cc:258-263)."""
    return CompressionConfig(
        bits=_env.get_int_env_or_default(COMPRESSION_QUANTIZATION_BITS, DEFAULT_BITS),
        bucket_size=_env.get_int_env_or_default(
            COMPRESSION_BUCKET_SIZE, DEFAULT_BUCKET_SIZE
        ),
        skip_incomplete_buckets=_env.get_bool_env_or_default(
            COMPRESSION_SKIP_INCOMPLETE_BUCKETS, False
        ),
        stochastic=stochastic_rounding(),
    )


def minimal_size() -> int:
    return _env.get_int_env_or_default(COMPRESSION_MINIMAL_SIZE, DEFAULT_MINIMAL_SIZE)


def fusion_threshold_elems(element_size: int = 4) -> int:
    """Fusion slice capacity in elements (reference: 64 MB slices,
    mpi_allreduce_operations.cc:128-133, common.h:40)."""
    mb = _env.get_int_env_or_default(FUSION_BUFFER_SIZE_MB, DEFAULT_FUSION_MB)
    return max(MIN_FUSION_SIZE, (mb * 1024 * 1024) // element_size)


def _reduction_from_env(name: str, default: str) -> str:
    raw = _env.get_str_env_or_default(name, default).upper()
    if raw in ("SRA", "SCATTER_REDUCE_ALLGATHER"):
        return REDUCTION_SRA
    if raw == "RING":
        return REDUCTION_RING
    if raw in ("ALLTOALL", "ALL_TO_ALL"):
        return REDUCTION_ALLTOALL
    if raw == "PSUM":
        return REDUCTION_PSUM
    raise ValueError(f"{name}={raw!r}: expected one of {_VALID_REDUCTIONS}")


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Hierarchical reduction strategy over the (cross, intra) mesh axes.

    TPU mapping of the reference's two-level MPI topology
    (mpi_context.cc:25-35, mpi_allreduce_operations.cc:70-115,139-185):
    the intra/"local" level rides the ICI mesh axis, the cross level the DCN
    axis. Communicator *types* (SHM/MPI/NCCL) are accepted for CLI/env parity
    but are advisory on TPU — the transport is always XLA collectives.
    """

    intra_reduction: str = REDUCTION_SRA
    cross_reduction: str = REDUCTION_RING
    intra_broadcast: bool = True  # CGX_INTRA_BROADCAST default on (.cc:134)
    intra_compress: bool = True  # CGX_INTRA_COMPRESS default on (.cc:135)
    cross_compress: bool = True

    def __post_init__(self):
        for r in (self.intra_reduction, self.cross_reduction):
            if r not in _VALID_REDUCTIONS:
                raise ValueError(f"unknown reduction {r!r}")


def topology_from_env() -> TopologyConfig:
    if _env.get_bool_env_or_default(DEBUG_ALL_TO_ALL_REDUCTION, False):
        intra = cross = REDUCTION_ALLTOALL
    else:
        intra = _reduction_from_env(INNER_REDUCTION_TYPE, REDUCTION_SRA)
        cross = _reduction_from_env(CROSS_REDUCTION_TYPE, REDUCTION_RING)
    return TopologyConfig(
        intra_reduction=intra,
        cross_reduction=cross,
        intra_broadcast=_env.get_bool_env_or_default(INTRA_BROADCAST, True),
        intra_compress=_env.get_bool_env_or_default(INTRA_COMPRESS, True),
    )


def fake_ratio() -> Optional[float]:
    """CGX_COMPRESSION_FAKE_RATIO: debug traffic shaping — reduce only the
    leading ``ratio`` fraction of each compressed slice so transport cost can
    be measured at a synthetic compression ratio
    (mpi_allreduce_operations.cc:130-144). Deliberately breaks correctness
    (the tail is left un-reduced), exactly like the reference. None = off."""
    v = _env.get_float_env_or_default(COMPRESSION_FAKE_RATIO, 0.0)
    if v <= 0.0 or v >= 1.0:
        return None
    return v


def layer_aligned_split() -> bool:
    """CGX_LAYER_ALIGNED_SPLIT: opt-in greedy chunk split that keeps layers
    whole within a rank's chunk (Quantizer::GetSizesAndOffsets semantics,
    compressor.cc:265-299) instead of the equal 8-aligned split. Bridge
    only: the SPMD path needs equal static chunk shapes for all_to_all."""
    return _env.get_bool_env_or_default(LAYER_ALIGNED_SPLIT, False)


def shm_enabled() -> bool:
    """CGX_SHM: the bridge's same-host shared-memory byte plane (the
    reference's default intra-node transport, shm_communicator.cc:116-177).
    On by default; rendezvous/creation failures fall back to the store."""
    return _env.get_bool_env_or_default(SHM, True)


def dummy_compression() -> bool:
    """CGX_DEBUG_DUMMY_COMPRESSION: pass-through codec for debugging
    (mpi_allreduce_operations.cc:46-54)."""
    return _env.get_bool_env_or_default(DEBUG_DUMMY_COMPRESSION, False)


def force_codec() -> bool:
    """CGX_DEBUG_FORCE_CODEC: run the quantize + self-dequantize round trip
    even on a 1-device axis (where the allreduce is the identity). Lets a
    single chip measure the codec work each rank performs inside SRA — the
    bench harness's north-star proxy uses it."""
    return _env.get_bool_env_or_default(DEBUG_FORCE_CODEC, False)


def runtime_metrics() -> bool:
    """CGX_METRICS_RUNTIME: bump wire-traffic counters at EXECUTION time via
    a host callback (one per compiled allreduce group per step per device
    program), not just at trace time — runtime observability the reference's
    printf-only logging lacks (SURVEY §5.5). Off by default: the callback
    costs a host round trip per step."""
    return _env.get_bool_env_or_default(METRICS_RUNTIME, False)


def fsdp_allgather_config() -> Optional["CompressionConfig"]:
    """CGX_FSDP_ALLGATHER_BITS: compress the FSDP *parameter* all-gather
    (``all_gather_into_tensor``) at this many bits — the other half of
    ZeRO-3's per-step traffic, which the gradient reduce-scatter codec
    leaves raw. 0 (default) disables; 2-8 enable a max-min wire at that
    width using the default bucket size. The reference cannot run FSDP at
    all (ProcessGroupCGX.cc:631-636 throws), so this knob is beyond-
    reference completion, default-off for exactness.
    """
    bits = _env.get_int_env_or_default(FSDP_ALLGATHER_BITS, 0)
    if bits <= 0:
        return None
    if not 2 <= bits <= MAX_BITS:
        raise ValueError(
            f"{FSDP_ALLGATHER_BITS} must be 0 (off) or 2..{MAX_BITS}, got {bits}"
        )
    base = default_compression_config()
    return dataclasses.replace(base, bits=bits)


def standalone_layer_elems() -> int:
    """Leaves at least this large form their own fusion group: their flat
    view is free (reshape), so they skip the gather-concat/scatter-back
    copies entirely. Small leaves still fuse (the reference's motivation
    for fusion is amortizing per-message latency of SMALL layers,
    mpi_allreduce_operations.cc:201-227; a multi-megabyte tensor needs no
    amortizing)."""
    return _env.get_int_env_or_default(STANDALONE_LAYER_ELEMS, 1 << 20)


def codec_impl() -> str:
    """Which codec implementation to use: "xla" (pure lax ops), "pallas"
    (fused TPU kernels), or "auto" (pallas on TPU, xla elsewhere)."""
    impl = _env.get_str_env_or_default(CODEC_IMPL, "auto").lower()
    if impl not in ("xla", "pallas", "auto"):
        raise ValueError(f"{CODEC_IMPL} must be xla|pallas|auto, got {impl!r}")
    return impl


SRA_EPILOGUE = "CGX_SRA_EPILOGUE"


def sra_epilogue() -> str:
    """SRA epilogue lowering: "auto" (the fused dequant-accumulate-
    requantize Pallas kernel on TPU for payloads at or above
    ``CGX_SRA_EPILOGUE_MIN_ELEMS``, the staged reference path elsewhere
    and below the crossover), "fused" (force the fused kernel at any
    size — interpret mode off-TPU; test knob), or "staged" (force the
    reference path everywhere). Wire bytes are identical between
    lowerings on the default ``div`` encode (docs/COMPRESSION_GUIDE.md
    "reduce_rows and the wire-identity contract")."""
    mode = _env.get_str_env_or_default(SRA_EPILOGUE, "auto").lower()
    if mode not in ("auto", "fused", "staged"):
        raise ValueError(
            f"{SRA_EPILOGUE} must be auto|fused|staged, got {mode!r}"
        )
    return mode


def xla_allreduce() -> str:
    """CGX_XLA_ALLREDUCE: routing mode of the in-XLA single-program
    quantized allreduce (``parallel/xla_allreduce.py``) for intra-slice
    groups, decided per collective by the topology router
    (``parallel/topology.py``):

    * "auto" (default) — stage intra-slice traffic only on a real TPU
      backend; everywhere else the existing paths run unchanged (staged
      programs, store keys and wire bytes are bit-identical with the knob
      unset — the grad_sync bit-identity suite pins this).
    * "on" — stage intra-slice traffic on any backend (CPU multi-device
      included), and route MIXED groups (a mesh spanning slices with >1
      device per slice) to the reference's two-level scheme: uncompressed
      ICI reduce inside the slice, compressed exchange across slices.
    * "off" — never route; the bridge/per-call paths keep all traffic.
    """
    mode = _env.get_str_env_or_default(XLA_ALLREDUCE, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"{XLA_ALLREDUCE} must be auto|on|off, got {mode!r}"
        )
    return mode


def schedule_mode() -> str:
    """CGX_SCHEDULE: chunked quantize->wire->epilogue pipelining of the
    compressed collectives (``parallel/schedule.py``):

    * "auto" (default) — pipeline only where it is bit-inert to enable:
      the staged in-XLA plane on a real TPU backend (where the latency-
      hiding scheduler can actually overlap the per-chunk collectives
      with the codec kernels). Everywhere else — CPU/CI, and the host
      bridge, whose pipelined schedule changes store keys — the existing
      monolithic paths run unchanged: staged programs, store keys and
      wire bytes are bit-identical with the knob unset (the grad_sync
      bit-identity suite pins this).
    * "on" — pipeline everywhere the schedule compiler can derive a
      multi-chunk plan: the staged plane on any backend (CPU multi-device
      benches/tests) AND the bridge worker loop (double-buffered
      encode/put/take/epilogue windows; per-chunk store keys).
    * "off" — never pipeline.
    """
    mode = _env.get_str_env_or_default(SCHEDULE, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"{SCHEDULE} must be auto|on|off, got {mode!r}")
    return mode


def planner_mode() -> str:
    """CGX_PLANNER: engagement of the whole-step schedule planner
    (``parallel/planner.py``) — the step-level compiler that sees every
    fusion slice and wire edge of a train step at once and jointly picks
    (pipeline depth, bit-width, emission order) against a trace-
    calibrated cost model:

    * "auto" (default) — plan only on a real TPU backend and only from a
      calibrated cost model (``CGX_PLANNER_MODEL`` or an in-process
      adoption; the built-in default rates were never measured on a TPU,
      so without one the static ``CGX_SCHED_CHUNKS`` schedule runs and
      ``cgx.plan.uncalibrated_static`` counts it); on every
      CPU/CI path no plan is derived and the staged programs, store keys
      and wire bytes are bit-identical to the pre-planner code
      (jaxpr-pinned in tests/test_planner.py).
    * "on" — plan on any backend (the CPU test/bench configuration) and
      let the bridge worker loop consume depth hints too (the bridge is
      a host plane, so "auto means TPU" never applies there).
    * "off" — never plan; the static knobs (``CGX_SCHED_CHUNKS``,
      per-layer bits) govern exactly as before.
    """
    mode = _env.get_str_env_or_default(PLANNER, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"{PLANNER} must be auto|on|off, got {mode!r}")
    return mode


def planner_avg_bits() -> float:
    """CGX_PLANNER_AVG_BITS: payload-weighted average bit-width budget of
    the planner's joint solve — when set, the planner re-allocates bits
    across a step's fusion slices (marginal allocation, the
    ``adaptive.solve_bit_allocation`` solver) instead of keeping each
    slice's resolved width. 0 (default) = keep resolved widths (the
    bit-equality configuration: a plan then changes only chunking and
    emission order, never wire bytes)."""
    v = _env.get_float_env_or_default(PLANNER_AVG_BITS, 0.0)
    if v and not 1.0 <= v <= float(MAX_BITS):
        raise ValueError(
            f"{PLANNER_AVG_BITS} must be 0 (off) or in [1, {MAX_BITS}], got {v}"
        )
    return v


def planner_model_path() -> Optional[str]:
    """CGX_PLANNER_MODEL: path of a persisted calibrated cost model
    (``planner.CostModel.save``'s json) every rank loads at decision
    time — the group-consistency channel for calibrated models: the SAME
    bytes reach every rank (JAX-side or pure-bridge), so planner depth
    decisions can never diverge across a group the way per-process
    in-memory calibration could. Unset (default) = the built-in default
    model (or a model installed in-process via
    ``planner.set_cost_model``)."""
    v = _env.get_str_env_or_default(PLANNER_MODEL, "")
    return v or None


DEFAULT_SCHED_CHUNKS = 4


def sched_chunks() -> int:
    """CGX_SCHED_CHUNKS: target pipeline depth — how many chunks the
    schedule compiler splits each fusion slice into. The compiler rounds
    chunk boundaries to the wire-layout alignment (``ws * bucket_size``
    elements) so a pipelined schedule quantizes every element in the
    same bucket as the monolithic layout (bit-equal results on aligned
    payloads — docs/PERF_NOTES.md "Compiled schedules"); payloads too
    small for the requested depth get fewer chunks, down to 1 (no
    pipeline). Default 4: enough depth that chunk k+1's quantize, chunk
    k's wire and chunk k-1's epilogue genuinely co-exist, small enough
    that per-chunk fixed costs stay amortized."""
    v = _env.get_int_env_or_default(SCHED_CHUNKS, DEFAULT_SCHED_CHUNKS)
    return max(v, 1)


DEFAULT_SRA_EPILOGUE_MIN_ELEMS = 1 << 20


def sra_epilogue_min_elems() -> int:
    """CGX_SRA_EPILOGUE_MIN_ELEMS: payload floor (decoded elements =
    rows x chunk) below which ``CGX_SRA_EPILOGUE=auto`` keeps the STAGED
    epilogue lowering even on TPU dispatch. Small fused buckets lose to
    the staged path — the kernel's per-call fixed cost dominates before
    its HBM-traffic savings amortize (BENCH_LOG
    ``sra_epilogue_fused_vs_staged_4bit_1MB_x8``: fused 6.5 ms vs staged
    1.0 ms at 2^18 elements, fused winning by ~1.9x at 2^27). Default
    2^20 (a 4 MB fp32 payload) sits safely above the measured losing
    region; re-measure the crossover per chip with
    ``tools/qbench.py sra_epilogue`` and tune. ``CGX_SRA_EPILOGUE=fused``
    still forces the kernel at any size (the test/bench knob)."""
    v = _env.get_int_env_or_default(
        SRA_EPILOGUE_MIN_ELEMS, DEFAULT_SRA_EPILOGUE_MIN_ELEMS
    )
    return max(v, 0)


def sra_accum() -> str:
    """CGX_SRA_ACCUM: accumulation domain of the fused SRA epilogue's
    peer-row fold (``codec_pallas._sra_epilogue_kernel``):

    * "exact" (default) — the audited f32 fold (decode each peer row,
      ``v0 + v1 + ...`` ascending): bit-identical wire bytes vs the
      staged reference lowering.
    * "int8" — fixed-point fold: peer rows stay in the int8 level domain
      and accumulate as ``sum_r lvl_r * s_r`` in int32, where ``s_r`` is
      the row's per-bucket unit snapped to a 12-fraction-bit fixed-point
      multiple of the block's max unit — ONE f32 conversion per block
      instead of one per peer row, and no full-width f32 peer-row
      intermediate. Wire bytes differ from "exact" within a bounded
      envelope (unit error <= U/2^13 per row — far inside the
      quantization envelope; tested); all devices in a program share one
      mode, so reducer error symmetry holds. Opt-in, like
      ``CGX_CODEC_ENCODE=mul``."""
    mode = _env.get_str_env_or_default(SRA_ACCUM, "exact").lower()
    if mode not in ("exact", "int8"):
        raise ValueError(f"{SRA_ACCUM} must be exact|int8, got {mode!r}")
    return mode


def producer_fuse() -> str:
    """CGX_PRODUCER_FUSE: producer-fused gradient quantization
    (``ops/fused_producer.py``) — the backward matmul of a wrapped dense
    layer emits the layer's SRA stage-1 wire payload directly (already
    bucketed, already packed), so the dp_grad enters the staged allreduce
    as a QTensor and the f32 gradient never round-trips HBM:

    * "auto" (default) — engage only on a real TPU backend; everywhere
      else the wrapped layers lower to the plain matmul and the staged
      programs stay BIT-IDENTICAL to the unwrapped code (jaxpr-pinned,
      like ``CGX_WIRE``/``CGX_SCHEDULE``).
    * "on" — engage on any backend (the CPU test/bench configuration;
      the fused matmul+quantize kernel still requires aligned geometry,
      with a compose fallback that quantizes the same values).
    * "off" — never engage."""
    mode = _env.get_str_env_or_default(PRODUCER_FUSE, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"{PRODUCER_FUSE} must be auto|on|off, got {mode!r}")
    return mode


def bridge_device_codec() -> str:
    """Whether the torch bridge stages segments through the accelerator for
    codec work (DLPack -> jitted JAX codec -> one copy back): "on", "off",
    or "auto" (on only when JAX's default backend is a TPU). The reference
    runs its codec on the device holding the gradients
    (ProcessGroupCGX.cc:374-407); this is the TPU-host analogue."""
    mode = _env.get_str_env_or_default(BRIDGE_DEVICE_CODEC, "auto").lower()
    if mode not in ("on", "off", "auto"):
        raise ValueError(
            f"{BRIDGE_DEVICE_CODEC} must be on|off|auto, got {mode!r}"
        )
    return mode


def bridge_device_min_numel() -> int:
    """Segments below this element count stay on the host codec (the
    host<->device hop has fixed latency; tiny segments lose)."""
    return _env.get_int_env_or_default(BRIDGE_DEVICE_MIN_NUMEL, 65536)


def global_seed() -> int:
    return _env.get_int_env_or_default(SEED, 0)


def bridge_timeout_ms() -> Optional[int]:
    """CGX_BRIDGE_TIMEOUT_MS: deadline for every blocking bridge wait —
    collective key waits, standalone shm takes, and the arena pressure
    path. Unset/0 = keep the group/default timeout (300 s). A peer that
    dies without reaching ``abort()`` surfaces as a
    :class:`~.robustness.errors.BridgeTimeoutError` within this budget
    instead of hanging."""
    v = _env.get_int_env_or_default(BRIDGE_TIMEOUT_MS, 0)
    return v if v > 0 else None


def wire_checksum() -> bool:
    """CGX_WIRE_CHECKSUM: carry a crc32 of every shm payload in its header
    and verify on ``take()`` (mismatch -> one fresh re-read ->
    :class:`~.robustness.errors.WireCorruptionError`). Default on; set 0
    to shave the checksum cost off latency-critical benches."""
    return _env.get_bool_env_or_default(WIRE_CHECKSUM, True)


def shm_max_mb() -> int:
    """CGX_SHM_MAX_MB: total arena capacity cap per writer. The
    grow-don't-block policy stays, but growth past this cap turns into a
    bounded backoff-and-reclaim wait, then a pressure error naming the
    un-acked key — instead of eating tmpfs until the host OOMs under a
    dead reader."""
    return _env.get_int_env_or_default(SHM_MAX_MB, 1024)


def metrics_dir() -> Optional[str]:
    """CGX_METRICS_DIR: target directory for flight-recorder dumps
    (``flightrec-rank<N>.jsonl``), periodic metric exports
    (``metrics-rank<N>.jsonl``) and leader cluster reports
    (``cluster-report.jsonl``). Unset (default) = all of those are
    no-ops — the clean path touches no filesystem and stays
    bit-identical (docs/OBSERVABILITY.md)."""
    v = _env.get_str_env_or_default(METRICS_DIR, "")
    return v or None


def metrics_flush_s() -> float:
    """CGX_METRICS_FLUSH_S: interval of the periodic per-rank metrics
    exporter (only active when CGX_METRICS_DIR is set)."""
    v = _env.get_float_env_or_default(METRICS_FLUSH_S, 10.0)
    return v if v > 0 else 10.0


def qerr_stats() -> bool:
    """CGX_QERR_STATS: stage a per-layer relative-L2 quantization-error
    measurement (this device's contribution vs its wire decode) into the
    compressed allreduce, reported through a host callback into the
    ``cgx.qerr.<path>`` histograms and the flight recorder. Off by
    default: enabling it adds a decode + norm pass per layer to the
    traced program (the clean path stays bit-identical only when off)."""
    return _env.get_bool_env_or_default(QERR_STATS, False)


def flightrec_cap() -> int:
    """CGX_FLIGHTREC_CAP: flight-recorder ring capacity in events."""
    v = _env.get_int_env_or_default(FLIGHTREC_CAP, 512)
    return v if v > 0 else 512


def wire_mode() -> str:
    """CGX_WIRE: engagement of the unified wire plane (``wire/``) — the
    per-edge compression dispatcher every non-allreduce collective routes
    through (MoE all-to-all, ring-attention K/V hops, pipeline activation
    hops, PowerSGD factor reductions):

    * "auto" (default) — the dispatcher compresses only on a real TPU
      backend, and only edges with a resolvable config. On every CPU/CI
      path the staged programs stay bit-identical to the pre-wire code
      (the knob-off inertness suite pins this).
    * "on" — compress resolvable edges on any backend (the CPU
      multi-device test/bench configuration).
    * "off" — never compress; every edge sends raw collectives.

    Unset with an empty edge registry and ``CGX_WIRE_BITS`` unset, every
    edge resolves to no config, so no program, store key or wire byte
    changes regardless of mode.
    """
    mode = _env.get_str_env_or_default(WIRE, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"{WIRE} must be auto|on|off, got {mode!r}")
    return mode


def wire_default_bits() -> int:
    """CGX_WIRE_BITS: env-default quantization width for wire edges with
    no registered config — the one-knob way to compress EVERY routed edge
    (MoE/ring/pipeline/PowerSGD-factor) at once. 0 (default) = off:
    unregistered edges stay raw. 1..8 enable a max-min wire at that width
    using the default bucket size. dp_grad edges are NOT covered (their
    default is the existing ``CGX_COMPRESSION_QUANTIZATION_BITS``)."""
    v = _env.get_int_env_or_default(WIRE_BITS, 0)
    if v and not 1 <= v <= MAX_BITS:
        raise ValueError(
            f"{WIRE_BITS} must be 0 (off) or 1..{MAX_BITS}, got {v}"
        )
    return v


def health_enabled() -> bool:
    """CGX_HEALTH: run the per-rank streaming health engine
    (``observability/health.py``) — online EWMA/P² estimators over the
    typed instruments, straggler scoring from collective-phase skew, and
    typed ``HealthEvent`` publication to the supervisor/Prometheus/
    ``cgx_top`` consumers. Off by default: with it unset no thread runs,
    no hot-path hook fires, and the clean path stays bit-identical
    (docs/OBSERVABILITY.md "Live health plane")."""
    return _env.get_bool_env_or_default(HEALTH, False)


def health_interval_s() -> float:
    """CGX_HEALTH_INTERVAL_S: sample interval of the health evaluator
    thread. Each tick is a registry read + pure-Python estimator update
    (microseconds), so sub-second intervals are safe."""
    v = _env.get_float_env_or_default(HEALTH_INTERVAL_S, 1.0)
    return v if v > 0 else 1.0


def health_straggler_factor() -> float:
    """CGX_HEALTH_STRAGGLER_FACTOR: a peer whose collective-phase wait
    signal exceeds the median peer's by this factor (sustained over two
    consecutive samples) is flagged as a straggler."""
    v = _env.get_float_env_or_default(HEALTH_STRAGGLER_FACTOR, 3.0)
    return v if v > 0 else 3.0


def health_step_factor() -> float:
    """CGX_HEALTH_STEP_FACTOR: step-time regression gate — the fast EWMA
    of step time exceeding the slow (baseline) EWMA by this factor raises
    a ``step_regression`` event."""
    v = _env.get_float_env_or_default(HEALTH_STEP_FACTOR, 2.0)
    return v if v > 0 else 2.0


def health_plan_drift_factor() -> float:
    """CGX_HEALTH_PLAN_DRIFT_FACTOR: plan-drift gate — a measured
    critical-path component (``cgx.critpath.component.*``) exceeding the
    plan's solve-time prediction (``cgx.plan.pred_component.*``) by this
    factor, sustained, raises a ``plan_drift`` event and pokes the
    planner's re-calibration (``observability.health.PlanDriftMonitor``)."""
    v = _env.get_float_env_or_default(HEALTH_PLAN_DRIFT_FACTOR, 1.5)
    return v if v > 0 else 1.5


def health_qerr_slo() -> Optional[float]:
    """CGX_HEALTH_QERR_SLO: compression-quality SLO — a ``cgx.qerr.*``
    relative-L2 p90 above this threshold raises a ``qerr_slo`` event
    (requires CGX_QERR_STATS for the qerr stream to exist). Unset/0 =
    no quality SLO."""
    v = _env.get_float_env_or_default(HEALTH_QERR_SLO, 0.0)
    return v if v > 0 else None


def memledger_enabled() -> bool:
    """CGX_MEMLEDGER: run the per-rank memory ledger — a unified byte
    accountant over every byte-owning surface (shm arena regions, the
    paged KV pool, snapshot rings, the staged-program caches, wire
    staging) with a sliding-window leak detector and a linear-trend
    OOM forecaster on top. Off by default: unset means zero hooks fire
    on any hot path, the planner's staging-budget filter stays out of
    the plan key, and staged programs / store keys / wire bytes are
    bit-identical to the ledger never having existed. Host-side
    observability only — deliberately NOT part of
    trace_knob_fingerprint()."""
    return _env.get_bool_env_or_default(MEMLEDGER, False)


def mem_flush_s() -> float:
    """CGX_MEM_FLUSH_S: sample/flush interval of the memory ledger —
    each tick samples every registered pool, refreshes the
    ``cgx.mem.*`` gauges, advances the leak/forecast windows, and
    (when CGX_METRICS_DIR is set) appends a ``mem-rank<N>.jsonl``
    snapshot line."""
    v = _env.get_float_env_or_default(MEM_FLUSH_S, 5.0)
    return v if v > 0 else 5.0


def mem_leak_window() -> int:
    """CGX_MEM_LEAK_WINDOW: sliding-window length in ledger samples
    for the leak detector (an owner whose alloc−release delta grows
    strictly monotonically across the full window is named in a
    ``mem_leak`` event) and the OOM forecaster's lead horizon (a pool
    whose linear-trend time-to-exhaustion drops inside
    window × CGX_MEM_FLUSH_S raises ``mem_pressure``). Floor of 3:
    two points cannot distinguish a trend from noise."""
    v = _env.get_int_env_or_default(MEM_LEAK_WINDOW, 5)
    return v if v >= 3 else 3


def prom_port() -> Optional[int]:
    """CGX_PROM_PORT: serve every ``cgx.*`` instrument plus the health
    engine's state as Prometheus text exposition on
    ``127.0.0.1:<port>/metrics`` (stdlib http.server; 0 = pick an
    ephemeral port, published to ``CGX_METRICS_DIR/prom-rank<N>.json``).
    Unset (default) = no endpoint."""
    raw = _env.get_str_env_or_default(PROM_PORT, "")
    if raw == "":
        return None
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{PROM_PORT} must be an integer port, got {raw!r}")
    if v < 0 or v > 65535:
        raise ValueError(f"{PROM_PORT} out of range: {v}")
    return v


def recovery_retries() -> int:
    """CGX_RECOVERY_RETRIES: how many times an expired bounded bridge wait
    is re-armed (exponential backoff + jitter, ``cgx.recovery.retries``)
    before the error escalates to the supervisor's eviction rung. 0
    (default) = recovery off — failures raise exactly as before, and no
    staged program or wire byte changes (docs/ROBUSTNESS.md Recovery).
    Waits whose heartbeat already names a dead suspect skip the retries:
    a SIGKILL'd peer will not come back, and burning ``retries`` full
    timeouts on it only delays the eviction rung."""
    v = _env.get_int_env_or_default(RECOVERY_RETRIES, 0)
    return max(v, 0)


def recovery_backoff_ms() -> float:
    """CGX_RECOVERY_BACKOFF_MS: base of the retry rung's exponential
    backoff (doubled per retry, plus up-to-50% uniform jitter so retrying
    ranks do not stampede the store in lockstep)."""
    v = _env.get_float_env_or_default(RECOVERY_BACKOFF_MS, 100.0)
    return v if v > 0 else 100.0


def recovery_corrupt_threshold() -> int:
    """CGX_RECOVERY_CORRUPT_THRESHOLD: after this many
    ``WireCorruptionError`` incidents in one supervised run, the ladder's
    degrade rung closes the shm byte plane and the whole group falls back
    to the store transport (coordinated through the generation
    rendezvous, so no rank keeps posting to a channel its peers stopped
    reading)."""
    v = _env.get_int_env_or_default(RECOVERY_CORRUPT_THRESHOLD, 2)
    return v if v > 0 else 2


_VALID_TRANSPORTS = ("", "auto", "socket", "store", "shm")


def transport_mode() -> str:
    """CGX_TRANSPORT: which data plane carries cross-rank payload bytes.
    Unset/"" (default) = the legacy store+shm paths, byte-identical to
    every prior release. ``socket`` = the supervised TCP plane of
    ``torch_backend/transport.py`` for every remote hop; ``auto`` =
    socket only when the group actually spans hosts (same-host groups
    keep shm); ``store``/``shm`` = pin the legacy planes explicitly
    (documentation aliases of the default routing). Host-side routing
    only — no staged program or wire *payload* byte depends on it."""
    v = _env.get_str_env_or_default(TRANSPORT, "").strip().lower()
    if v not in _VALID_TRANSPORTS:
        raise ValueError(
            f"{TRANSPORT} must be one of {_VALID_TRANSPORTS[1:]}, got {v!r}"
        )
    return v


def transport_retries() -> int:
    """CGX_TRANSPORT_RETRIES: failed reconnect attempts (backoff +
    jitter, ``retry.WaitRetry``) before the supervisor degrades a peer
    edge from the socket plane back to the store plane mid-run."""
    v = _env.get_int_env_or_default(TRANSPORT_RETRIES, 3)
    return max(v, 0)


def transport_backoff_ms() -> float:
    """CGX_TRANSPORT_BACKOFF_MS: base of the reconnect ladder's
    exponential backoff (doubled per attempt, up-to-50% jitter)."""
    v = _env.get_float_env_or_default(TRANSPORT_BACKOFF_MS, 50.0)
    return v if v > 0 else 50.0


def transport_io_timeout_ms() -> float:
    """CGX_TRANSPORT_IO_TIMEOUT_MS: deadline for every socket operation
    on the transport plane — connect, recv slice, send. No call on the
    plane ever blocks past it (the analyzer's bounded-io rule enforces
    the discipline statically)."""
    v = _env.get_float_env_or_default(TRANSPORT_IO_TIMEOUT_MS, 2000.0)
    return v if v > 0 else 2000.0


def transport_ping_ms() -> float:
    """CGX_TRANSPORT_PING_MS: idle-link health-check cadence of the
    ``ConnectionSupervisor`` (a PING frame per quiet interval keeps
    dead-peer detection ahead of the bridge timeout)."""
    v = _env.get_float_env_or_default(TRANSPORT_PING_MS, 500.0)
    return v if v > 0 else 500.0


def transport_ring() -> int:
    """CGX_TRANSPORT_RING: capacity (frames) of the per-peer un-acked
    resend ring. A full ring bounds the sender: posts wait for acks and
    eventually degrade the edge rather than growing without bound."""
    v = _env.get_int_env_or_default(TRANSPORT_RING, 256)
    return v if v > 0 else 256


def transport_host() -> str:
    """CGX_TRANSPORT_HOST: the address each rank advertises for its
    transport listener (default 127.0.0.1 — single-host; a fleet sets
    the NIC address)."""
    v = _env.get_str_env_or_default(TRANSPORT_HOST, "").strip()
    return v or "127.0.0.1"


def snapshot_every() -> int:
    """CGX_SNAPSHOT_EVERY: cadence (in steps) of the in-memory training
    state snapshot the supervisor rolls back to after a reconfiguration
    (riding ``checkpoint.snapshot_in_memory``, compression-registry
    snapshot included). 0 (default) = no snapshots — recovery resumes
    from the current state without replay."""
    v = _env.get_int_env_or_default(SNAPSHOT_EVERY, 0)
    return max(v, 0)


def elastic_enabled() -> bool:
    """CGX_ELASTIC: master enable for the elastic membership plane
    (``robustness/elastic.py``) — survivors poll the join-intent counter
    at step boundaries, a preempted-then-respawned rank re-enters through
    the join rendezvous, and the group can GROW back to its original
    world size without a checkpoint file ever touching disk. Off
    (default) = membership is shrink-only, exactly the PR 5 ladder; no
    store traffic, no staged-program or wire-byte changes
    (docs/ROBUSTNESS.md "Elastic membership")."""
    return _env.get_bool_env_or_default(ELASTIC, False)


def join_timeout_ms() -> float:
    """CGX_JOIN_TIMEOUT_MS: the single bound on every join-path wait —
    the joiner's wait for its admit record, the survivors' wait for the
    joiner's ack, the snapshot-page stream's staleness probe, and the
    post-reconfigure ready barrier. A joiner that cannot make the bound
    aborts ALONE (survivors have not reconfigured yet and continue at the
    old generation unharmed); a survivor-side expiry abandons the grow
    and resumes stepping. Survivors therefore never stall longer than
    this bound on a join attempt."""
    v = _env.get_float_env_or_default(JOIN_TIMEOUT_MS, 30000.0)
    return v if v > 0 else 30000.0


def join_donors() -> int:
    """CGX_JOIN_DONORS: snapshot-page donor fan-out — how many survivors
    (ranked by the health plane's load scores, least-loaded first) stripe
    the joiner's state pages (page ordinal modulo donors; every survivor
    holds identical state, so any stripe assignment is correct). 1
    (default) = the single least-loaded survivor ships everything."""
    v = _env.get_int_env_or_default(JOIN_DONORS, 1)
    return max(v, 1)


# ---------------------------------------------------------------------------
# Asynchronous cross-slice plane (PR 13 — docs/PERF_NOTES.md "Asynchronous
# cross-slice plane").
# ---------------------------------------------------------------------------

ASYNC_OUTER_OPTS = ("sgd", "nesterov")
DEFAULT_ASYNC_H = 8
DEFAULT_ASYNC_MAX_LAG = 4
# Aggressive default width for the xslice_delta edge when neither a
# registered edge config nor CGX_WIRE_BITS says otherwise: deltas cross the
# slowest fabric in the system, and local-SGD tolerates coarse outer
# quantization because error feedback carries the residual forward.
DEFAULT_ASYNC_DELTA_BITS = 4


def async_mode() -> str:
    """CGX_ASYNC: engagement of the asynchronous cross-slice plane
    (``parallel/async_plane.py``) — intra-slice gradients keep the staged
    synchronous allreduce while cross-slice exchange becomes a decoupled
    local-SGD outer loop shipping compressed parameter deltas every
    ``CGX_ASYNC_H`` steps through a dedicated sender thread:

    * "off" (default) — never engage. Staged programs, store keys and
      wire bytes are bit-identical to the pre-async code (pinned by
      tests/test_async_plane.py): the knob-unset inertness contract every
      CGX_* plane carries.
    * "on" — engage anywhere the group spans slices. Group-global and
      env-only (the launcher sets it identically on every rank), because
      "skip the cross exchange" is a branch every rank must take together
      or the bridge collective deadlocks — the ``engaged_bridge``
      discipline.
    * "auto" — the step planner decides per topology: the async plane
      engages (and picks H) only where the planner's sync-vs-async cost
      curves say the decoupled exchange wins (``planner.async_route``).
      Inert on every CPU/CI path without ``CGX_PLANNER=on`` — the
      ``CGX_SCHEDULE`` gate discipline.
    """
    mode = _env.get_str_env_or_default(ASYNC, "off").lower()
    if mode not in ("off", "on", "auto"):
        raise ValueError(f"{ASYNC} must be off|on|auto, got {mode!r}")
    return mode


def async_engaged() -> bool:
    """The group-global bridge-plane gate: explicit ``CGX_ASYNC=on`` only.
    "auto" resolves through the planner at the AsyncPlane tier (where the
    payload and topology are known); the bridge's skip-the-cross-stage
    branch must be derivable from env alone on every rank — a per-process
    planner decision diverging across ranks would deadlock the
    collective."""
    return async_mode() == "on"


def async_h() -> int:
    """CGX_ASYNC_H: inner steps per outer round — how often a slice ships
    its compressed parameter delta across DCN. 0 (default) = let the
    planner pick H from its cost curves under ``CGX_ASYNC=auto``
    (``DEFAULT_ASYNC_H`` when the planner is off)."""
    v = _env.get_int_env_or_default(ASYNC_H, 0)
    return max(v, 0)


def async_max_lag() -> int:
    """CGX_ASYNC_MAX_LAG: bounded staleness — the most outer rounds a peer
    slice may fall behind before the health plane's ``async_lag`` event
    escalates to an :class:`~.robustness.errors.AsyncStalenessError` (the
    recovery ladder's entry, same as a bridge timeout). Floor 1: a bound
    of 0 would re-synchronize every round and defeat the plane."""
    v = _env.get_int_env_or_default(ASYNC_MAX_LAG, DEFAULT_ASYNC_MAX_LAG)
    return max(v, 1)


def async_outer() -> str:
    """CGX_ASYNC_OUTER: the outer optimizer applied to the aggregated
    cross-slice delta — "sgd" (default; lr 1.0 makes the outer step plain
    local-SGD averaging) or "nesterov" (DiLoCo's outer momentum)."""
    v = _env.get_str_env_or_default(ASYNC_OUTER, "sgd").lower()
    if v not in ASYNC_OUTER_OPTS:
        raise ValueError(
            f"{ASYNC_OUTER} must be one of {ASYNC_OUTER_OPTS}, got {v!r}"
        )
    return v


def async_outer_lr() -> float:
    """CGX_ASYNC_OUTER_LR: outer learning rate (default 1.0 — with the
    sgd outer that is exact delta averaging)."""
    v = _env.get_float_env_or_default(ASYNC_OUTER_LR, 1.0)
    if v <= 0:
        raise ValueError(f"{ASYNC_OUTER_LR} must be > 0, got {v}")
    return v


def async_outer_momentum() -> float:
    """CGX_ASYNC_OUTER_MOMENTUM: nesterov momentum of the outer optimizer
    (default 0.9, the DiLoCo setting; ignored under the sgd outer)."""
    v = _env.get_float_env_or_default(ASYNC_OUTER_MOMENTUM, 0.9)
    if not 0.0 <= v < 1.0:
        raise ValueError(
            f"{ASYNC_OUTER_MOMENTUM} must be in [0, 1), got {v}"
        )
    return v


# ---------------------------------------------------------------------------
# Serving data plane (PR 15 — docs/SERVING.md). All reads are re-read per
# call like every other config accessor; the trace-affecting subset rides
# ``trace_knob_fingerprint`` (and therefore every staged-program cache key,
# the serving decode-program cache included) so a knob flip can never serve
# a stale compiled decode step.
# ---------------------------------------------------------------------------

DEFAULT_KV_BITS = 8
DEFAULT_KV_PAGE_TOKENS = 16
DEFAULT_SERVE_MAX_BATCH = 8
DEFAULT_SERVE_MAX_PAGES = 256
DEFAULT_SERVE_MAX_SEQ = 256


def kv_bits() -> int:
    """CGX_KV_BITS: env-default max-min quantization width of the
    ``kv_page`` wire edge — the KV-cache pages a prefill worker ships to
    decode workers and the committed pages the decode scheduler's paged
    attention reads. 0 = raw half-precision shipping (the f16 baseline
    the serving bench contrasts against); 1..8 = quantize at that width.
    A registered ``kv_page`` edge config (or the serving SLO controller's
    writes) overrides this per layer — see ``serving/kv_cache.py``
    ``resolve_kv_config``. Default 8: measured token-identical greedy
    decode on the test model (tests/test_serving.py bit-envelope
    suite)."""
    v = _env.get_int_env_or_default(KV_BITS, DEFAULT_KV_BITS)
    if v and not 1 <= v <= MAX_BITS:
        raise ValueError(
            f"{KV_BITS} must be 0 (raw f16) or 1..{MAX_BITS}, got {v}"
        )
    return v


def kv_page_tokens() -> int:
    """CGX_KV_PAGE_TOKENS: tokens per fixed-size KV page — the paged
    allocator's block granularity and the transport's shipping unit.
    0 (default) = let the planner pick from its serve cost curves
    (``planner.solve_serve_plan``; ``DEFAULT_KV_PAGE_TOKENS`` when the
    planner is off). Larger pages amortize per-page meta and store keys;
    smaller pages waste less pool on ragged sequence tails."""
    v = _env.get_int_env_or_default(KV_PAGE_TOKENS, 0)
    return max(v, 0)


def kv_ship_depth() -> int:
    """CGX_KV_SHIP_DEPTH: how many prefill pages the transport sender
    keeps in flight per stream before yielding the thread — the
    pipelining depth of the prefill→decode hop. 0 (default) = planner
    decides (``planner.solve_serve_plan``)."""
    v = _env.get_int_env_or_default(KV_SHIP_DEPTH, 0)
    return max(v, 0)


def serve_max_batch() -> int:
    """CGX_SERVE_MAX_BATCH: decode lanes of the continuous-batching
    scheduler — the static batch dimension of the compiled decode step
    (lanes admit/evict per step; inactive lanes are masked)."""
    v = _env.get_int_env_or_default(SERVE_MAX_BATCH, DEFAULT_SERVE_MAX_BATCH)
    return max(v, 1)


def serve_max_pages() -> int:
    """CGX_SERVE_MAX_PAGES: KV block-pool capacity in pages — the static
    pool dimension of the compiled decode step. Admission blocks (and
    ``cgx.serve.pool_exhausted`` counts) when the refcounted free list
    runs dry."""
    v = _env.get_int_env_or_default(SERVE_MAX_PAGES, DEFAULT_SERVE_MAX_PAGES)
    return max(v, 1)


def serve_max_seq() -> int:
    """CGX_SERVE_MAX_SEQ: per-sequence KV capacity in tokens (prompt +
    generated) — bounds the per-lane page-table width of the compiled
    decode step."""
    v = _env.get_int_env_or_default(SERVE_MAX_SEQ, DEFAULT_SERVE_MAX_SEQ)
    return max(v, 1)


def serve_prefill_timeout_ms() -> float:
    """CGX_SERVE_PREFILL_TIMEOUT_MS: staleness bound on a prefill page
    stream — a partially-delivered stream that stops advancing for this
    long is declared dead and the scheduler FAILS OVER to a local
    prefill (``cgx.serve.prefill_failovers``) instead of wedging the
    admission queue; the recovery-ladder entry for the serving plane
    (docs/SERVING.md "Prefill failover"). Host-side only — never baked
    into a compiled program."""
    v = _env.get_float_env_or_default(SERVE_PREFILL_TIMEOUT_MS, 2000.0)
    return v if v > 0 else 2000.0


def serve_ttft_slo_ms() -> Optional[float]:
    """CGX_SERVE_TTFT_SLO_MS: time-to-first-token SLO the serving SLO
    controller (``serving/slo.py``) re-solves KV bit-width against — a
    ``cgx.serve.ttft_ms`` p90 above this target pushes the kv_page bit
    budget DOWN (fewer wire bytes, faster admission). Unset/0 = no TTFT
    objective. Host-side controller input, never traced."""
    v = _env.get_float_env_or_default(SERVE_TTFT_SLO_MS, 0.0)
    return v if v > 0 else None


def serve_tps_slo() -> Optional[float]:
    """CGX_SERVE_TPS_SLO: aggregate tokens-per-second SLO for the SLO
    controller — a ``cgx.serve.tokens_per_s`` gauge below this target
    pushes the kv_page bit budget down; comfortably above it (and under
    the TTFT target) the budget recovers toward ``CGX_KV_BITS`` for
    quality. Unset/0 = no throughput objective."""
    v = _env.get_float_env_or_default(SERVE_TPS_SLO, 0.0)
    return v if v > 0 else None


def trace_knob_fingerprint() -> Tuple:
    """Every env knob a staged train-step program bakes in at TRACE time,
    in one hashable tuple — the env component of ``make_train_step``'s
    build-cache key (ISSUE 14's knob→cache-key completeness pass found
    the build cache keyed registry/route/schedule/wire/producer eras but
    not the env-derived codec and guard knobs: a
    ``CGX_COMPRESSION_QUANTIZATION_BITS`` or ``CGX_QERR_STATS`` flip
    between calls with an unchanged registry version would serve a stale
    trace). Re-read per build like every other config read — cheap host
    Python, and an env flip can then never hit a stale program.

    The raw ``get_optional_str_env`` reads at the tail mirror knobs whose
    validating parsers live beside their kernels (``codec_pallas.
    _encode_strategy``/``_pack_strategy``/``_forced_tile_chunks``) — the
    fingerprint keys the raw value and leaves validation to the one
    owner, so the two can never drift."""
    return (
        default_compression_config(),
        minimal_size(),
        fusion_threshold_elems(1),
        standalone_layer_elems(),
        topology_from_env(),
        codec_impl(),
        sra_epilogue(),
        sra_epilogue_min_elems(),
        sra_accum(),
        dummy_compression(),
        force_codec(),
        fake_ratio(),
        qerr_stats(),
        runtime_metrics(),
        nonfinite_guard(),
        _env.get_optional_str_env(CODEC_ENCODE),
        _env.get_optional_str_env(PALLAS_PACK),
        _env.get_optional_str_env(PALLAS_TILE_CHUNKS),
        # Serving plane (PR 15): the trace-affecting CGX_KV_*/CGX_SERVE_*
        # subset — each is a static shape or codec width of the compiled
        # decode-step program (serving/scheduler.py keys its program
        # cache on this same fingerprint, the ISSUE 15 knob→key
        # completeness requirement). Host-side serving knobs (failover
        # timeout, SLO targets, ship depth) stay out: they never lower.
        kv_bits(),
        kv_page_tokens(),
        serve_max_batch(),
        serve_max_pages(),
        serve_max_seq(),
    )


NONFINITE_POLICIES = ("off", "skip", "exact")


def nonfinite_guard() -> str:
    """CGX_NONFINITE_GUARD: what the train step does when any rank's
    gradients contain NaN/Inf (detected pre-quantization, agreed globally):
    "off" (default — legacy behavior, the NaN poisons every bucket),
    "skip" (drop the step: params/optimizer/compressor state keep their
    pre-step values), or "exact" (fall back to an uncompressed allreduce of
    the sanitized gradients for that step). See docs/ROBUSTNESS.md."""
    v = _env.get_str_env_or_default(NONFINITE_GUARD, "off").lower()
    if v not in NONFINITE_POLICIES:
        raise ValueError(
            f"{NONFINITE_GUARD} must be one of {NONFINITE_POLICIES}, got {v!r}"
        )
    return v


# ---------------------------------------------------------------------------
# Per-layer registries.
# ---------------------------------------------------------------------------

LayerId = Tuple[int, int]  # (bucket_idx, layer_idx) — reference LayerId

# Numeric registry: exact parity with the reference's static
# ``layers_configs`` map (compressor.h:93-107) + ``layers_sizes_``
# (mpi_allreduce_operations.h:37-49). Used by the torch bridge.
_layer_configs: Dict[LayerId, CompressionConfig] = {}
_layer_sizes: Dict[int, list] = {}  # bucket_idx -> [numel per layer]

# Name-pattern registry: JAX-idiomatic — regex over pytree leaf paths.
_pattern_configs: Dict[str, CompressionConfig] = {}

# Bumped on every registry mutation; trace caches that bake per-layer
# configs in at trace time (make_train_step) key on it so a re-registration
# (e.g. adapt_bits) forces a retrace instead of silently never applying.
_registry_version: int = 0


def registry_version() -> int:
    return _registry_version


def _bump_registry_version() -> None:
    global _registry_version
    _registry_version += 1


def register_layer(
    bucket_idx: int,
    layer_idx: int,
    numel: int,
    bits: int = 0,
    bucket_size: int = 0,
) -> None:
    """Parity API with ``torch_cgx.register_layer``
    (ProcessGroupCGX.cc:837-846, mpi_allreduce_operations.h:37-49).

    Zero bits/bucket_size mean "inherit env default at use time".
    Note: the reference's ``set_quantization_bucket_size`` pybind export
    mistakenly forwards to SetQBits (ProcessGroupCGX.cc:848-850,
    SURVEY.md §8.1) — fixed here, not reproduced.
    """
    sizes = _layer_sizes.setdefault(bucket_idx, [])
    if layer_idx == len(sizes):
        sizes.append(numel)
    elif layer_idx < len(sizes):
        sizes[layer_idx] = numel
    else:
        raise ValueError(
            f"layer_idx {layer_idx} out of order for bucket {bucket_idx} "
            f"(have {len(sizes)} layers)"
        )
    # Zeros are stored as-is and back-filled from the env default at lookup
    # time (get_layer_config), like the reference.
    _layer_configs[(bucket_idx, layer_idx)] = CompressionConfig(
        bits=bits, bucket_size=bucket_size
    )
    _bump_registry_version()


def set_quantization_bits(layer_id: LayerId, bits: int) -> None:
    cfg = _layer_configs.get(layer_id, CompressionConfig(bits=0, bucket_size=0))
    _layer_configs[layer_id] = dataclasses.replace(cfg, bits=bits)


def set_quantization_bucket_size(layer_id: LayerId, bucket_size: int) -> None:
    cfg = _layer_configs.get(layer_id, CompressionConfig(bits=0, bucket_size=0))
    _layer_configs[layer_id] = dataclasses.replace(cfg, bucket_size=bucket_size)


def get_layer_config(layer_id: LayerId) -> CompressionConfig:
    """Resolved config for a (bucket, layer): registered values with zeros
    back-filled from the env default (compressor.cc:47-60)."""
    default = default_compression_config()
    cfg = _layer_configs.get(layer_id)
    if cfg is None:
        return default
    return cfg.merged_with_default(default)


def registered_layer_sizes(bucket_idx: int) -> Optional[list]:
    return _layer_sizes.get(bucket_idx)


def registered_buckets() -> list:
    """Bucket indices with registered layer sizes (torch bridge lookup)."""
    return list(_layer_sizes.keys())


# Side channel: the DDP hook tags the bucket it is about to allreduce so the
# backend can resolve per-layer configs by *identity* instead of guessing from
# the buffer's element count — the analogue of the reference's explicit
# ``bucket_idx_`` rotation (mpi_allreduce_operations.cc:257-285). Thread-local
# because the tag is consumed on the same thread, inside the same
# ``dist.all_reduce`` call the hook makes.
_tls = threading.local()


def set_current_bucket(bucket_key: Optional[Hashable]) -> None:
    _tls.current_bucket = bucket_key


def take_current_bucket() -> Optional[Hashable]:
    key = getattr(_tls, "current_bucket", None)
    _tls.current_bucket = None
    return key


def stochastic_rounding() -> bool:
    """Env-level QSGD switch (the reference's compile-time
    ``QSGD_DETERMENISTIC`` inverse, gpu_rand.h:52-58)."""
    return _env.get_bool_env_or_default(STOCHASTIC_ROUNDING, False)


def set_layer_pattern_config(pattern: str, config: CompressionConfig) -> None:
    """JAX-native per-layer config: regex over parameter tree paths
    (e.g. ``r".*kernel$"``). Later registrations win."""
    re.compile(pattern)  # validate eagerly
    _pattern_configs[pattern] = config
    _bump_registry_version()


def resolve_pattern_config(path: str) -> Optional[CompressionConfig]:
    match = None
    for pattern, cfg in _pattern_configs.items():
        if re.search(pattern, path):
            match = cfg
    if match is None:
        return None
    return match.merged_with_default(default_compression_config())


def clear_registry() -> None:
    """Reset all per-layer registries (the reference keeps them in-process
    statics that survive only until restart — SURVEY.md §5.4)."""
    _layer_configs.clear()
    _layer_sizes.clear()
    _pattern_configs.clear()
    _bump_registry_version()


def reset_registries() -> None:
    """Full config-plane reset: the per-layer registries
    (:func:`clear_registry`) PLUS the wire plane's per-edge registry and
    its derived state (resolution caches, per-edge EF zeroing hooks, the
    closed-loop controller's cadence/allocation) when the ``wire``
    subsystem is loaded. The recovery supervisor's
    ``invalidate_trace_caches`` resets only the derived state (configs
    survive a reconfigure); this entry point is the stronger
    test-harness/new-job reset. Lazy via ``sys.modules`` — importing the
    wire plane from here would cycle (wire imports config)."""
    import sys as _sys

    clear_registry()
    edges_mod = _sys.modules.get("torch_cgx_tpu.wire.edges")
    if edges_mod is not None:
        edges_mod.clear_edges()
        edges_mod.reset_edge_state("reset_registries")
