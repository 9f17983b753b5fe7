"""Per-chip codec-kernel autotuner with a persisted on-disk cache.

The Pallas codec kernels have three free lowering choices the math does
not pin: the grid tile (``tc`` — chunks per block), the bit-plane pack
strategy (``sum`` vs ``butterfly``) and whether the double-buffered
manual-DMA lowering (``CGX_PALLAS_DB``) beats the grid pipeline. The
static heuristics in ``codec_pallas`` pick safe defaults, but the
measured optimum varies per (shape, bits, bucket, chip): the BENCH_r05
session found tc=4 beating tc=16 at some widths on v5-lite while tc=32
wedged the Mosaic compile outright. This module is the GC3-style answer
for the kernel tier: measured best configs live in a bounded in-memory
memo backed by an on-disk JSON cache keyed per chip kind, so one
hardware session's sweep (``bench.py --codec-roofline`` or
``tools/qbench.py``) benefits every later run on the same chip.

Discipline (the layout-/schedule-LRU contract):

* **Keying** — ``(kernel kind, chunk count, bucket, bits, ws)`` plus the
  trace-time lowering knobs that change what a tuned entry means
  (``CGX_CODEC_ENCODE``); the chip kind keys the FILE, so one cache file
  never serves another chip generation.
* **Counters** — ``cgx.codec.autotune_hits`` / ``autotune_misses`` /
  ``autotune_loads`` / ``autotune_tuned`` / ``autotune_invalidations``
  (documented in docs/OBSERVABILITY.md; ``cgx_report``/``cgx_top``
  render the hit rate).
* **Invalidation** — ``supervisor.invalidate_trace_caches`` (and the
  layout-cache invalidation it triggers) drops the in-memory memo, so a
  recovery reconfiguration re-reads from disk instead of serving state
  from the dead generation.
* **Inertness** — ``CGX_AUTOTUNE=auto`` (the default) only *consults*
  the cache; with no cache file on disk every lookup is a miss and the
  static heuristics run unchanged (tier-1 bit-for-bit). Measurement
  happens only through the explicit :func:`tune` API (hardware
  sessions), never inside a traced program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import threading
import time
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

from .. import config as cfg_mod
from ..utils.logging import metrics

# Kernel kinds the tuner distinguishes (each has its own geometry/cost
# profile; "flat" covers both flat quantize and flat dequantize, whose
# tile choice is shared so stochastic draw geometry stays aligned).
KIND_FLAT = "flat"
KIND_CHUNKS = "chunks"
KIND_EPILOGUE = "epilogue"
_KINDS = (KIND_FLAT, KIND_CHUNKS, KIND_EPILOGUE)


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One measured best lowering for a (kind, shape, bits, bucket, ws)
    key: the tile (``tc``), optionally a pack strategy and whether the
    double-buffered DMA lowering won, plus the measured throughput the
    decision was based on (GB/s of kernel input — diagnostic only)."""

    tc: int
    pack: Optional[str] = None
    db: Optional[bool] = None
    gbps: float = 0.0


_LOCK = threading.RLock()
_MEMO: Dict[Tuple, TunedConfig] = {}
_LOADED: Dict[str, bool] = {}  # per cache-file path: disk image merged?
_STATS = {"hits": 0, "misses": 0, "loads": 0, "tuned": 0}


def stats() -> Dict[str, int]:
    """Copy of the {hits, misses, loads, tuned} counters (tests/report)."""
    with _LOCK:
        return dict(_STATS)


def _chip_slug() -> str:
    """Filesystem-safe chip identity: ``<backend>-<device_kind>``. A plan
    measured on one chip generation must never serve another (the
    schedule-LRU ``_chip_fingerprint`` contract)."""
    import jax

    raw = f"{jax.default_backend()}-{jax.devices()[0].device_kind}"
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", raw)


def cache_path() -> Path:
    """The on-disk cache file for the current chip (created on first
    :func:`record`/:func:`tune`; merely looking it up touches nothing)."""
    base = cfg_mod.autotune_dir()
    if base is None:
        base = os.path.join(
            os.path.expanduser("~"), ".cache", "torch_cgx_tpu"
        )
    return Path(base) / f"autotune-{_chip_slug()}.json"


def _env_fingerprint() -> Tuple:
    """Lowering knobs a tuned entry bakes in: an entry measured under one
    encode strategy must not serve another (``mul`` shifts the
    compute/HBM balance the tile choice optimizes)."""
    from . import codec_pallas

    return (codec_pallas._encode_strategy(),)


def _key(kind: str, n_chunks: int, bucket_size: int, bits: int, ws: int):
    if kind not in _KINDS:
        raise ValueError(f"unknown autotune kind {kind!r} (one of {_KINDS})")
    return (kind, int(n_chunks), int(bucket_size), int(bits), int(ws),
            _env_fingerprint())


def _key_str(key: Tuple) -> str:
    kind, n_chunks, bucket, bits, ws, env = key
    return f"{kind}/c{n_chunks}/b{bucket}/q{bits}/w{ws}/e{'-'.join(env)}"


def _load_disk(path: Path) -> None:
    """Merge the on-disk image into the memo once per path (torn/corrupt
    files are ignored entry-wise — the bench-gate torn-file discipline)."""
    spath = str(path)
    if _LOADED.get(spath):
        return
    _LOADED[spath] = True
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(raw, dict):
        return
    _STATS["loads"] += 1
    metrics.add("cgx.codec.autotune_loads")
    for ks, ent in raw.get("entries", {}).items():
        try:
            kind, c, b, q, w, e = ks.split("/")
            key = (kind, int(c[1:]), int(b[1:]), int(q[1:]), int(w[1:]),
                   tuple(x for x in e[1:].split("-") if x))
            cfg = TunedConfig(
                tc=int(ent["tc"]),
                pack=ent.get("pack"),
                db=ent.get("db"),
                gbps=float(ent.get("gbps", 0.0)),
            )
        except (KeyError, ValueError, TypeError):
            continue  # skip unparseable entries, keep the rest
        if cfg.tc >= 1 and key not in _MEMO:
            _MEMO[key] = cfg


def _persist(path: Path) -> None:
    """Atomically rewrite the cache file from the memo (re-merging the
    current disk image first, so concurrent processes tuning different
    shapes don't clobber each other's entries wholesale)."""
    _LOADED.pop(str(path), None)
    _load_disk(path)
    entries = {
        _key_str(k): {
            "tc": c.tc,
            **({"pack": c.pack} if c.pack else {}),
            **({"db": c.db} if c.db is not None else {}),
            "gbps": round(c.gbps, 3),
        }
        for k, c in _MEMO.items()
    }
    doc = {
        "chip": _chip_slug(),
        "updated": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "entries": entries,
    }
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # persistence is best-effort; the memo still serves this run


def lookup(
    kind: str,
    *,
    n_chunks: int,
    bucket_size: int,
    bits: int = 0,
    ws: int = 0,
) -> Optional[TunedConfig]:
    """The tuned config for this kernel shape on this chip, or ``None``
    (mode off, or no measured entry). Pure consultation — never measures,
    never writes; safe at trace time."""
    if cfg_mod.autotune_mode() == "off":
        return None
    key = _key(kind, n_chunks, bucket_size, bits, ws)
    with _LOCK:
        _load_disk(cache_path())
        hit = _MEMO.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            metrics.add("cgx.codec.autotune_hits")
        else:
            _STATS["misses"] += 1
            metrics.add("cgx.codec.autotune_misses")
        return hit


def record(
    kind: str,
    cfg: TunedConfig,
    *,
    n_chunks: int,
    bucket_size: int,
    bits: int = 0,
    ws: int = 0,
    persist: bool = True,
) -> None:
    """Install (and by default persist) a measured best config."""
    if cfg.tc < 1:
        raise ValueError(f"tuned tc must be >= 1, got {cfg.tc}")
    key = _key(kind, n_chunks, bucket_size, bits, ws)
    with _LOCK:
        _MEMO[key] = cfg
        _STATS["tuned"] += 1
        metrics.add("cgx.codec.autotune_tuned")
        if persist:
            _persist(cache_path())


def tune(
    kind: str,
    candidates: Sequence[TunedConfig],
    measure: Callable[[TunedConfig], float],
    *,
    n_chunks: int,
    bucket_size: int,
    bits: int = 0,
    ws: int = 0,
    input_bytes: int = 0,
    persist: bool = True,
) -> Optional[TunedConfig]:
    """Measure ``candidates`` with ``measure(cfg) -> seconds`` and record
    the winner. A candidate whose measurement raises is skipped (a Mosaic
    compile failure for one tile must not kill the sweep — the tc=32
    lesson); all candidates failing returns None and records nothing.
    Gated off entirely under ``CGX_AUTOTUNE=off``."""
    if cfg_mod.autotune_mode() == "off" or not candidates:
        return None
    best: Optional[Tuple[float, TunedConfig]] = None
    for cand in candidates:
        try:
            t = float(measure(cand))
        except Exception:
            continue
        if t <= 0:
            continue
        if best is None or t < best[0]:
            best = (t, cand)
    if best is None:
        return None
    t, cand = best
    gbps = (input_bytes / t / 1e9) if input_bytes else 0.0
    winner = dataclasses.replace(cand, gbps=gbps)
    record(
        kind, winner, n_chunks=n_chunks, bucket_size=bucket_size,
        bits=bits, ws=ws, persist=persist,
    )
    return winner


def invalidate(reason: str = "reconfigure") -> None:
    """Drop the in-memory memo and per-file load marks (the next lookup
    re-reads disk). Called alongside the layout/schedule LRU invalidation
    — ``supervisor.invalidate_trace_caches`` — so no post-recovery
    program consults state cached under the dead generation."""
    with _LOCK:
        _MEMO.clear()
        _LOADED.clear()
        _STATS.update(hits=0, misses=0, loads=0, tuned=0)
    metrics.add("cgx.codec.autotune_invalidations")
    from ..utils.logging import get_logger

    get_logger().info("codec autotune memo invalidated (%s)", reason)


def snap_to_divisor(tc: int, n_chunks: int, cap: int) -> int:
    """Largest divisor of ``n_chunks`` that is <= min(tc, cap): the flat
    kernels' grid requires the tile to divide the chunk count exactly, and
    ``cap`` re-applies the VMEM budget so a stale/corrupt cache entry can
    never stage an over-budget block."""
    tc = max(1, min(int(tc), int(cap), n_chunks))
    for t in range(tc, 0, -1):
        if n_chunks % t == 0:
            return t
    return 1
