#!/usr/bin/env python3
"""Perf regression gate over the committed bench trajectory.

BENCH_LOG.jsonl is the committed round-over-round perf record (bench.py,
tools/shm_bench.py, tools/qbench.py all append to it). Until now a
regression was only caught by a human doing BENCH_LOG archaeology; this
gate makes it mechanical:

* **history** — every valid record in the log (failure records like
  ``device_init_failure`` and ``unresolved`` qbench rows are excluded)
  is normalized to ``(metric key, throughput value)``; the baseline per
  key is the **median** of its history (robust to one lucky/unlucky
  run). ``BASELINE.json``'s ``published`` table, when populated, adds
  hard floors.

  Rows carry ``backend``/``chip`` tags (bench.py's ``log_jsonl`` fills
  them from the live backend; host-side tools tag ``backend: "host"``).
  A device bench that ran on the **CPU stand-in** (``backend``/``chip``
  == ``"cpu"`` — a round in which no chip answered) is keyed into its own
  ``<metric>@cpu``
  trajectory: placeholder rows never mix into the chip-truth median,
  never meet a published floor, and ``--smoke`` skips their
  placeholder-only trajectories entirely.

  Gated metric families (anything with a GB/s unit qualifies
  automatically): the ``pallas_codec_*`` round trips, the
  ``sra_allreduce_*`` multi-device record, the
  ``sra_epilogue_fused_vs_staged_*`` staged-vs-fused epilogue records
  (bench.py emits one per run; a fused-path regression fails the gate
  once the trajectory holds a baseline), the qbench variants, and
  shm_bench.
* **overlap floor** — records carrying a top-level ``overlap_frac``
  (the ``bench.py --schedule`` pipelined rows: cgx_trace attribution's
  share of collective wall time hidden under concurrent compute) gate a
  second trajectory, ``<metric>:overlap_frac``, the same way throughput
  does: higher is better, placeholder rows key ``@cpu``, published
  floors from BASELINE.json apply. A schedule change that quietly
  re-serializes communication fails here even when GB/s barely moves
  (ROADMAP item 2's explicit ask).
* **prediction floor** — records carrying the step planner's own
  cost-model prediction (``pred_ratio`` = predicted / measured step
  time, plus the raw ``predicted_step_ms``/``measured_step_ms`` pair —
  the ``bench.py --planner`` rows) gate a third trajectory,
  ``<metric>:pred_ratio``, whose gated value is prediction ACCURACY
  ``min(r, 1/r)`` — 1.0 = perfect model, and drift in EITHER direction
  (under- or over-prediction) regresses. Candidates additionally
  meet a HARD floor: a record whose measured step time exceeds
  ``predicted * CGX_GATE_PRED_SLACK`` (env; ``--pred-slack`` overrides;
  default 1.5) fails loudly regardless of trajectory history — a
  planner regression and cost-model drift are both caught, the ISSUE 12
  ask. ``@cpu`` separation applies exactly as for throughput.
* **candidate** — a fresh run's JSON records (``--candidate file`` or
  ``-`` for stdin, same schemas the tools print).
* **verdict** — a candidate value more than ``--threshold`` percent
  below its baseline (throughput metrics: lower is worse) fails the
  gate with the offending metric named; exit code 1.

``--smoke`` is the tier-1 self-check: for every metric with >= 2
committed records the *best of the last 3* is treated as the candidate
against the earlier history — validating both the gate logic and that
the committed trajectory contains no sustained cliff (one contended
shared-box run is tolerated; three in a row is a regression).

Default threshold: 30%. The host-side benches (shm_bench on a shared
CI box) show ~±20% run-to-run noise, so 30% flags genuine cliffs (a 2×
regression is caught with huge margin) without tripping on scheduler
jitter; tighten with ``--threshold`` on quiet hardware.

    python tools/bench_gate.py --candidate fresh.jsonl
    python tools/bench_gate.py --smoke            # runs in tier-1
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from statistics import median
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Records that carry no comparable throughput number.
_EXCLUDED_METRICS = {"device_init_failure", "lint_failure"}

# CPU-placeholder suffix: device benches that ran on the CPU fallback
# (rounds in which no chip answered) form their OWN trajectory under
# this suffix, so a
# placeholder row can never dilute the chip-truth baseline (or be
# compared against a published floor measured on silicon).
_PLACEHOLDER_SUFFIX = "@cpu"


def is_placeholder(rec: dict) -> bool:
    """A device bench that actually ran on the CPU stand-in: bench.py's
    ``log_jsonl`` tags every row with the live ``backend``/``chip``
    (host-side tools tag ``backend: "host"`` — genuinely host metrics,
    NOT placeholders)."""
    detail = rec.get("detail") or {}
    return (
        rec.get("backend") == "cpu"
        or rec.get("chip") == "cpu"
        or (isinstance(detail, dict) and detail.get("chip") == "cpu")
    )


# Torn-tolerant JSONL reading is deliberately duplicated across the
# tools/ CLIs (cgx_report, cgx_trace, here): each tool stays a single
# scp-able stdlib-only file.
def _parse_lines(lines) -> List[dict]:
    out = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            continue  # torn tail
    return out


def _read_jsonl(path: str) -> List[dict]:
    try:
        with open(path) as f:
            return _parse_lines(f)
    except OSError:
        return []


def normalize(rec: dict) -> Optional[Tuple[str, float]]:
    """(metric key, higher-is-better value) for one log record, or None
    when the record carries nothing comparable. CPU-placeholder rows get
    the ``@cpu`` key suffix — a separate trajectory from chip truth."""
    norm = _normalize_bare(rec)
    if norm is None:
        return None
    key, v = norm
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, v


# Overlap-fraction floor (ROADMAP item 2): schedule-pipelined bench
# records carry a top-level ``overlap_frac`` — the cgx_trace attribution
# measurement (share of collective wall time hidden under concurrent
# compute). It is gated EXACTLY like a throughput: higher is better, a
# candidate more than --threshold percent below its baseline fails, and
# placeholder rows key into their own ``@cpu`` trajectory. A pipelining
# regression (a schedule change that quietly re-serializes the wire)
# shows up here even when raw GB/s barely moves.
_OVERLAP_SUFFIX = ":overlap_frac"


def normalize_overlap(rec: dict) -> Optional[Tuple[str, float]]:
    """(``<metric>:overlap_frac`` key, fraction) for records carrying the
    cgx_trace overlap measurement, or None. Unlike throughput, 0.0 is a
    VALID (and maximally alarming) measurement — a run whose pipeline
    fully re-serialized must meet the floor head-on, not bypass the gate
    by being too broken to normalize."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    metric = rec.get("metric")
    v = rec.get("overlap_frac")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
        return None
    key = f"{metric}{_OVERLAP_SUFFIX}"
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, float(v)


# Cost-model prediction floor (ISSUE 12): planner bench records carry
# the model's own step-time prediction next to the measurement. The
# gated trajectory value is prediction ACCURACY — min(r, 1/r) of the
# predicted/measured ratio, 1.0 = perfect, lower = drift in EITHER
# direction (a one-sided higher-is-better ratio gate would fail a model
# whose overprediction improved toward 1.0 and could never fail one
# drifting into unbounded overprediction). The hard slack check below
# additionally catches a blown UNDERprediction in a single candidate
# run with no history.
_PRED_SUFFIX = ":pred_ratio"
_DEFAULT_PRED_SLACK = 1.5


def pred_slack() -> float:
    """CGX_GATE_PRED_SLACK: how far a measured step time may exceed the
    planner's prediction before the candidate fails outright."""
    try:
        v = float(os.environ.get("CGX_GATE_PRED_SLACK", ""))
    except ValueError:
        return _DEFAULT_PRED_SLACK
    return v if v > 0 else _DEFAULT_PRED_SLACK


def normalize_pred(rec: dict) -> Optional[Tuple[str, float]]:
    """(``<metric>:pred_ratio`` key, accuracy ``min(r, 1/r)``) for
    records carrying the planner's prediction, or None. The raw ratio
    ``r`` (predicted/measured) is taken from the record when present,
    else derived from the ``predicted_step_ms``/``measured_step_ms``
    pair; the gated value is symmetric around the 1.0 ideal."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    metric = rec.get("metric")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    v = rec.get("pred_ratio")
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        p, m = rec.get("predicted_step_ms"), rec.get("measured_step_ms")
        if (
            isinstance(p, (int, float)) and isinstance(m, (int, float))
            and not isinstance(p, bool) and not isinstance(m, bool)
            and m > 0
        ):
            v = p / m
        else:
            return None
    if v <= 0:
        return None
    key = f"{metric}{_PRED_SUFFIX}"
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, min(float(v), 1.0 / float(v))


def check_pred_slack(
    candidates: List[dict], slack: Optional[float] = None
) -> List[dict]:
    """The HARD prediction floor over a candidate set: any record whose
    measured step time exceeds ``predicted * slack`` fails loudly (no
    baseline history needed — the planner's own prediction IS the
    floor)."""
    slack = pred_slack() if slack is None else slack
    out: List[dict] = []
    for rec in candidates:
        if not isinstance(rec, dict) or rec.get("unresolved"):
            continue
        metric = rec.get("metric")
        p, m = rec.get("predicted_step_ms"), rec.get("measured_step_ms")
        if not metric or not isinstance(p, (int, float)) or not isinstance(
            m, (int, float)
        ) or isinstance(p, bool) or isinstance(m, bool) or p <= 0:
            continue
        if m > p * slack:
            key = f"{metric}:pred_slack"
            if is_placeholder(rec):
                key += _PLACEHOLDER_SUFFIX
            out.append({
                "metric": key,
                "value": round(m, 3),
                "baseline": round(p * slack, 3),
                "delta_pct": round((p * slack - m) / (p * slack) * 100.0, 1),
            })
    return out


# Component-decomposed prediction accuracy (ISSUE 17): records carrying
# a ``pred_components`` dict ({component: predicted/measured ratio})
# gate one ``<metric>:pred_ratio:<component>`` trajectory per component,
# each symmetric around 1.0 exactly like the whole-step ratio above — a
# drift confined to one stage (say the wire model after an interconnect
# change) fails ITS trajectory instead of averaging away inside the
# whole-step number. ``@cpu`` placeholder separation applies unchanged.


def normalize_pred_components(rec: dict) -> List[Tuple[str, float]]:
    """[(``<metric>:pred_ratio:<component>`` key, ``min(r, 1/r)``)] for
    records carrying per-component prediction ratios; [] otherwise."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return []
    metric = rec.get("metric")
    comps = rec.get("pred_components")
    if not metric or metric in _EXCLUDED_METRICS:
        return []
    if not isinstance(comps, dict):
        return []
    suffix = _PLACEHOLDER_SUFFIX if is_placeholder(rec) else ""
    out: List[Tuple[str, float]] = []
    for comp, r in sorted(comps.items()):
        if not isinstance(r, (int, float)) or isinstance(r, bool) or r <= 0:
            continue
        out.append((
            f"{metric}{_PRED_SUFFIX}:{comp}{suffix}",
            min(float(r), 1.0 / float(r)),
        ))
    return out


# Serving latency floor (ISSUE 15): serve bench records carry the
# measured time-to-first-token next to the tokens/s throughput. Lower is
# better for a latency, so the gated trajectory value is its INVERSE
# (1000/ms — "admissions per second"), making the standard
# higher-is-better threshold machinery apply unchanged: a TTFT
# regression shows as the inverse dropping. ``@cpu`` separation applies
# exactly as for throughput (the decode program runs on the test
# backend).
_TTFT_SUFFIX = ":ttft_inv"


def normalize_serve_ttft(rec: dict) -> Optional[Tuple[str, float]]:
    """(``<metric>:ttft_inv`` key, 1000/ttft_ms) for records carrying a
    top-level ``ttft_ms``, or None."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    metric = rec.get("metric")
    v = rec.get("ttft_ms")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        return None
    key = f"{metric}{_TTFT_SUFFIX}"
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, 1000.0 / float(v)


# Elastic rejoin floor (ISSUE 16): rejoin bench records carry the
# announce-to-step-loop latency of a checkpoint-free rank join. Lower is
# better, so the gated trajectory is the INVERSE (1000/ms — "joins per
# second"), same machinery as the TTFT floor above.
_REJOIN_SUFFIX = ":rejoin_inv"


def normalize_rejoin(rec: dict) -> Optional[Tuple[str, float]]:
    """(``<metric>:rejoin_inv`` key, 1000/rejoin_latency_ms) for records
    carrying a top-level ``rejoin_latency_ms``, or None."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    metric = rec.get("metric")
    v = rec.get("rejoin_latency_ms")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        return None
    key = f"{metric}{_REJOIN_SUFFIX}"
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, 1000.0 / float(v)


# Memory footprint floor (ISSUE 18): when the memory ledger is on,
# bench.py stamps every record with the run's ``peak_mb`` high-water
# mark. Lower is better for a footprint, so the gated trajectory is the
# INVERSE (1/MB), same machinery as the TTFT floor above: a memory
# regression — a cache that stopped evicting, a staging buffer that
# doubled — shows as the inverse dropping past the threshold. Records
# without the key (ledger off) gate nothing; ``@cpu`` separation
# applies unchanged.
_PEAK_MB_SUFFIX = ":peak_mb"


def normalize_peak_mb(rec: dict) -> Optional[Tuple[str, float]]:
    """(``<metric>:peak_mb`` key, 1/peak_mb) for records carrying a
    top-level ``peak_mb``, or None."""
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    metric = rec.get("metric")
    v = rec.get("peak_mb")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        return None
    key = f"{metric}{_PEAK_MB_SUFFIX}"
    if is_placeholder(rec):
        key += _PLACEHOLDER_SUFFIX
    return key, 1.0 / float(v)


def normalize_all(rec: dict) -> List[Tuple[str, float]]:
    """Every gated (key, higher-is-better value) pair one record yields:
    its throughput trajectory and, when present, its overlap-fraction,
    prediction-ratio, TTFT-inverse, rejoin-inverse and peak-memory-
    inverse trajectories."""
    out = []
    for fn in (normalize, normalize_overlap, normalize_pred,
               normalize_serve_ttft, normalize_rejoin, normalize_peak_mb):
        norm = fn(rec)
        if norm is not None:
            out.append(norm)
    out.extend(normalize_pred_components(rec))
    return out


def _normalize_bare(rec: dict) -> Optional[Tuple[str, float]]:
    if not isinstance(rec, dict) or rec.get("unresolved"):
        return None
    tool = rec.get("tool")
    if tool == "qbench":
        v = rec.get("gbps_in")
        if not isinstance(v, (int, float)) or v <= 0:
            return None
        key = "qbench_{}_tc{}_mb{}_b{}_{}_{}".format(
            rec.get("variant", "?"), rec.get("tc", "?"), rec.get("mb", "?"),
            rec.get("bits", "?"), rec.get("pack", "?"), rec.get("encode", "?"),
        )
        return key, float(v)
    metric = rec.get("metric")
    if not metric or metric in _EXCLUDED_METRICS:
        return None
    v = rec.get("value")
    if not isinstance(v, (int, float)) or v <= 0:
        return None
    unit = str(rec.get("unit", ""))
    if "GB/s" not in unit and "tok/s" not in unit:
        return None  # only throughput metrics are gated (direction known)
    return str(metric), float(v)


def build_baselines(
    history: List[dict], published: Optional[Dict[str, float]] = None
) -> Dict[str, float]:
    """metric key -> baseline value (median of valid history; published
    floors win when higher — a number we have published is a promise)."""
    by_key: Dict[str, List[float]] = defaultdict(list)
    for rec in history:
        for key, v in normalize_all(rec):
            by_key[key].append(v)
    out = {k: median(v) for k, v in by_key.items()}
    for k, v in (published or {}).items():
        if not isinstance(v, (int, float)) or v <= 0:
            continue
        if k.endswith(_PLACEHOLDER_SUFFIX):
            continue  # a published floor is a chip promise, never cpu
        out[k] = max(out.get(k, 0.0), float(v))
    return out


def gate(
    candidates: List[dict],
    baselines: Dict[str, float],
    threshold_pct: float,
) -> Tuple[List[dict], List[dict]]:
    """(regressions, checks). Each check: {metric, value, baseline,
    delta_pct}; regressions are the checks past the threshold."""
    checks: List[dict] = []
    regressions: List[dict] = []
    for rec in candidates:
        for key, value in normalize_all(rec):
            base = baselines.get(key)
            if base is None or base <= 0:
                continue  # first sighting: nothing to regress against
            delta_pct = (value - base) / base * 100.0
            row = {
                "metric": key,
                "value": round(value, 4),
                "baseline": round(base, 4),
                "delta_pct": round(delta_pct, 1),
            }
            checks.append(row)
            if delta_pct < -threshold_pct:
                regressions.append(row)
    return regressions, checks


def smoke(
    history: List[dict], threshold_pct: float, window: int = 3
) -> Tuple[List[dict], List[dict]]:
    """Self-check on the committed trajectory: per metric, the **best of
    the last ``window`` records** vs the median of the earlier history.

    The best-of-window candidate is deliberate: the host-side benches
    run on shared boxes, and one contended run (the trajectory has a
    64 MB row whose *store* path was also 2.4x slower than trend —
    machine load, not a code change) must not fail CI. A sustained
    cliff — every recent record slow, which is what a real regression
    looks like — still fails."""
    by_key: Dict[str, List[float]] = defaultdict(list)
    for rec in history:
        for key, v in normalize_all(rec):
            by_key[key].append(v)
    regressions: List[dict] = []
    checks: List[dict] = []
    for key, vals in by_key.items():
        if key.endswith(_PLACEHOLDER_SUFFIX):
            # Placeholder-only trajectory: a CPU stand-in exists to prove
            # the code path runs, not to defend a perf floor — shared-box
            # noise on it must never fail CI.
            continue
        if len(vals) < 2:
            continue
        w = min(window, len(vals) - 1)
        earlier, recent = vals[:-w], vals[-w:]
        best = max(recent)
        base = median(earlier)
        if base <= 0:
            continue
        delta_pct = (best - base) / base * 100.0
        row = {
            "metric": key,
            "value": round(best, 4),
            "baseline": round(base, 4),
            "delta_pct": round(delta_pct, 1),
        }
        checks.append(row)
        if delta_pct < -threshold_pct:
            regressions.append(row)
    return regressions, checks


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--log", default=os.path.join(_REPO, "BENCH_LOG.jsonl"),
        help="trajectory log (default: the committed BENCH_LOG.jsonl)",
    )
    ap.add_argument(
        "--baseline", default=os.path.join(_REPO, "BASELINE.json"),
        help="BASELINE.json with optional published floors",
    )
    ap.add_argument(
        "--candidate", default=None,
        help="fresh run's JSONL records ('-' = stdin)",
    )
    ap.add_argument(
        "--threshold", type=float, default=30.0,
        help="max tolerated drop vs baseline, percent (default 30)",
    )
    ap.add_argument(
        "--pred-slack", type=float, default=None,
        help="hard prediction floor: fail a candidate whose measured "
             "step time exceeds predicted*slack (default: "
             "$CGX_GATE_PRED_SLACK or 1.5)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="self-check the committed trajectory (latest vs history)",
    )
    ap.add_argument("--json", action="store_true", help="JSON verdict")
    args = ap.parse_args(argv)

    history = _read_jsonl(args.log)
    if not history:
        print(f"bench_gate: no records in {args.log!r}", file=sys.stderr)
        return 2

    if args.smoke:
        regressions, checks = smoke(history, args.threshold)
    elif args.candidate:
        if args.candidate == "-":
            candidates = _parse_lines(sys.stdin)
        else:
            candidates = _read_jsonl(args.candidate)
        if not candidates:
            print("bench_gate: candidate has no parseable records",
                  file=sys.stderr)
            return 2
        published = {}
        try:
            with open(args.baseline) as f:
                published = json.load(f).get("published", {}) or {}
        except (OSError, ValueError):
            pass
        baselines = build_baselines(history, published)
        regressions, checks = gate(candidates, baselines, args.threshold)
        # The hard prediction floor needs no history: the planner's own
        # cost-model prediction rides in the record.
        slack_fails = check_pred_slack(candidates, args.pred_slack)
        checks.extend(slack_fails)
        regressions.extend(slack_fails)
    else:
        ap.error("one of --candidate or --smoke is required")
        return 2  # unreachable; argparse exits

    if args.json:
        print(json.dumps({
            "ok": not regressions,
            "threshold_pct": args.threshold,
            "checks": checks,
            "regressions": regressions,
        }, indent=2))
    else:
        mode = "smoke" if args.smoke else "candidate"
        print(f"bench_gate ({mode}): {len(checks)} metric(s) checked, "
              f"threshold {args.threshold:g}%")
        for c in checks:
            mark = "REGRESSION" if c in regressions else "ok"
            print(f"  [{mark}] {c['metric']}: {c['value']} vs baseline "
                  f"{c['baseline']} ({c['delta_pct']:+.1f}%)")
        if regressions:
            worst = min(regressions, key=lambda r: r["delta_pct"])
            print(
                f"bench_gate: FAIL — {worst['metric']} dropped "
                f"{-worst['delta_pct']:.1f}% (threshold "
                f"{args.threshold:g}%)",
                file=sys.stderr,
            )
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
