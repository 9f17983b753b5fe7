"""Bench regression gate tests (ISSUE 3): a steady trajectory must pass
``--smoke``, a synthetic 2x regression must fail with the offending
metric named, and the record normalization must skip failure/unresolved
rows.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GATE = os.path.join(_REPO, "tools", "bench_gate.py")


def _load_gate():
    spec = importlib.util.spec_from_file_location("bench_gate", _GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_METRIC = "bridge_put_take_16MB"


def _trajectory(tmp_path, values=(0.50, 0.52, 0.48, 0.51)) -> str:
    """A small host-metric trajectory (the repo commits no log: the
    benchmark that will own one is ROADMAP A1)."""
    log = tmp_path / "trajectory.jsonl"
    log.write_text("".join(
        json.dumps({"tool": "shm_bench", "metric": _METRIC, "value": v,
                    "unit": "GB/s", "backend": "host"}) + "\n"
        for v in values
    ))
    return str(log)


def test_smoke_passes_on_a_steady_trajectory(tmp_path):
    proc = subprocess.run(
        [sys.executable, _GATE, "--smoke", "--log", _trajectory(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "checked" in proc.stdout


def test_synthetic_2x_regression_fails_named(tmp_path):
    # Acceptance: a fresh run at half the historical throughput exits
    # nonzero and names the offending metric.
    gate = _load_gate()
    log = _trajectory(tmp_path)
    base = gate.build_baselines(gate._read_jsonl(log))[_METRIC]
    cand = tmp_path / "cand.jsonl"
    cand.write_text(json.dumps({
        "tool": "shm_bench", "metric": _METRIC, "value": base / 2,
        "unit": "GB/s",
    }) + "\n")
    proc = subprocess.run(
        [sys.executable, _GATE, "--log", log, "--candidate", str(cand)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 1
    assert _METRIC in proc.stderr  # the offending metric is named
    assert "REGRESSION" in proc.stdout


def test_candidate_within_threshold_passes(tmp_path):
    gate = _load_gate()
    log = _trajectory(tmp_path)
    base = gate.build_baselines(gate._read_jsonl(log))[_METRIC]
    cand = tmp_path / "cand.jsonl"
    cand.write_text(json.dumps({
        "tool": "shm_bench", "metric": _METRIC, "value": base * 0.9,
        "unit": "GB/s",
    }) + "\n")
    proc = subprocess.run(
        [sys.executable, _GATE, "--log", log, "--candidate", str(cand),
         "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout
    verdict = json.loads(proc.stdout)
    assert verdict["ok"] and verdict["checks"]


def test_normalize_skips_failures_and_unresolved():
    gate = _load_gate()
    assert gate.normalize({"tool": "bench", "metric": "device_init_failure",
                           "value": 0, "unit": "none"}) is None
    assert gate.normalize({"tool": "qbench", "variant": "x", "gbps_in": None,
                           "unresolved": "noise"}) is None
    assert gate.normalize({"tool": "qbench", "variant": "current", "tc": 16,
                           "mb": 128, "bits": 4, "pack": "sum",
                           "encode": "div", "gbps_in": 130.5}) == (
        "qbench_current_tc16_mb128_b4_sum_div", 130.5)
    key, v = gate.normalize({"tool": "shm_bench", "metric": "m",
                             "value": 0.5, "unit": "GB/s (shm)"})
    assert key == "m" and v == 0.5
    # non-throughput units carry no gate direction: skipped
    assert gate.normalize({"tool": "bench", "metric": "m", "value": 3.0,
                           "unit": "steps"}) is None


def test_gate_logic_threshold_and_first_sighting():
    gate = _load_gate()
    baselines = {"m": 1.0}
    reg, checks = gate.gate(
        [{"tool": "shm_bench", "metric": "m", "value": 0.65,
          "unit": "GB/s"},
         {"tool": "shm_bench", "metric": "new", "value": 0.1,
          "unit": "GB/s"}],
        baselines, threshold_pct=30.0,
    )
    assert len(checks) == 1  # first sighting of "new" is not gated
    assert reg and reg[0]["metric"] == "m"
    assert reg[0]["delta_pct"] == pytest.approx(-35.0)
    reg2, _ = gate.gate(
        [{"tool": "shm_bench", "metric": "m", "value": 0.75,
          "unit": "GB/s"}],
        baselines, threshold_pct=30.0,
    )
    assert not reg2  # -25% is inside the 30% band


def test_published_floor_wins_over_history():
    gate = _load_gate()
    history = [{"tool": "shm_bench", "metric": "m", "value": 0.4,
                "unit": "GB/s"}]
    b = gate.build_baselines(history, published={"m": 0.8})
    assert b["m"] == 0.8


# ---------------------------------------------------------------------------
# CPU-placeholder separation (ISSUE 6 satellite): rows that ran on the CPU
# stand-in while no chip answered must form their own trajectory and never
# dilute — or be judged against — chip truth.
# ---------------------------------------------------------------------------


def _chip_row(value, **kw):
    return {"tool": "bench", "metric": "pallas_codec_roundtrip",
            "value": value, "unit": "GB/s", "chip": "TPU v5 lite",
            "backend": "tpu", **kw}


def _cpu_row(value, **kw):
    return {"tool": "bench", "metric": "pallas_codec_roundtrip",
            "value": value, "unit": "GB/s", "chip": "cpu",
            "backend": "cpu", **kw}


def test_placeholder_rows_key_into_their_own_trajectory():
    gate = _load_gate()
    assert gate.normalize(_chip_row(100.0)) == (
        "pallas_codec_roundtrip", 100.0)
    assert gate.normalize(_cpu_row(2.0)) == (
        "pallas_codec_roundtrip@cpu", 2.0)
    # detail.chip tagging (older rows carried the chip inside detail)
    rec = {"tool": "bench", "metric": "pallas_codec_roundtrip",
           "value": 3.0, "unit": "GB/s", "detail": {"chip": "cpu"}}
    assert gate.normalize(rec) == ("pallas_codec_roundtrip@cpu", 3.0)
    # host-side tools are genuinely host metrics, NOT placeholders
    host = {"tool": "shm_bench", "metric": "bridge_put_take",
            "value": 1.0, "unit": "GB/s", "backend": "host"}
    assert gate.normalize(host) == ("bridge_put_take", 1.0)


def test_placeholder_rows_never_dilute_chip_median():
    gate = _load_gate()
    # three cpu stand-ins around two real chip rows: the chip baseline
    # must stay the chip median, not collapse toward the placeholders
    hist = [_chip_row(100.0), _cpu_row(2.0), _chip_row(110.0),
            _cpu_row(2.1), _cpu_row(1.9)]
    b = gate.build_baselines(hist)
    assert b["pallas_codec_roundtrip"] == pytest.approx(105.0)
    assert b["pallas_codec_roundtrip@cpu"] == pytest.approx(2.0)


def test_published_floor_is_a_chip_promise_never_cpu():
    gate = _load_gate()
    b = gate.build_baselines(
        [_cpu_row(2.0)],
        published={"pallas_codec_roundtrip": 90.0,
                   "pallas_codec_roundtrip@cpu": 50.0},
    )
    # the floor lands on the chip key; a floor on a placeholder key is
    # refused outright (nothing could ever meet it honestly)
    assert b["pallas_codec_roundtrip"] == 90.0
    assert b["pallas_codec_roundtrip@cpu"] == pytest.approx(2.0)


def test_placeholder_candidate_never_meets_chip_floor():
    gate = _load_gate()
    regs, checks = gate.gate(
        [_cpu_row(2.0)], {"pallas_codec_roundtrip": 100.0}, 30.0)
    # different trajectory key: not compared at all, not a regression
    assert not regs and not checks


def test_smoke_skips_placeholder_only_trajectories():
    gate = _load_gate()
    # a placeholder trajectory with a sustained 10x cliff: smoke must not
    # gate it (it proves the code path runs, it defends no floor)...
    hist = [_cpu_row(2.0), _cpu_row(2.1), _cpu_row(0.2), _cpu_row(0.2),
            _cpu_row(0.2)]
    regs, checks = gate.smoke(hist, threshold_pct=30.0)
    assert regs == [] and checks == []
    # ...while the same cliff on chip truth still fails loudly
    hist = [_chip_row(100.0), _chip_row(101.0), _chip_row(10.0),
            _chip_row(10.0), _chip_row(10.0)]
    regs, _ = gate.smoke(hist, threshold_pct=30.0)
    assert regs and regs[0]["metric"] == "pallas_codec_roundtrip"


# ---------------------------------------------------------------------------
# Overlap-fraction floor (ISSUE 9 satellite): sched records gate a second
# trajectory, <metric>:overlap_frac, like throughput — @cpu separation
# preserved.
# ---------------------------------------------------------------------------


def _sched_rec(overlap, value=0.02, backend="host"):
    return {
        "tool": "bench",
        "metric": "sched_pipelined_vs_monolithic_4bit_32MB_x4",
        "value": value,
        "unit": "GB/s",
        "overlap_frac": overlap,
        "backend": backend,
        "chip": backend,
    }


def test_overlap_normalizer_yields_second_trajectory():
    gate = _load_gate()
    rec = _sched_rec(0.25)
    keys = dict(gate.normalize_all(rec))
    assert keys["sched_pipelined_vs_monolithic_4bit_32MB_x4"] == 0.02
    assert (
        keys["sched_pipelined_vs_monolithic_4bit_32MB_x4:overlap_frac"]
        == 0.25
    )
    # 0.0 is a VALID measurement (total collapse must face the floor,
    # not bypass it); absent/negative overlap contributes nothing
    assert gate.normalize_overlap(_sched_rec(0.0)) is not None
    assert gate.normalize_overlap(_sched_rec(-1.0)) is None
    assert gate.normalize_overlap({"metric": "x", "value": 1}) is None


def test_overlap_total_collapse_fails_the_gate():
    # The worst regression — the pipeline fully re-serialized
    # (overlap_frac 0.0, e.g. the schedule silently degraded to one
    # chunk) — must fail, not slip past normalization.
    gate = _load_gate()
    history = [_sched_rec(0.25), _sched_rec(0.22), _sched_rec(0.28)]
    baselines = gate.build_baselines(history)
    regressions, _ = gate.gate([_sched_rec(0.0)], baselines, 30.0)
    assert any(
        r["metric"].endswith(":overlap_frac") and r["value"] == 0.0
        for r in regressions
    )


def test_overlap_regression_fails_the_gate():
    gate = _load_gate()
    history = [_sched_rec(0.25), _sched_rec(0.22), _sched_rec(0.28)]
    baselines = gate.build_baselines(history)
    # a run whose pipeline quietly re-serialized: overlap collapses while
    # throughput barely moves — the overlap floor must catch it
    regressions, checks = gate.gate(
        [_sched_rec(0.01, value=0.019)], baselines, 30.0
    )
    names = {r["metric"] for r in regressions}
    assert "sched_pipelined_vs_monolithic_4bit_32MB_x4:overlap_frac" in names
    assert "sched_pipelined_vs_monolithic_4bit_32MB_x4" not in names


def test_overlap_placeholder_rows_key_cpu_trajectory():
    gate = _load_gate()
    rec = gate.normalize_overlap(_sched_rec(0.3, backend="cpu"))
    assert rec is not None
    assert rec[0].endswith(":overlap_frac@cpu")
    # and the cpu trajectory never meets the host baseline
    history = [_sched_rec(0.25)] * 3
    baselines = gate.build_baselines(history)
    regressions, checks = gate.gate(
        [_sched_rec(0.01, backend="cpu")], baselines, 30.0
    )
    assert not regressions and not checks


# ---------------------------------------------------------------------------
# Cost-model prediction floor (ISSUE 12): the <metric>:pred_ratio
# trajectory + the hard CGX_GATE_PRED_SLACK check.
# ---------------------------------------------------------------------------


def test_pred_normalizer_yields_third_trajectory():
    bg = _load_gate()
    rec = {
        "metric": "planner_vs_static_4bit_32MB_x4",
        "value": 1.2, "unit": "GB/s",
        "pred_ratio": 1.1,
        "predicted_step_ms": 110.0, "measured_step_ms": 100.0,
        "backend": "host", "chip": "host",
    }
    # the gated value is prediction ACCURACY min(r, 1/r): symmetric
    # around the 1.0 ideal, so drift in EITHER direction regresses
    keys = dict(bg.normalize_all(rec))
    assert keys["planner_vs_static_4bit_32MB_x4:pred_ratio"] == \
        pytest.approx(1 / 1.1)
    # derived from the ms pair when the ratio field is absent
    del rec["pred_ratio"]
    keys = dict(bg.normalize_all(rec))
    assert keys["planner_vs_static_4bit_32MB_x4:pred_ratio"] == \
        pytest.approx(1 / 1.1)
    # an underpredicting model maps to the same accuracy
    rec["pred_ratio"] = 1 / 1.1
    keys = dict(bg.normalize_all(rec))
    assert keys["planner_vs_static_4bit_32MB_x4:pred_ratio"] == \
        pytest.approx(1 / 1.1)


def test_pred_placeholder_rows_key_cpu_trajectory():
    bg = _load_gate()
    rec = {
        "metric": "planner_vs_static_4bit_32MB_x4",
        "pred_ratio": 0.9, "backend": "cpu", "chip": "cpu",
    }
    norm = bg.normalize_pred(rec)
    assert norm is not None
    assert norm[0].endswith(":pred_ratio@cpu")


def test_pred_slack_violation_fails_loudly(monkeypatch):
    # A record whose measured step exceeds predicted*slack fails the
    # candidate gate with NO history needed — the planner's own
    # prediction is the floor (planner regression / cost-model drift).
    bg = _load_gate()
    monkeypatch.delenv("CGX_GATE_PRED_SLACK", raising=False)
    bad = {
        "metric": "planner_vs_static_4bit_32MB_x4",
        "predicted_step_ms": 100.0, "measured_step_ms": 151.0,
    }
    ok = {
        "metric": "planner_vs_static_4bit_32MB_x4",
        "predicted_step_ms": 100.0, "measured_step_ms": 149.0,
    }
    fails = bg.check_pred_slack([bad, ok])
    assert len(fails) == 1
    assert fails[0]["metric"] == "planner_vs_static_4bit_32MB_x4:pred_slack"
    # env knob moves the floor
    monkeypatch.setenv("CGX_GATE_PRED_SLACK", "2.0")
    assert bg.check_pred_slack([bad]) == []
    # explicit argument wins over env
    assert len(bg.check_pred_slack([bad], 1.2)) == 1


def test_pred_ratio_regression_fails_the_gate():
    bg = _load_gate()
    history = [
        {"metric": "planner_vs_static_4bit_32MB_x4", "pred_ratio": r,
         "backend": "host", "chip": "host"}
        for r in (1.0, 1.05, 0.95)
    ]
    baselines = bg.build_baselines(history)
    # accuracies: (1.0, 1/1.05, 0.95) -> median 1/1.05
    assert baselines["planner_vs_static_4bit_32MB_x4:pred_ratio"] == \
        pytest.approx(1 / 1.05)
    # drift in EITHER direction fails: heavy underprediction...
    cand = [{"metric": "planner_vs_static_4bit_32MB_x4", "pred_ratio": 0.4,
             "backend": "host", "chip": "host"}]
    regressions, _checks = bg.gate(cand, baselines, 30.0)
    assert len(regressions) == 1
    assert regressions[0]["metric"].endswith(":pred_ratio")
    # ...and unbounded OVERprediction (ratio 5.0 -> accuracy 0.2)
    cand = [{"metric": "planner_vs_static_4bit_32MB_x4", "pred_ratio": 5.0,
             "backend": "host", "chip": "host"}]
    regressions, _checks = bg.gate(cand, baselines, 30.0)
    assert len(regressions) == 1


def test_peak_mb_normalizes_inverse_and_gates_lower_better():
    # ISSUE 18 satellite: records carrying the memory ledger's peak_mb
    # gate an INVERSE (1/MB) trajectory, so a footprint growth fails
    # exactly like a throughput cliff.
    bg = _load_gate()
    rec = {"metric": "bench_4bit_512MB", "value": 10.0, "peak_mb": 256.0,
           "backend": "tpu", "chip": "v5e"}
    key, v = bg.normalize_peak_mb(rec)
    assert key == "bench_4bit_512MB:peak_mb"
    assert v == pytest.approx(1.0 / 256.0)
    # present in the full normalization fan-out
    assert (key, v) in bg.normalize_all(rec)
    # ledger off (no key), bogus values, unresolved rows: no trajectory
    assert bg.normalize_peak_mb({"metric": "m", "value": 1.0}) is None
    assert bg.normalize_peak_mb({"metric": "m", "peak_mb": 0}) is None
    assert bg.normalize_peak_mb({"metric": "m", "peak_mb": True}) is None
    assert bg.normalize_peak_mb(
        {"metric": "m", "peak_mb": 9.0, "unresolved": True}) is None
    # placeholder rows stay in their own @cpu trajectory
    ph = {"metric": "bench_4bit_512MB", "peak_mb": 256.0,
          "backend": "tpu", "chip": "cpu"}
    key_ph, _ = bg.normalize_peak_mb(ph)
    assert key_ph.endswith("@cpu")


def test_peak_mb_growth_fails_the_gate():
    bg = _load_gate()
    history = [
        {"metric": "bench_4bit_512MB", "value": 10.0, "peak_mb": mb,
         "backend": "host", "chip": "host"}
        for mb in (250.0, 256.0, 260.0)
    ]
    baselines = bg.build_baselines(history)
    assert baselines["bench_4bit_512MB:peak_mb"] == \
        pytest.approx(1.0 / 256.0)
    # a 2x memory growth (inverse halves) fails, named
    cand = [{"metric": "bench_4bit_512MB", "value": 10.0, "peak_mb": 512.0,
             "backend": "host", "chip": "host"}]
    regressions, _checks = bg.gate(cand, baselines, 30.0)
    assert [r["metric"] for r in regressions] == \
        ["bench_4bit_512MB:peak_mb"]
    # a shrink (inverse grows) passes
    cand[0]["peak_mb"] = 128.0
    regressions, _checks = bg.gate(cand, baselines, 30.0)
    assert regressions == []
