"""The readers of the scheduler's own spans (PR 25): each reduces one of the
program's ``cgx.serve.*`` histograms between the untraced loop's start and
end, returns nothing where the program (the parent commit) has no such
histogram, and a rehearsed traced run of the decode cell returns them all."""

import pytest

from benchmark import run as harness, spec

HISTOGRAM_OF = {
    "queue_wait_ms": "cgx.serve.queue_wait_s",
    "prefill_forward_ms": "cgx.serve.prefill_forward_s",
    "prefill_quantize_ms": "cgx.serve.prefill_quantize_s",
    "prefill_ingest_ms": "cgx.serve.prefill_ingest_s",
    "prefill_tail_copy_ms": "cgx.serve.prefill_tail_copy_s",
    "admit_lane_ms": "cgx.serve.admit_lane_s",
    "decode_prepare_ms": "cgx.serve.decode_prepare_s",
    "decode_emit_ms": "cgx.serve.decode_emit_s",
}
DECODE_CELL = "gpt2l-serve-decode"


def ctx_of(start, end):
    return {"counters": {"start": start, "end": end}}


@pytest.mark.parametrize("metric,hist", sorted(HISTOGRAM_OF.items()))
def test_histogram_reader_means_the_growth(metric, hist):
    read = spec.load_reader(metric).read
    start = {f"{hist}.count": 4.0, f"{hist}.sum": 1.0}
    end = {f"{hist}.count": 9.0, f"{hist}.sum": 1.25}
    assert read(ctx_of(start, end)) == pytest.approx(50.0)  # ms
    assert read(ctx_of({}, end)) == pytest.approx(1250.0 / 9)
    # No growth, and a program that has no such histogram: nothing.
    assert read(ctx_of(end, end)) is None
    assert read(ctx_of({}, {})) is None
    other = {"cgx.serve.decode_step_s.count": 3.0,
             "cgx.serve.decode_step_s.sum": 0.5}
    assert read(ctx_of({}, other)) is None


def test_host_gc_pct_is_a_share_of_the_ticks():
    read = spec.load_reader("host_gc_pct").read
    start = {"cgx.serve.step_s.sum": 10.0, "cgx.serve.host_gc_s.sum": 0.5}
    end = {"cgx.serve.step_s.sum": 60.0, "cgx.serve.host_gc_s.sum": 1.5}
    assert read(ctx_of(start, end)) == pytest.approx(2.0)
    # Ticks and no full collection: 0, which is a reading.
    assert read(ctx_of({}, {"cgx.serve.step_s.sum": 3.0})) == 0.0
    # No ticks timed (the parent commit): nothing.
    assert read(ctx_of({}, {})) is None
    assert read(ctx_of(end, end)) is None


def test_every_new_metric_is_an_entry_with_a_reader():
    per_layer = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in [*HISTOGRAM_OF, "host_gc_pct"]:
        entry = per_layer[name]
        assert entry["source"] == "program_span"
        assert callable(spec.load_reader(name).read)
        decode_only = entry["moves"] == "serve_itl_p50_ms"
        assert (entry["workloads"] == [DECODE_CELL]) == decode_only


def test_rehearsed_traced_decode_run_returns_every_new_metric():
    result = harness.run(
        ["--workload", DECODE_CELL, "--seed", "2500000011", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu", "1"])
    assert result["correct"], result["checks"]
    for name in [*HISTOGRAM_OF, "host_gc_pct"]:
        assert name in result["metrics"], name
        assert result["metrics"][name]["value"] >= 0
    parts = sum(result["metrics"][n]["value"] for n in (
        "prefill_forward_ms", "prefill_quantize_ms", "prefill_ingest_ms",
        "prefill_tail_copy_ms"))
    assert parts <= result["metrics"]["prefill_ms"]["value"]
