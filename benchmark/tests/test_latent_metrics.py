"""The readers PR 27 added for the latent-attention cell: the expert
counters' two metrics and the latent read's roofline share reduce what the
program counted or the trace holds, and return nothing (never raise) for a
program or a configuration that has neither."""

import json

import pytest

from benchmark import bytes_latent, run as harness, spec

CELL = "joyai-serve-decode3k"


@pytest.fixture(scope="module")
def cfg():
    bench = spec.load_benchmark()
    return spec.load_cell(bench, CELL, rehearse=False)["config"]


def test_latent_step_bytes_from_the_shapes(cfg):
    rows = 32 * 4096
    one = lambda width: (rows * width  # 8-bit words
                         + rows * width // 512 * 8  # two float32 a bucket
                         + rows * width * 4)  # float32 out
    assert bytes_latent.stream_call_bytes(32, 4096, 512, 8, 512) == one(512)
    assert bytes_latent.latent_step_bytes(cfg) == 5 * (one(512) + one(64))


def counters(steps, touched, load_max, assignments):
    return {"cgx.serve.decode_steps": steps,
            "cgx.serve.moe.experts_touched": touched,
            "cgx.serve.moe.load_max": load_max,
            "cgx.serve.moe.assignments": assignments}


def test_expert_counter_readers_reduce_the_growth(cfg):
    start = counters(10.0, 5000.0, 70.0, 10 * 1024.0)
    end = counters(30.0, 5000.0 + 20 * 512, 70.0 + 20 * 6,
                   10 * 1024.0 + 20 * 1024)
    ctx = {"config": cfg, "counters": {"start": start, "end": end}}
    touched = spec.load_reader("moe_experts_touched_pct").read(ctx)
    assert touched == pytest.approx(50.0)  # 512 of 4 x 256 a step
    ratio = spec.load_reader("moe_expert_load_max_over_mean").read(ctx)
    assert ratio == pytest.approx(6.0)  # mean load 1024 / (4 x 256) = 1


@pytest.mark.parametrize("metric", ["moe_experts_touched_pct",
                                    "moe_expert_load_max_over_mean",
                                    "latent_dequantize_roofline"])
def test_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    gpt2 = json.loads((spec.ROOT / "benchmark" / "configs"
                       / "gpt2-large-serve-kv8.json").read_text())
    empty = {"start": {}, "end": {}}
    for config, found in ((cfg, empty), (gpt2, empty),
                          (gpt2, {"start": {}, "end": counters(1, 1, 1, 1)})):
        ctx = {"config": config, "counters": found, "trace": None,
               "loop": {}, "peaks": None, "device_ids": [0]}
        assert read(ctx) is None


def test_rehearsed_traced_run_reports_the_new_metrics():
    result = harness.run(["--workload", CELL, "--seed", "11", "--seconds",
                          "4", "--trace", "1", "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    got = result["metrics"]
    assert 0 < got["moe_experts_touched_pct"]["value"] <= 100
    assert got["moe_expert_load_max_over_mean"]["value"] >= 1
    assert [c for c in result["checks"] if c["name"] == "moe_dropped"] == [
        {"name": "moe_dropped", "value": 0.0, "limit": 0, "ok": True}]
