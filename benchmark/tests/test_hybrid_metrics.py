"""What PR 31 added for the hybrid state-space cell: the bytes of a state
update counted from the configuration, the two readers of the
``cgx_ssm_update`` kernel's device time (which return nothing, never raise,
for a program or a configuration without one), and a rehearsed traced run
of the cell, which returns every metric listed for it that a CPU run can
read."""

import json

import pytest

from benchmark import bytes_ssm, run as harness, spec

CELL = "granite-serve-chat64"
MS = 1_000_000


@pytest.fixture(scope="module")
def cfg():
    bench = spec.load_benchmark()
    return spec.load_cell(bench, CELL, rehearse=False)["config"]


def test_the_configuration_keeps_every_published_key(cfg):
    # The catalog is beside the builder's guides, not in the repo.
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(path)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == []
    for key, value in row["config"].items():
        assert cfg[key] == value, key


def test_state_update_bytes_from_the_configuration(cfg):
    state = 64 * 128 * 4096 * 4  # lanes x d_state x channels x float32
    small = 64 * (3 * 4096 + 2 * 128) * 4
    assert bytes_ssm.call_bytes(cfg) == 2 * state + small
    assert bytes_ssm.step_bytes(cfg) == 36 * (2 * state + small)
    narrow = spec.merge(cfg, {"precision": {"ssm_state": "bfloat16"}})
    assert bytes_ssm.call_bytes(narrow) == state + small


def traced(cfg, steps=2):
    # Window 0..100 ms; two decode steps of three state updates each, one
    # of them outside the window; a dequantize beside them.
    ops = [[f"cgx_ssm_update.{i}", (10 + 10 * i) * MS, 2 * MS]
           for i in range(6)]
    ops += [["cgx_ssm_update.9", 150 * MS, 2 * MS],
            ["cgx_dequantize_flat.3", 80 * MS, 5 * MS],
            ["fusion.1", 90 * MS, 5 * MS]]
    return {
        "config": cfg, "loop": {"traced_decode_steps": steps},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
        "counters": {"start": {}, "end": {}},
    }


def test_readers_reduce_the_kernels_device_time(cfg):
    ctx = traced(cfg)
    ms = spec.load_reader("ssm_update_ms").read(ctx)
    assert ms == pytest.approx(6.0)  # 6 calls x 2 ms over 2 steps
    share = spec.load_reader("ssm_update_roofline").read(ctx)
    least_ms = bytes_ssm.step_bytes(cfg) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 6.0)


@pytest.mark.parametrize("metric", ["ssm_update_ms", "ssm_update_roofline"])
def test_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    gpt2 = json.loads((spec.ROOT / "benchmark" / "configs"
                       / "gpt2-large-serve-kv8.json").read_text())
    no_kernel = traced(cfg)
    no_kernel["trace"]["devices"]["0"] = [["fusion.1", 10 * MS, 5 * MS]]
    no_steps = traced(cfg, steps=0)
    no_trace = dict(traced(cfg), trace=None)
    for ctx in (no_kernel, no_steps, no_trace):
        assert read(ctx) is None
    if metric == "ssm_update_roofline":  # a configuration without the layers
        assert read(traced(gpt2)) is None


def test_rehearsed_traced_run_returns_the_cells_metrics():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= {"ssm_update_ms",
                                           "ssm_update_roofline"}
    result = harness.run(["--workload", CELL, "--seed", "2500000011",
                          "--seconds", "3", "--trace", "1",
                          "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # Every metric listed for the cell whose source a CPU run has; the
    # device trace's three need the chip.
    for m in listed:
        if m["source"] == "device_trace":
            assert m["name"] not in result["metrics"]
        else:
            assert result["metrics"][m["name"]]["value"] >= 0, m["name"]
    untraced = harness.run(["--workload", CELL, "--seed", "2500000012",
                            "--seconds", "3", "--trace", "0",
                            "--rehearse-cpu", "1"])
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
