"""What PR 33 added for the gated delta-rule cell: the bytes of a state
update counted from the configuration (against a hand count), the two
readers of the ``cgx_gdn_update`` kernel's device time (which return
nothing, never raise, for a program or a configuration without one), the
configuration's published keys, and a rehearsed traced run of the cell,
which returns every metric listed for it that a CPU run can read."""

import json

import pytest

from benchmark import bytes_gdn, run as harness, spec

CELL = "olmoh-serve-chat96"
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
    row = next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # The cut: four whole periods, every kind of layer in its ratio.
    assert cfg["num_hidden_layers"] == 16 == len(cfg["layer_types"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:16]
    assert cfg["published"]["num_hidden_layers"] == 32


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1 and mix["driver"] == "serve_closed_gdn"
    assert mix["clients"] == 96 == serve["max_batch"]
    assert mix["prompt_groups"] == [
        {"lo": 65, "hi": 127, "lengths": 16, "share": 1}]
    assert mix["output"] == {"lo": 64, "hi": 192, "lengths": 33}
    assert (mix["population"], mix["population_seed"]) == (2048, 33)
    assert (mix["first_output_scale"], mix["ramp_s"], mix["trace_s"],
            mix["check_requests"]) == ([0.1, 1.0], 4.0, 4.0, 8)
    # A lane's longest table fits its positions, and the pool every lane's.
    assert 127 + 192 <= serve["max_seq"] == 320
    assert serve["max_pages"] == 96 * (320 // serve["page_tokens"])


def test_state_update_bytes_from_the_configuration(cfg):
    # By hand: 96 lanes x 30 heads x (96 x 192) float32, read and written;
    # q and k (96 each), v and o (192 each), alpha and beta a head.
    state = 96 * 30 * 96 * 192 * 4
    small = 96 * 30 * (96 + 96 + 192 + 192 + 1 + 1) * 4
    assert (state, small) == (212_336_640, 6_658_560)
    assert bytes_gdn.call_bytes(cfg) == 2 * state + small == 431_331_840
    assert bytes_gdn.step_bytes(cfg) == 12 * (2 * state + small)
    narrow = spec.merge(cfg, {"precision": {"gdn_state": "bfloat16"}})
    assert bytes_gdn.call_bytes(narrow) == state + small


def traced(cfg, steps=2):
    # Window 0..100 ms; two decode steps of three state updates each, one
    # of them outside the window; a dequantize beside them.
    ops = [[f"cgx_gdn_update.{i}", (10 + 10 * i) * MS, 2 * MS]
           for i in range(6)]
    ops += [["cgx_gdn_update.9", 150 * MS, 2 * MS],
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
    ms = spec.load_reader("gdn_update_ms").read(ctx)
    assert ms == pytest.approx(6.0)  # 6 calls x 2 ms over 2 steps
    share = spec.load_reader("gdn_update_roofline").read(ctx)
    least_ms = bytes_gdn.step_bytes(cfg) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 6.0)


@pytest.mark.parametrize("metric", ["gdn_update_ms", "gdn_update_roofline"])
def test_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    granite = json.loads((spec.ROOT / "benchmark" / "configs"
                          / "granite-4.0-h-micro-serve-kv8.json").read_text())
    no_kernel = traced(cfg)
    no_kernel["trace"]["devices"]["0"] = [["cgx_ssm_update.1", 10 * MS,
                                           5 * MS]]
    no_steps = traced(cfg, steps=0)
    no_trace = dict(traced(cfg), trace=None)
    for ctx in (no_kernel, no_steps, no_trace):
        assert read(ctx) is None
    if metric == "gdn_update_roofline":  # a configuration without the layers
        assert read(traced(granite)) is None


def test_rehearsed_traced_run_returns_the_cells_metrics():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= {"gdn_update_ms",
                                           "gdn_update_roofline"}
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
