"""What PR 45 added for the Trinity cell: the bytes of a step's expert
products counted from the configuration and the held experts the step
touched (against a hand count), the roofline share that reads them (and
returns nothing, never raises, for a trace, a driver or a program without
what it reads), the accepted readers on this configuration's keys, the
configuration's published keys and its cut, the cell's traffic, and a
rehearsed traced run of the cell, which returns every metric listed for it
that a CPU run can read."""

import json

import pytest

from benchmark import bytes_experts, bytes_window, run as harness, spec

CELL = "trinity-serve-agent64"
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
    row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layers_kept",
                              "num_experts", "vocab_size"]
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
        else:
            assert cfg["published"][key] == value, key
    # The cut: the leading dense layers once, one whole period after them,
    # an eighth of the experts and of the vocabulary (the guide's floors).
    assert cfg["layers_kept"] == [0, 8, 9, 10, 11]
    assert cfg["num_hidden_layers"] == len(cfg["layers_kept"]) == 5
    kinds = [cfg["layer_types"][i] for i in cfg["layers_kept"]]
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"]
    assert [i < cfg["num_dense_layers"] for i in cfg["layers_kept"]] == [
        True, False, False, False, False]
    assert cfg["num_experts"] * 8 == cfg["num_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 200192
    for key in ("assumed", "precision", "init", "control", "deployment"):
        assert cfg.get(key), key


def test_the_readers_names_say_what_the_sources_keys_say(cfg):
    """The accepted readers read this cell's sizes under names the source
    does not use: each has to say what the source's key says."""
    assert cfg["sliding_window_size"] == cfg["sliding_window"]
    assert cfg["first_k_dense_replace"] == cfg["num_dense_layers"]
    assert cfg["sliding_window_layout"] == [
        int(cfg["layer_types"][i] == "sliding_attention")
        for i in cfg["layers_kept"]]
    assert bytes_window.window_layers(cfg) == 4
    # A page of one stream: 256 tokens x 8 heads x 128 = 262,144 values, a
    # byte each, two float32 a 512-value bucket, bfloat16 rows out.
    assert bytes_window.page_bytes(cfg) == 262_144 + 512 * 8 + 524_288


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1
    assert mix["driver"] == "serve_closed_afmoe"
    assert mix["clients"] == 64 == serve["max_batch"]
    assert mix["prompt_groups"] == [
        {"lo": 769, "hi": 1023, "lengths": 16, "share": 3},
        {"lo": 3841, "hi": 4095, "lengths": 16, "share": 1}]
    assert mix["output"] == {"lo": 256, "hi": 768, "lengths": 33}
    assert (mix["population"], mix["first_output_scale"], mix["ramp_s"],
            mix["trace_s"], mix["check_requests"],
            mix["check_long_requests"]) == (2048, [0.1, 1.0], 4.0, 4.0, 8, 3)
    # A lane's longest table fits its positions, the global pool every
    # lane's, and a long prompt ends within a page of the window's edge.
    assert 4095 + 768 <= serve["max_seq"] == 4864
    assert serve["max_pages"] == 64 * (4864 // serve["page_tokens"]) == 1216
    window = cell["config"]["sliding_window"]
    assert window - serve["page_tokens"] < 3841 and 4095 < window


def test_expert_bytes_from_the_configuration(cfg):
    # By hand: gate, up and down of one expert are 3 x 3072 x 3072 bfloat16.
    assert bytes_experts.expert_bytes(cfg) == 3 * 3072 * 3072 * 2 == 56_623_104
    # 20 of a layer's 32 held experts touched on each of four layers.
    assert bytes_experts.step_bytes(cfg, 80) == 80 * 56_623_104
    wide = spec.merge(cfg, {"precision": {"params": "float32"}})
    assert bytes_experts.expert_bytes(wide) == 2 * 56_623_104


def traced(cfg, steps=2, touched=160.0):
    # Window 0..100 ms; two decode steps of twelve products each (three a
    # layer), ragged-dot and the repo's kernel alike; one product outside
    # the window; other ops beside them.
    ops = [[f"ragged-dot.{i}", (2 + 3 * i) * MS, 2 * MS] for i in range(12)]
    ops += [[f"cgx_grouped_matmul.{i}", (50 + 3 * i) * MS, 2 * MS]
            for i in range(12)]
    ops += [["ragged-dot.99", 150 * MS, 2 * MS],
            ["cgx_dequantize_window.3", 92 * MS, 2 * MS],
            ["fusion.1", 95 * MS, 5 * MS]]
    return {
        "config": cfg,
        "loop": {"traced_decode_steps": steps,
                 "traced_experts_touched": touched},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
        "counters": {"start": {}, "end": {}},
    }


def test_the_roofline_share_is_the_touched_bytes_over_the_products_time(cfg):
    ctx = traced(cfg)
    assert spec.load_reader("experts_matmul_ms").read(ctx) == (
        pytest.approx(24.0))  # 24 products x 2 ms over 2 steps
    share = spec.load_reader("experts_matmul_roofline").read(ctx)
    least_ms = bytes_experts.step_bytes(cfg, 80.0) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 24.0)


def test_the_roofline_share_cannot_pass_100(cfg):
    # Every held expert of every expert layer touched, read in the time HBM
    # needs for them: the most the count can be over the least the products
    # can take.
    ctx = traced(cfg, steps=1, touched=4 * 32.0)
    least_s = bytes_experts.step_bytes(cfg, 4 * 32) / 819e9
    ctx["trace"]["devices"]["0"] = [
        ["cgx_grouped_matmul.1", 10 * MS, int(least_s * 1e9)]]
    share = spec.load_reader("experts_matmul_roofline").read(ctx)
    assert share == pytest.approx(100.0, rel=1e-6)


def test_the_roofline_reader_returns_nothing_where_there_is_nothing(cfg):
    read = spec.load_reader("experts_matmul_roofline").read
    no_products = traced(cfg)
    no_products["trace"]["devices"]["0"] = [["fusion.1", 10 * MS, 5 * MS]]
    # A driver that leaves no count of the traced steps' touched experts
    # (every accepted one), a window without a decode step, no trace.
    no_count = traced(cfg)
    del no_count["loop"]["traced_experts_touched"]
    for ctx in (no_products, no_count, traced(cfg, touched=0.0),
                traced(cfg, steps=0), dict(traced(cfg), trace=None)):
        assert read(ctx) is None


def test_the_accepted_readers_count_this_configurations_layers(cfg):
    counters = {
        "start": {"cgx.serve.decode_steps": 10,
                  "cgx.serve.moe.assignments": 1000.0,
                  "cgx.serve.moe.held_assignments": 100.0,
                  "cgx.serve.moe.experts_touched": 50.0,
                  "cgx.serve.kv.live_pages.window": 1000.0},
        "end": {"cgx.serve.decode_steps": 20,
                "cgx.serve.moe.assignments": 11240.0,
                "cgx.serve.moe.held_assignments": 1380.0,
                "cgx.serve.moe.experts_touched": 690.0,
                "cgx.serve.kv.live_pages.window": 6440.0},
    }
    ctx = dict(traced(cfg), counters=counters)
    assert spec.load_reader("moe_held_assignment_pct").read(ctx) == (
        pytest.approx(12.5))
    # 640 touched over 10 steps x 4 expert layers (the dense layer 0 is
    # none) x 32 held experts.
    assert spec.load_reader("moe_held_experts_touched_pct").read(ctx) == (
        pytest.approx(50.0))
    # 5,440 live pages over 10 steps x 64 lanes x 17 slots.
    assert spec.load_reader("kv_window_live_pct").read(ctx) == (
        pytest.approx(50.0))


def test_rehearsed_traced_run_returns_the_cells_metrics():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= {
        "experts_matmul_roofline", "experts_matmul_ms",
        "moe_held_assignment_pct", "moe_held_experts_touched_pct",
        "kv_window_read_ms", "kv_global_read_ms", "kv_window_read_roofline",
        "kv_window_live_pct", "kv_window_recycled_pct",
        "batch_occupancy_pct", "loop_compiles", "device_idle_pct.serve"}
    assert not {m["name"] for m in listed} & {
        "kv_read_ms", "moe_experts_touched_pct", "decode_step_ms"}
    roofline = spec.find(bench["per_layer"], "experts_matmul_roofline",
                         "metric")
    assert roofline["workloads"] == [CELL]
    result = harness.run(["--workload", CELL, "--seed", "4500000011",
                          "--seconds", "3", "--trace", "1",
                          "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # Every metric listed for the cell whose source a CPU run has; the
    # device trace's need the chip.
    for m in listed:
        if m["source"] == "device_trace":
            assert m["name"] not in result["metrics"]
        else:
            assert result["metrics"][m["name"]]["value"] >= 0, m["name"]
    # An eighth of the experts held: an eighth of the assignments, near.
    assert 8 < result["metrics"]["moe_held_assignment_pct"]["value"] < 18
    assert 0 < result["metrics"]["moe_held_experts_touched_pct"]["value"] < 100
    assert 0 < result["metrics"]["kv_window_live_pct"]["value"] < 100
    assert 0 < result["metrics"]["kv_window_recycled_pct"]["value"] < 100
    untraced = harness.run(["--workload", CELL, "--seed", "4500000012",
                            "--seconds", "3", "--trace", "0",
                            "--rehearse-cpu", "1"])
    assert untraced["correct"]
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
    control = harness.run(["--workload", CELL, "--seed", "4500000012",
                           "--seconds", "3", "--trace", "0", "--control",
                           "--rehearse-cpu", "1"])
    assert control["metrics"] == {}
