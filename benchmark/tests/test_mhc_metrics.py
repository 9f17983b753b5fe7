"""What PR 48 added for the Xing cell: the bytes of a step's hyper-connection
reads counted from the configuration (against a hand count), the three
readers (which return nothing, never raise, for a trace, a driver or a
configuration without what they read), the configuration's published keys
and its cut, the cell's traffic, and a rehearsed run of the cell, traced,
untraced and under its control."""

import json

import pytest

from benchmark import bytes_mhc, run as harness, spec, traffic

CELL = "xing4-serve-doc16k"
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
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "num_nextn_predict_layers"]
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
        else:
            assert cfg["published"][key] == value, key
    # The cut: the leading dense layers once, five expert layers after them
    # (the guide's floor is four), no prediction module.
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 0)
    for key in ("assumed", "precision", "init", "control", "deployment",
                "published"):
        assert cfg.get(key), key
    assert cfg["precision"]["hc_coefficients"] == "float32"


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1
    assert mix["driver"] == "serve_closed_latent"
    assert mix["clients"] == 32 == serve["max_batch"]
    groups = mix["prompt_groups"]
    assert [(g["lengths"], g["share"]) for g in groups] == [(16, 3), (16, 1)]
    # ISSUE 48's ranges; at whole pages of 256 they are six padded lengths,
    # a prefill program each, and every one is sent.
    assert [(g["lo"], g["hi"]) for g in groups] == [(7681, 8191),
                                                    (15361, 16383)]
    assert traffic.padded_lengths(mix, serve["page_tokens"]) == [
        7936, 8192, 15616, 15872, 16128, 16384]
    pages = serve["page_tokens"]
    assert mix["output"] == {"lo": 256, "hi": 768, "lengths": 33}
    assert (mix["population"], mix["check_requests"],
            mix["check_long_requests"]) == (2048, 8, 3)
    # A lane's longest table fits its positions, the pool every lane's.
    assert groups[1]["hi"] + 768 <= serve["max_seq"] == 69 * pages
    assert serve["max_pages"] == 32 * 69


def test_step_bytes_against_a_count_by_hand(cfg):
    # One call over 32 lanes of four 3,584-wide bfloat16 streams: the
    # streams 32 x 14,336 x 2; phi 14,336 x 24 float32; the sublayer's
    # input 32 x 3,584 x 2; H_post and H_res 32 x 20 float32.
    streams, u = 32 * 14_336 * 2, 32 * 3_584 * 2
    assert bytes_mhc.call_bytes(cfg) == (
        streams + 14_336 * 24 * 4 + u + 32 * 20 * 4) == 2_525_696
    # The read-out: four mixes, nothing but the one stream written.
    assert bytes_mhc.call_bytes(cfg, mixes=False) == (
        streams + 14_336 * 4 * 4 + u) == 1_376_256
    assert bytes_mhc.step_bytes(cfg) == 12 * 2_525_696 + 1_376_256
    wide = spec.merge(cfg, {"precision": {"activations": "float32"}})
    assert bytes_mhc.call_bytes(wide) - bytes_mhc.call_bytes(cfg) == (
        streams + u)


def traced(cfg, steps=2):
    # Window 0..100 ms; two decode steps of thirteen calls of 10 us each,
    # a prefill's calls beside them, one decode call outside the window.
    ops = [[f"cgx_mhc_pre_decode.{i}", (2 + i) * MS, 10_000]
           for i in range(26)]
    ops += [["cgx_mhc_pre_prefill.3", 40 * MS, 2 * MS],
            ["cgx_mhc_pre_decode.99", 150 * MS, 10_000],
            ["fusion.1", 95 * MS, 5 * MS]]
    return {
        "config": cfg,
        "loop": {"traced_decode_steps": steps,
                 "traced_mhc_res_err_ppm": 90.0},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
        "counters": {"start": {}, "end": {}},
    }


def test_the_readers_reduce_the_decode_calls_alone(cfg):
    ctx = traced(cfg)
    assert spec.load_reader("mhc_pre_ms").read(ctx) == pytest.approx(0.13)
    share = spec.load_reader("mhc_pre_roofline").read(ctx)
    least_ms = bytes_mhc.step_bytes(cfg) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 0.13)
    assert 0 < share < 100
    assert spec.load_reader("mhc_res_err_ppm").read(ctx) == (
        pytest.approx(45.0))


@pytest.mark.parametrize("metric", ["mhc_pre_ms", "mhc_pre_roofline",
                                    "mhc_res_err_ppm"])
def test_the_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    no_kernel = traced(cfg)
    no_kernel["trace"]["devices"]["0"] = [["fusion.1", 10 * MS, 5 * MS]]
    no_kernel["loop"].pop("traced_mhc_res_err_ppm")
    joyai = json.loads((spec.ROOT / "benchmark" / "configs"
                        / "joyai-flash-serve-kv8.json").read_text())
    other = dict(no_kernel, config=joyai)
    for ctx in (no_kernel, other, traced(cfg, steps=0),
                dict(no_kernel, trace=None)):
        assert read(ctx) is None


def test_rehearsed_runs_return_the_cells_metrics_and_the_control_fails():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= {
        "mhc_pre_ms", "mhc_pre_roofline", "mhc_res_err_ppm",
        "latent_dequantize_roofline", "experts_matmul_ms",
        "experts_matmul_roofline", "moe_experts_touched_pct",
        "moe_expert_load_max_over_mean", "batch_occupancy_pct",
        "loop_compiles", "device_idle_pct.serve"}
    for name in ("mhc_pre_ms", "mhc_pre_roofline", "mhc_res_err_ppm"):
        assert CELL in spec.find(bench["per_layer"], name,
                                 "metric")["workloads"]
    result = harness.run(["--workload", CELL, "--seed", "7", "--seconds",
                          "3", "--trace", "1", "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # Every metric listed for the cell whose source a CPU run has; the
    # device trace's need the chip.
    for m in listed:
        if m["source"] == "device_trace":
            assert m["name"] not in result["metrics"]
        else:
            assert result["metrics"][m["name"]]["value"] >= 0, m["name"]
    assert 0 < result["metrics"]["moe_experts_touched_pct"]["value"] <= 100
    # Twenty iterations leave the mixes within a thousandth of doubly
    # stochastic.
    assert 0 <= result["metrics"]["mhc_res_err_ppm"]["value"] < 1000
    untraced = harness.run(["--workload", CELL, "--seed", "10", "--seconds",
                            "3", "--trace", "0", "--rehearse-cpu", "1"])
    assert untraced["correct"]
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
    control = harness.run(["--workload", CELL, "--seed", "10", "--seconds",
                           "3", "--trace", "0", "--control",
                           "--rehearse-cpu", "1"])
    assert control["metrics"] == {} and not control["correct"]
    failed = {c["name"] for c in control["checks"] if not c["ok"]}
    assert failed == {"served_gap_max", "served_gap_mean"}
