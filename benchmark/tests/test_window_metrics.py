"""What PR 41 added for the window/global cell: the bytes of a step's window
reads counted from the configuration and the step's live pages (against a
hand count), the two readers of the two page classes' device time, the
roofline share that cannot pass 100 %, the two readers of the scheduler's
window counters (each returns nothing, never raises, for a program, a
configuration or a trace without what it reads), the configuration's
published keys, the sample that ``correct`` compares, and a rehearsed traced
run of the cell, which returns every metric listed for it that a CPU run can
read."""

import json

import pytest

from benchmark import bytes_window, run as harness, spec

CELL = "smallthinker-serve-mix8k"
MS = 1_000_000
NEW = ("kv_window_read_ms", "kv_global_read_ms", "kv_window_read_roofline",
       "kv_window_live_pct", "kv_window_recycled_pct")


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
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # The cut: two whole periods of global, window, window, window.
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"]["num_hidden_layers"] == 52
    assert cfg["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert cfg["rope_layout"][:8] == cfg["sliding_window_layout"][:8]
    for key in ("assumed", "precision", "init", "control", "deployment"):
        assert cfg.get(key), key


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1
    assert mix["driver"] == "serve_closed_window"
    assert mix["clients"] == 48 == serve["max_batch"]
    assert mix["prompt_groups"] == [
        {"lo": 449, "hi": 511, "lengths": 16, "share": 1},
        {"lo": 7937, "hi": 8191, "lengths": 16, "share": 1}]
    assert mix["output"] == {"lo": 256, "hi": 768, "lengths": 33}
    assert (mix["population"], mix["population_seed"]) == (2048, 41)
    assert (mix["first_output_scale"], mix["ramp_s"], mix["trace_s"],
            mix["check_requests"], mix["check_long_requests"]) == (
        [0.1, 1.0], 4.0, 4.0, 8, 3)
    # A lane's longest table fits its positions, the global pools every
    # lane's, and the long prompts are twice the window.
    assert 8191 + 768 <= serve["max_seq"] == 9216
    assert serve["max_pages"] == 48 * (9216 // serve["page_tokens"]) == 1728
    assert 8192 == 2 * cell["config"]["sliding_window_size"]


def test_window_read_bytes_from_the_configuration(cfg):
    # By hand: a page of one stream is 256 tokens x 4 heads x 128 = 131,072
    # values: a byte each of 8-bit words, two float32 a 512-value bucket,
    # bfloat16 rows out.
    page = 131_072 + 256 * 8 + 131_072 * 2
    assert bytes_window.page_bytes(cfg) == page == 395_264
    assert bytes_window.window_layers(cfg) == 6
    # K and V of six layers over 400 live pages a step.
    assert bytes_window.step_bytes(cfg, 400) == 2 * 6 * 400 * page
    narrow = spec.merge(cfg, {"precision": {"kv_page_bits": 4}})
    assert bytes_window.page_bytes(narrow) == page - 65_536
    # Every slot of every ring live is the most a step can count, and what
    # the kernel decodes whatever is live.
    full = bytes_window.step_bytes(cfg, 48 * 17)
    assert full == 2 * 6 * 816 * page


def traced(cfg, steps=2, live=800.0, counters=None):
    # Window 0..100 ms; two decode steps of six window reads and two global
    # reads each; one window read outside the window; other ops beside them.
    ops = [[f"cgx_dequantize_window.{i}", (5 + 5 * i) * MS, 3 * MS]
           for i in range(12)]
    ops += [[f"cgx_dequantize_flat.{i}", (70 + 5 * i) * MS, 4 * MS]
            for i in range(4)]
    ops += [["cgx_dequantize_window.99", 150 * MS, 3 * MS],
            ["cgx_grouped_matmul.3", 92 * MS, 2 * MS],
            ["fusion.1", 95 * MS, 5 * MS]]
    return {
        "config": cfg,
        "loop": {"traced_decode_steps": steps,
                 "traced_live_window_pages": live},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
        "counters": counters or {"start": {}, "end": {}},
    }


def test_readers_reduce_the_two_page_classes_device_time(cfg):
    ctx = traced(cfg)
    assert spec.load_reader("kv_window_read_ms").read(ctx) == (
        pytest.approx(18.0))  # 12 calls x 3 ms over 2 steps
    assert spec.load_reader("kv_global_read_ms").read(ctx) == (
        pytest.approx(8.0))  # 4 calls x 4 ms over 2 steps
    share = spec.load_reader("kv_window_read_roofline").read(ctx)
    least_ms = bytes_window.step_bytes(cfg, 400.0) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 18.0)


def test_the_roofline_share_cannot_pass_100(cfg):
    # Every slot of every ring live, read in the time HBM needs for them:
    # the most the live count can be over the least the read can take.
    ctx = traced(cfg, steps=1, live=48 * 17.0)
    least_s = bytes_window.step_bytes(cfg, 48 * 17) / 819e9
    ctx["trace"]["devices"]["0"] = [
        ["cgx_dequantize_window.1", 10 * MS, int(least_s * 1e9)]]
    share = spec.load_reader("kv_window_read_roofline").read(ctx)
    assert share == pytest.approx(100.0, rel=1e-6)


def test_readers_reduce_the_window_counters(cfg):
    counters = {
        "start": {"cgx.serve.decode_steps": 10,
                  "cgx.serve.kv.live_pages.window": 1000.0,
                  "cgx.serve.window.pages_committed": 120.0,
                  "cgx.serve.window.pages_recycled": 20.0},
        "end": {"cgx.serve.decode_steps": 20,
                "cgx.serve.kv.live_pages.window": 5080.0,
                "cgx.serve.window.pages_committed": 600.0,
                "cgx.serve.window.pages_recycled": 260.0},
    }
    ctx = traced(cfg, counters=counters)
    # 4,080 live pages over 10 steps x 48 lanes x 17 slots.
    assert spec.load_reader("kv_window_live_pct").read(ctx) == (
        pytest.approx(50.0))
    assert spec.load_reader("kv_window_recycled_pct").read(ctx) == (
        pytest.approx(50.0))


@pytest.mark.parametrize("metric", NEW)
def test_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    joyai = json.loads((spec.ROOT / "benchmark" / "configs"
                        / "joyai-flash-serve-kv8.json").read_text())
    # A parent's program: the one kernel name, none of the counters.
    parent_trace = traced(cfg)
    parent_trace["trace"]["devices"]["0"] = [
        ["cgx_dequantize_flat.1", 10 * MS, 5 * MS]]
    parent_trace["loop"] = {"traced_decode_steps": 2}
    parent_counters = {"start": {"cgx.serve.decode_steps": 0},
                       "end": {"cgx.serve.decode_steps": 9}}
    no_steps = traced(cfg, steps=0)
    no_trace = dict(traced(cfg), trace=None)
    if metric == "kv_global_read_ms":
        for ctx in (no_steps, no_trace, traced(joyai)):
            assert read(ctx) is None
    elif metric.endswith(("_ms", "_roofline")):
        for ctx in (parent_trace, no_steps, no_trace):
            assert read(ctx) is None
        if metric == "kv_window_read_roofline":
            assert read(traced(joyai)) is None
            assert read(traced(cfg, live=0.0)) is None
    else:
        for ctx in (traced(cfg), traced(cfg, counters=parent_counters),
                    dict(traced(cfg), counters=None)):
            assert read(ctx) is None


def test_the_sample_holds_the_longest_and_enough_long_requests():
    driver = spec.load_module("drivers", "serve_closed_window")
    mix = {"prompt_groups": [{"lo": 449, "hi": 511}, {"lo": 7937, "hi": 8191}],
           "check_requests": 8, "check_long_requests": 3}
    done = [{"prompt": [0] * (8000 if i % 10 == 0 else 500),
             "output": [0] * (300 + i)} for i in range(40)]
    for seed in range(5):
        picked = driver.sample(done, mix, seed)
        assert len(picked) == len(set(picked)) == 8
        assert picked[0] == 30  # the longest prompt + answer
        assert sum(len(done[i]["prompt"]) == 8000 for i in picked) >= 3
    few = driver.sample(done[1:8], mix, 0)  # no long request finished
    assert len(few) == 7


def test_rehearsed_traced_run_returns_the_cells_metrics():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= set(NEW) | {
        "experts_matmul_ms", "moe_experts_touched_pct",
        "moe_expert_load_max_over_mean", "batch_occupancy_pct"}
    assert not {m["name"] for m in listed} & {
        "kv_read_ms", "cgx_dequantize_flat_roofline", "decode_step_ms"}
    result = harness.run(["--workload", CELL, "--seed", "4100000011",
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
    assert 0 < result["metrics"]["kv_window_live_pct"]["value"] < 100
    assert 0 < result["metrics"]["kv_window_recycled_pct"]["value"] < 100
    untraced = harness.run(["--workload", CELL, "--seed", "4100000012",
                            "--seconds", "3", "--trace", "0",
                            "--rehearse-cpu", "1"])
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
    control = harness.run(["--workload", CELL, "--seed", "4100000012",
                           "--seconds", "3", "--trace", "0", "--control",
                           "--rehearse-cpu", "1"])
    assert control["metrics"] == {}
