"""What PR 37 added for the KDA / latent-attention cell: the bytes of a state
update counted from the configuration (against a hand count), the two readers
of the ``cgx_kda_update`` kernel's device time and the two readers of the held
experts' counters (which return nothing, never raise, for a program or a
configuration without them), the configuration's published keys, and a
rehearsed traced run of the cell, which returns every metric listed for it
that a CPU run can read."""

import json

import pytest

from benchmark import bytes_kda, reference_ling_hybrid, run as harness, spec

CELL = "ling3-serve-reason128"
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
    row = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layers_kept",
                              "num_experts", "vocab_size"]
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # The cut: the leading dense layer and a whole period, a quarter of the
    # experts (two whole routing groups) and of the vocabulary.
    assert cfg["layers_kept"] == [0, 6, 7, 8, 9, 10, 11]
    assert cfg["num_hidden_layers"] == 7
    assert reference_ling_hybrid.layer_plan(cfg) == (
        [("kda", True)] + [("kda", False)] * 5 + [("mla", False)])
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (42, 512, 157184)
    assert cfg["num_experts_published"] == 512 == 4 * cfg["num_experts"]
    assert cfg["num_experts"] == 2 * (512 // cfg["n_group"])
    assert cfg["vocab_size"] * 4 == 157184
    for key in ("assumed", "precision", "init", "control", "deployment"):
        assert cfg.get(key) or key == "control", key


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1 and mix["driver"] == "serve_closed_kda"
    assert mix["clients"] == 128 == serve["max_batch"]
    assert mix["prompt_groups"] == [
        {"lo": 193, "hi": 255, "lengths": 16, "share": 1},
        {"lo": 449, "hi": 511, "lengths": 16, "share": 1}]
    assert mix["output"] == {"lo": 384, "hi": 1152, "lengths": 33}
    assert mix["population"] == 2048
    assert (mix["first_output_scale"], mix["ramp_s"], mix["trace_s"],
            mix["check_requests"]) == ([0.1, 1.0], 4.0, 4.0, 8)
    # A lane's longest table fits its positions, and the pool every lane's.
    assert 511 + 1152 <= serve["max_seq"] == 1792
    assert serve["max_pages"] == 128 * (1792 // serve["page_tokens"]) == 896


def test_state_update_bytes_from_the_configuration(cfg):
    # By hand: 128 lanes x 32 heads x (128 x 128) float32, read and written;
    # q, k and alpha (128 each), v and o (128 each), beta a head.
    state = 128 * 32 * 128 * 128 * 4
    small = 128 * 32 * (3 * 128 + 2 * 128 + 1) * 4
    assert (state, small) == (268_435_456, 10_502_144)
    assert bytes_kda.call_bytes(cfg) == 2 * state + small == 547_373_056
    assert bytes_kda.kda_layers(cfg) == 6
    assert bytes_kda.step_bytes(cfg) == 6 * (2 * state + small)
    narrow = spec.merge(cfg, {"precision": {"kda_state": "bfloat16"}})
    assert bytes_kda.call_bytes(narrow) == state + small


def traced(cfg, steps=2, counters=None):
    # Window 0..100 ms; two decode steps of three state updates each, one of
    # them outside the window; another model's kernel beside them.
    ops = [[f"cgx_kda_update.{i}", (10 + 10 * i) * MS, 2 * MS]
           for i in range(6)]
    ops += [["cgx_kda_update.9", 150 * MS, 2 * MS],
            ["cgx_gdn_update.3", 80 * MS, 5 * MS],
            ["fusion.1", 90 * MS, 5 * MS]]
    return {
        "config": cfg, "loop": {"traced_decode_steps": steps},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
        "counters": counters or {"start": {}, "end": {}},
    }


def test_readers_reduce_the_kernels_device_time(cfg):
    ctx = traced(cfg)
    ms = spec.load_reader("kda_update_ms").read(ctx)
    assert ms == pytest.approx(6.0)  # 6 calls x 2 ms over 2 steps
    share = spec.load_reader("kda_update_roofline").read(ctx)
    least_ms = bytes_kda.step_bytes(cfg) / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 6.0)


def test_readers_reduce_the_held_experts_counters(cfg):
    counters = {
        "start": {"cgx.serve.decode_steps": 10,
                  "cgx.serve.moe.assignments": 1000.0,
                  "cgx.serve.moe.held_assignments": 300.0,
                  "cgx.serve.moe.experts_touched": 500.0},
        "end": {"cgx.serve.decode_steps": 20,
                "cgx.serve.moe.assignments": 5000.0,
                "cgx.serve.moe.held_assignments": 1300.0,
                "cgx.serve.moe.experts_touched": 4340.0},
    }
    ctx = traced(cfg, counters=counters)
    assert spec.load_reader("moe_held_assignment_pct").read(ctx) == (
        pytest.approx(25.0))
    # 3,840 touched over 10 steps x 6 expert layers x 128 held experts.
    assert spec.load_reader("moe_held_experts_touched_pct").read(ctx) == (
        pytest.approx(50.0))


@pytest.mark.parametrize("metric", [
    "kda_update_ms", "kda_update_roofline", "moe_held_assignment_pct",
    "moe_held_experts_touched_pct"])
def test_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    joyai = json.loads((spec.ROOT / "benchmark" / "configs"
                        / "joyai-flash-serve-kv8.json").read_text())
    no_kernel = traced(cfg)
    no_kernel["trace"]["devices"]["0"] = [["cgx_gdn_update.1", 10 * MS,
                                           5 * MS]]
    no_steps = traced(cfg, steps=0)
    no_trace = dict(traced(cfg), trace=None)
    # A program that holds every expert counts no held assignments.
    whole = {"start": {"cgx.serve.decode_steps": 0},
             "end": {"cgx.serve.decode_steps": 9,
                     "cgx.serve.moe.assignments": 90.0,
                     "cgx.serve.moe.experts_touched": 50.0}}
    if metric.startswith("kda"):
        for ctx in (no_kernel, no_steps, no_trace):
            assert read(ctx) is None
        if metric == "kda_update_roofline":
            assert read(traced(joyai)) is None
    else:
        for ctx in (traced(cfg), traced(cfg, counters=whole),
                    traced(joyai, counters=whole),
                    dict(traced(cfg), counters=None)):
            assert read(ctx) is None


def test_rehearsed_traced_run_returns_the_cells_metrics():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= {
        "kda_update_ms", "kda_update_roofline", "moe_held_assignment_pct",
        "moe_held_experts_touched_pct"}
    assert not {m["name"] for m in listed} & {
        "latent_dequantize_roofline", "moe_experts_touched_pct"}
    result = harness.run(["--workload", CELL, "--seed", "3700000011",
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
    assert 0 < result["metrics"]["moe_held_assignment_pct"]["value"] < 100
    untraced = harness.run(["--workload", CELL, "--seed", "3700000012",
                            "--seconds", "3", "--trace", "0",
                            "--rehearse-cpu", "1"])
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
    control = harness.run(["--workload", CELL, "--seed", "3700000012",
                           "--seconds", "3", "--trace", "0", "--control",
                           "--rehearse-cpu", "1"])
    assert control["metrics"] == {}
