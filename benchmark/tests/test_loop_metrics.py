"""What PR 52 added for the Ouro cell: the bytes of a looped decoder's decode
step counted from the configuration (against a hand count at the published
sizes), the two readers (which return nothing, never raise, for a driver, a
program or a configuration without what they read), the configuration's
published keys, the cell's traffic and the lists the cell was appended to,
and a rehearsed run of the cell, traced, untraced and under its control."""

import json

import pytest

from benchmark import bytes_loop, run as harness, spec, traffic
from benchmark import weights_loop

CELL = "ouro-serve-answer16"
OWN = ("loop_step_hbm_roofline", "loop_exit_mean_pass")


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
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"] == []  # nothing is cut
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["vocab_size"]) == (48, 4, 49152)
    for key in ("assumed", "precision", "init", "control", "deployment"):
        assert cfg.get(key), key
    # the five lines the issue marks as assumed, each named
    said = " ".join(cfg["assumed"])
    for mark in ("(1) no bias", "(2) cache slot index", "(3) the sandwich",
                 "(4) the final RMSNorm closes every pass",
                 "(5) the exit gate"):
        assert mark in said, mark
    assert cfg["precision"]["exit_gate"] == "float32"
    assert cfg["control"]["env"] == {"CGX_KV_BITS": "4"}


def test_the_draw_is_the_init_blocks(cfg):
    """The handles the init block states reach the tree: the two norms that
    scale a sub-layer's output are drawn about ``out_norm_gain`` (0.1 at the
    published depth: a pass's 96 outputs add up to its input's scale; 1.0 in
    the three-layer rehearsal, which has no depth to tame), the others about
    1, the gate's bias is one number, and q and k are wider than v."""
    assert cfg["init"]["out_norm_gain"] == pytest.approx(
        1 / (2 * cfg["num_hidden_layers"]) ** 0.5, rel=0.05)
    tiny = spec.load_cell(spec.load_benchmark(), CELL,
                          rehearse=True)["config"]
    assert tiny["init"]["out_norm_gain"] == 1.0
    tiny["init"]["out_norm_gain"] = 0.25
    layer = weights_loop.make_params(tiny, 3)["layer_1"]
    for name, mean in (("in_norm", 1.0), ("pre_mlp_norm", 1.0),
                       ("post_attn_norm", 0.25), ("post_mlp_norm", 0.25)):
        assert float(layer[name].mean()) == pytest.approx(mean, rel=0.02)
    q, v = (layer["attn"][k].astype("float32") for k in ("q", "v"))
    assert float(q.std()) == pytest.approx(tiny["init"]["qk_std"], rel=0.1)
    assert float(v.std()) == pytest.approx(tiny["init"]["std"], rel=0.1)


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1
    assert mix["driver"] == "serve_closed_loop"
    assert mix["clients"] == 16 == serve["max_batch"]
    groups = mix["prompt_groups"]
    assert [(g["lo"], g["hi"], g["share"]) for g in groups] == [
        (65, 127, 1), (193, 255, 1)]
    # ISSUE 52's ranges; at whole pages of 32 they are four padded lengths,
    # a prefill program each.
    assert traffic.padded_lengths(mix, serve["page_tokens"]) == [
        96, 128, 224, 256]
    assert mix["output"] == {"lo": 96, "hi": 192, "lengths": 33}
    assert (mix["ramp_s"], mix["trace_s"], mix["check_requests"]) == (
        4.0, 4.0, 8)
    # A lane's longest table fits its positions, the pool every lane's.
    assert groups[1]["hi"] + 192 == 447 < serve["max_seq"] == 448
    assert serve["max_seq"] == 14 * serve["page_tokens"]
    assert serve["max_pages"] == 16 * 14


def test_the_cell_is_in_the_lists_it_was_appended_to():
    """The cell reports two end-to-end metrics beside ``setup_s``, and is in
    every per-layer list that holds all the other serving cells, in its own
    two, and in no other (a test of a list asks that its own cell is in it,
    not that the list ends with it)."""
    bench = spec.load_benchmark()
    assert {m["name"] for m in spec.end_to_end_for(bench, CELL)} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}
    serving = {w["name"] for w in bench["workloads"]
               if w["traffic"] != "cycle8" and w["name"] != CELL}
    for m in bench["per_layer"]:
        listed = set(m["workloads"])
        if m["name"] in OWN:
            assert listed == {CELL}
            assert m["moves"] == "serve_tokens_per_s" and m["unit"]
        else:
            assert (CELL in listed) == (serving <= listed), m["name"]
    for name in ("kv_read_ms", "cgx_dequantize_flat_roofline"):
        assert CELL not in spec.find(bench["per_layer"], name,
                                     "metric")["workloads"]


def test_step_bytes_against_a_count_by_hand(cfg):
    # A layer: four 2,048 x 2,048 projections and three 2,048 x 5,632 of the
    # SwiGLU in bfloat16, four float32 norms of 2,048.
    layer = (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2 + 4 * 2048 * 4
    assert bytes_loop.layer_weight_bytes(cfg) == layer == 102_793_216
    # 48 layers four times, the final norm and the gate four times, the head
    # once, sixteen rows of the embedding: the issue's 19.9 GB.
    weights = (4 * (48 * layer + (2 * 2048 + 1) * 4) + 2048 * 49152 * 2
               + 16 * 2048 * 2)
    assert bytes_loop.weight_bytes(cfg, 16) == weights
    assert weights == pytest.approx(19.94e9, rel=1e-3)
    # A page of one stream of one slot: 32 x 2,048 bytes and 128 buckets'
    # pairs; a position's pages over the 384 slots and streams: 798,720.
    assert bytes_loop.page_bytes(cfg) == 65_536 + 128 * 8
    assert bytes_loop.slots(cfg) == 2 * 4 * 48
    assert bytes_loop.slots(cfg) * bytes_loop.page_bytes(cfg) / 32 == 798_720
    assert bytes_loop.tail_row_bytes(cfg) == 2048 * 4
    # Sixteen lanes that hold 108 pages and 264 tail rows between them.
    assert bytes_loop.step_bytes(cfg, 16, 108, 264) == (
        weights + 384 * (108 * 66_560 + 264 * 8_192))
    # 4-bit pages halve the packed bytes and leave the pairs.
    control = spec.merge(cfg, {"precision": {"kv_page_bits": 4}})
    assert bytes_loop.page_bytes(control) == 32_768 + 128 * 8


def counted(cfg, **over):
    """A reader's context after a loop of 100 decode steps of 16 lanes."""
    end = {"cgx.serve.decode_steps": 100.0,
           "cgx.serve.loop.passes": 100.0 * 16 * 4,
           "cgx.serve.kv.decoded_pages.global": 100.0 * 108,
           "cgx.serve.kv.live_tail_rows": 100.0 * 264,
           "cgx.serve.device.step_s.sum": 8.0,
           "cgx.serve.device.step_s.count": 100.0}
    loop = {"traced_decode_steps": 50.0, "traced_loop_passes": 3200.0,
            "traced_exit_mass_1": 400_000.0, "traced_exit_mass_2": 200_000.0,
            "traced_exit_mass_3": 100_000.0, "traced_exit_mass_4": 100_000.0}
    ctx = {"config": cfg, "loop": loop, "trace": None,
           "peaks": {"hbm_bytes_per_s": 819e9}, "device_ids": [0],
           "counters": {"start": {}, "end": end}}
    ctx.update(over)
    return ctx


def test_the_readers_read_the_programs_counters(cfg):
    share = spec.load_reader("loop_step_hbm_roofline").read(counted(cfg))
    least_s = bytes_loop.step_bytes(cfg, 16, 108, 264) / 819e9
    assert share == pytest.approx(100.0 * least_s / 0.08)
    assert 0 < share < 100
    # 800,000 thousandths: 50 steps of 16 lanes; the mean pass.
    assert spec.load_reader("loop_exit_mean_pass").read(counted(cfg)) == (
        pytest.approx((400 + 2 * 200 + 3 * 100 + 4 * 100) / 800))


@pytest.mark.parametrize("metric", OWN)
def test_the_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    bare = counted(cfg, loop={"traced_decode_steps": 50.0})
    bare["counters"] = {"start": {}, "end": {
        "cgx.serve.decode_steps": 100.0,
        "cgx.serve.device.step_s.sum": 8.0,
        "cgx.serve.device.step_s.count": 100.0}}
    gpt2 = json.loads((spec.ROOT / "benchmark" / "configs"
                       / "gpt2-large-serve-kv8.json").read_text())
    for ctx in (bare, dict(bare, config=gpt2), dict(bare, counters=None),
                dict(bare, peaks=None)):
        assert read(ctx) is None
    if metric == "loop_step_hbm_roofline":
        assert read(counted(cfg, peaks=None)) is None  # a rehearsal


def test_rehearsed_runs_return_the_cells_metrics_and_the_control_fails():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= set(OWN) | {
        "batch_occupancy_pct", "step_device_ms", "device_idle_pct.serve",
        "loop_compiles", "prefill_device_ms"}
    result = harness.run(["--workload", CELL, "--seed", "7", "--seconds",
                          "3", "--trace", "1", "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # Every metric listed for the cell whose source a CPU run has; the
    # device trace's and the share of the chip's published peak need the
    # chip.
    for m in listed:
        if (m["source"] == "device_trace"
                or m["name"] == "loop_step_hbm_roofline"):
            assert m["name"] not in result["metrics"]
        else:
            assert result["metrics"][m["name"]]["value"] >= 0, m["name"]
    # three passes at the rehearsal's size
    assert 1 < result["metrics"]["loop_exit_mean_pass"]["value"] < 3
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
    assert "served_gap_mean" in failed
