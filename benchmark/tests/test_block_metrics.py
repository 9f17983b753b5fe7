"""What PR 54 added for the SDAR cell: the bytes of a block-diffusion
decoder's decode step counted from the configuration (against a hand count
at the published sizes and against the tree the weights module draws), the
two readers (which return nothing, never raise, for a driver, a program or a
configuration without what they read), the configuration's published keys,
the cell's traffic and the lists the cell was appended to, the replay's
gaps, and a rehearsed run of the cell, traced, untraced and under its
control."""

import json

import jax
import numpy as np
import pytest

from benchmark import bytes_block, run as harness, spec, traffic
from benchmark import reference_block_diffusion as reference
from benchmark import weights_block_diffusion as weights

CELL = "sdar-serve-gen256"
OWN = ("block_forwards_per_token", "block_step_hbm_roofline")


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
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    entry = spec.find(spec.load_benchmark()["configs"], cfg["name"], "config")
    assert cfg["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 7
    assert cfg["published"]["num_hidden_layers"] == 48 == row["layers"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"], cfg["rope_theta"]) == (
                2048, 32, 4, 128, 128, 768, 8, 151936, 1000000)
    for key in ("assumed", "precision", "init", "control", "deployment",
                "harness"):
        assert cfg.get(key), key
    # what config.json does not hold, each under ``assumed``
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["confidence_threshold"], cfg["mask_token_id"]) == (
                4, 4, 0.9, 151669)
    said = " ".join(cfg["assumed"])
    for mark in ("(1) block length 4", "(2) denoising steps 4",
                 "(3) the unmask rule low_confidence_dynamic",
                 "(4) mask id 151669", "(5) no logit shift",
                 "(6) the prompt's remainder", "(7) the store forward",
                 "(8) QK-norm's placement"):
        assert mark in said, mark
    assert cfg["control"]["env"] == {"CGX_KV_BITS": "4"}
    assert cfg["serve"]["page_tokens"] % cfg["block_length"] == 0


def test_the_deployments_bytes_are_the_trees(cfg):
    """The deployment sentence's 9.97 GB is what the weights module draws,
    leaf by leaf, and what ``bytes_block`` counts a step to read of it."""
    tree = jax.eval_shape(lambda: weights.make_params(cfg, 1))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert held == 9_972_087_808
    assert "9.97 GB of bfloat16 weights" in cfg["deployment"]
    embed = cfg["vocab_size"] * cfg["hidden_size"] * 2
    assert held == (7 * bytes_block.layer_weight_bytes(cfg) + 2 * embed
                    + cfg["hidden_size"] * 4)
    pool = 7 * 2 * (cfg["serve"]["max_pages"] + 1) * bytes_block.page_bytes(
        cfg)
    assert pool == 596_843_520 and "0.597 GB" in cfg["deployment"]


def test_the_draw_is_the_init_blocks(cfg):
    tiny = spec.load_cell(spec.load_benchmark(), CELL,
                          rehearse=True)["config"]
    tiny["init"].update(qk_norm_gain=1.4, o_std=0.25)
    params = weights.make_params(tiny, 3)
    attn = params["layer_1"]["attn"]
    assert float(attn["q_norm"].mean()) == pytest.approx(1.4, rel=0.1)
    assert float(attn["o"].std()) == pytest.approx(0.25, rel=0.1)
    assert float(params["embed"].std()) == pytest.approx(
        tiny["init"]["embed_std"], rel=0.05)
    assert params["layer_0"]["moe"]["router"].dtype == np.float32
    assert cfg["init"]["qk_norm_gain"] ** 2 == pytest.approx(1.96)


def test_the_cell_is_the_issues_traffic():
    cell = spec.load_cell(spec.load_benchmark(), CELL, rehearse=False)
    mix, serve = cell["traffic"], cell["config"]["serve"]
    assert cell["cell"]["chips"] == 1
    assert mix["driver"] == "serve_closed_block"
    assert mix["clients"] == 64 == serve["max_batch"]
    groups = mix["prompt_groups"]
    assert [(g["lo"], g["hi"], g["share"]) for g in groups] == [
        (449, 511, 3), (961, 1023, 1)]
    # one padded length a group, and all four remainders over the block
    assert traffic.padded_lengths(mix, serve["page_tokens"]) == [512, 1024]
    for g in groups:
        every = traffic.lengths(g["lo"], g["hi"], g["lengths"])
        assert {n % 4 for n in every} == {0, 1, 2, 3}
    assert mix["output"] == {"lo": 256, "hi": 256, "lengths": 1}
    assert {out for _, out in traffic.population(mix)} == {256}
    assert mix["first_output_scale"] == [0.1, 1.0]
    assert (mix["ramp_s"], mix["trace_s"]) == (4.0, 4.0)
    # A lane's longest table fits its positions, the pool every lane's.
    assert groups[1]["hi"] + 256 == 1279 < serve["max_seq"] == 1280
    assert serve["max_seq"] == 20 * serve["page_tokens"]
    assert serve["max_pages"] == 64 * 20
    # at least 1,000 checked positions: a denoising step a position
    assert mix["check_requests"] * mix["check_blocks"] * 4 >= 1000
    limits = json.loads((spec.ROOT / "benchmark" / "limits"
                         / f"{CELL}.json").read_text())["limits"]
    assert limits["checked_positions"] == 1000
    assert set(limits) == {"served_gap_max", "served_gap_mean",
                           "unmask_gap_max", "unmask_gap_mean",
                           "moe_dropped", "checked_positions"}


def test_the_cell_is_in_the_lists_it_was_appended_to():
    """The cell reports two end-to-end metrics beside ``setup_s``, and is in
    every per-layer list that holds all the other serving cells, in the
    experts' four, in its own two, and in no other."""
    bench = spec.load_benchmark()
    assert {m["name"] for m in spec.end_to_end_for(bench, CELL)} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}
    serving = {w["name"] for w in bench["workloads"]
               if w["traffic"] != "cycle8" and w["name"] != CELL}
    experts = {"experts_matmul_ms", "experts_matmul_roofline",
               "moe_experts_touched_pct", "moe_expert_load_max_over_mean"}
    for m in bench["per_layer"]:
        listed = set(m["workloads"])
        if m["name"] in OWN:
            assert listed == {CELL}
            assert m["moves"] == "serve_tokens_per_s" and m["unit"]
        elif m["name"] in experts:
            assert CELL in listed
        else:
            assert (CELL in listed) == (serving <= listed), m["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "sdar-30b-a3b-serve-kv8"
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(OWN)


def test_step_bytes_against_a_count_by_hand(cfg):
    # A layer: q and o of 2,048 x 4,096, k and v of 2,048 x 512 and 128
    # experts of three 2,048 x 768 matrices in bfloat16; the router of
    # 2,048 x 128, two norms of 2,048 and two of 128 in float32.
    layer = ((2 * 2048 * 4096 + 2 * 2048 * 512 + 128 * 3 * 2048 * 768) * 2
             + (2048 * 128 + 2 * 2048 + 2 * 128) * 4)
    assert bytes_block.layer_weight_bytes(cfg) == layer == 1_246_774_272
    # Seven layers, the final norm, the head once, 64 lanes' blocks of four
    # rows of the embedding: the issue's 9.35 GB.
    read = 7 * layer + 2048 * 4 + 2048 * 151936 * 2 + 64 * 4 * 2048 * 2
    assert bytes_block.weight_bytes(cfg, 64) == read
    assert read == pytest.approx(9.35e9, rel=1e-3)
    # A page of one stream of one layer: 64 x 512 bytes and 64 buckets'
    # pairs; fourteen streams.
    assert bytes_block.page_bytes(cfg) == 32_768 + 64 * 8
    assert bytes_block.streams(cfg) == 14
    assert bytes_block.tail_row_bytes(cfg) == 512 * 4
    # 64 lanes that hold 900 pages and 2,000 tail rows between them.
    assert bytes_block.step_bytes(cfg, 64, 900, 2000) == (
        read + 14 * (900 * 33_280 + 2000 * 2_048))
    # 4-bit pages halve the packed bytes and leave the pairs.
    control = spec.merge(cfg, {"precision": {"kv_page_bits": 4}})
    assert bytes_block.page_bytes(control) == 16_384 + 64 * 8


def counted(cfg, **over):
    """A reader's context after a loop of 100 decode steps of 64 lanes,
    every block four denoising forwards and a store."""
    end = {"cgx.serve.decode_steps": 100.0,
           "cgx.serve.block.lane_steps": 100.0 * 64,
           "cgx.serve.tokens_generated": 100.0 * 64 * 4 / 5,
           "cgx.serve.kv.decoded_pages.global": 100.0 * 900,
           "cgx.serve.kv.live_tail_rows": 100.0 * (2000 + 64 * 4),
           "cgx.serve.device.step_s.sum": 1.6,
           "cgx.serve.device.step_s.count": 100.0}
    ctx = {"config": cfg, "loop": {"traced_decode_steps": 50.0},
           "trace": None, "peaks": {"hbm_bytes_per_s": 819e9},
           "device_ids": [0], "counters": {"start": {}, "end": end}}
    ctx.update(over)
    return ctx


def test_the_readers_read_the_programs_counters(cfg):
    share = spec.load_reader("block_step_hbm_roofline").read(counted(cfg))
    least_s = bytes_block.step_bytes(cfg, 64, 900, 2000) / 819e9
    assert share == pytest.approx(100.0 * least_s / 0.016)
    assert 0 < share < 100
    assert spec.load_reader("block_forwards_per_token").read(
        counted(cfg)) == pytest.approx(1.25)


@pytest.mark.parametrize("metric", OWN)
def test_the_readers_return_nothing_where_there_is_nothing(cfg, metric):
    read = spec.load_reader(metric).read
    bare = counted(cfg)
    bare["counters"] = {"start": {}, "end": {
        "cgx.serve.decode_steps": 100.0,
        "cgx.serve.device.step_s.sum": 8.0,
        "cgx.serve.device.step_s.count": 100.0}}
    gpt2 = json.loads((spec.ROOT / "benchmark" / "configs"
                       / "gpt2-large-serve-kv8.json").read_text())
    for ctx in (bare, dict(bare, config=gpt2), dict(bare, counters=None)):
        assert read(ctx) is None
    if metric == "block_step_hbm_roofline":
        assert read(counted(cfg, peaks=None)) is None  # a rehearsal
        assert read(counted(cfg, config=gpt2)) is None


def test_a_steps_gaps():
    """What a replayed step says: the served token's gap where the server
    unmasked, and how far the reference's log-confidence there lies under
    its most confident masked position."""
    gap = np.asarray([0.0, 0.3, 0.1, 0.2])
    logconf = np.asarray([-1.0, -0.5, -2.0, -0.7])
    when = np.asarray([-1, 2, 1, 3])  # a prompt token, then steps 2, 1, 3
    # step 1: positions 1-3 are masked; the server unmasked position 2,
    # the reference's least confident of them
    served, unmask = reference.step_gaps(gap, logconf, when, 1)
    assert served.tolist() == [0.1] and unmask.tolist() == [1.5]
    # step 2: positions 1 and 3 are masked; the server's is the best
    served, unmask = reference.step_gaps(gap, logconf, when, 2)
    assert served.tolist() == [0.3] and unmask.tolist() == [0.0]
    # two unmasked at once are held to the second most confident
    served, unmask = reference.step_gaps(gap, logconf,
                                         np.asarray([-1, 1, 1, 2]), 1)
    assert served.tolist() == [0.3, 0.1]
    assert unmask.tolist() == pytest.approx([0.0, 1.3])


def test_the_replays_rows_are_the_servers_blocks(cfg):
    tiny = dict(cfg, block_length=4, mask_token_id=99)
    prompt, output = [1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12, 13]
    steps = [0, 1, 2, 0, 3, 1, 0]
    # 6 + 7 = 13: blocks at 4 (two prompt tokens, two generated), at 8
    # (whole), and one at 12 the length cut short
    assert reference.whole_blocks(prompt, output, tiny) == 2
    pairs, (tokens, positions, starts) = reference.replay_rows(
        prompt, output, steps, tiny)
    assert pairs == [(0, 0), (0, 1), (1, 0), (1, 1), (1, 2), (1, 3)]
    assert tokens.tolist() == [
        [5, 6, 99, 99], [5, 6, 7, 99], [99, 99, 99, 99], [99, 10, 99, 99],
        [99, 10, 99, 12], [9, 10, 99, 12]]
    assert positions[2].tolist() == [8, 9, 10, 11] and starts[2] == 8
    assert reference.final_tokens(prompt, output, tiny, 16).tolist() == [
        1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 0, 0, 0, 0]


def test_rehearsed_runs_return_the_cells_metrics_and_the_control_fails():
    bench = spec.load_benchmark()
    listed = spec.per_layer_for(bench, CELL)
    assert {m["name"] for m in listed} >= set(OWN) | {
        "batch_occupancy_pct", "step_device_ms", "device_idle_pct.serve",
        "loop_compiles", "prefill_device_ms", "experts_matmul_ms"}
    result = harness.run(["--workload", CELL, "--seed", "5400000007",
                          "--seconds", "3", "--trace", "1",
                          "--rehearse-cpu", "1"])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # Every metric listed for the cell whose source a CPU run has; the
    # device trace's, the device's account (a tiny step never blocks a
    # read) and the share of the chip's published peak need the chip.
    account = {"step_device_ms", "commit_device_ms", "prefill_device_ms",
               "prefill_device_us_per_token", "block_step_hbm_roofline"}
    for m in listed:
        if m["source"] == "device_trace" or m["name"] in account:
            continue
        assert result["metrics"][m["name"]]["value"] >= 0, m["name"]
    assert "block_step_hbm_roofline" not in result["metrics"]
    # short answers: a first block's prompt tokens and a last block's
    # discarded ones make it more than 1.25
    assert 1.25 <= result["metrics"]["block_forwards_per_token"]["value"] < 2
    untraced = harness.run(["--workload", CELL, "--seed", "5400000010",
                            "--seconds", "3", "--trace", "0",
                            "--rehearse-cpu", "1"])
    assert untraced["correct"]
    assert sorted(untraced["metrics"]) == [
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"]
    assert [c["name"] for c in untraced["checks"]] == [
        "moe_dropped", "checked_positions", "served_gap_max",
        "served_gap_mean", "unmask_gap_max", "unmask_gap_mean"]
    control = harness.run(["--workload", CELL, "--seed", "5400000010",
                           "--seconds", "3", "--trace", "0", "--control",
                           "--rehearse-cpu", "1"])
    assert control["metrics"] == {} and not control["correct"]
    failed = {c["name"] for c in control["checks"] if not c["ok"]}
    assert {"served_gap_mean", "unmask_gap_mean"} <= failed
