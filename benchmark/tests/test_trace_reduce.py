"""The reduction from a trace to numbers, on a hand-made trace (exact
arithmetic) and on a small trace recorded on the chip."""

import json
from pathlib import Path

import pytest

from benchmark import spec, trace_reduce as tr

MS = 1_000_000


def hand_made():
    # Window 0..100 ms. Ops: 10-30, 20-40 (overlap), 60-70; a kernel 80-85.
    return {
        "devices": {"0": [
            ["fusion.1", 10 * MS, 20 * MS], ["fusion.2", 20 * MS, 20 * MS],
            ["all-gather-done.3", 60 * MS, 6 * MS],
            ["all_to_all.9", 66 * MS, 4 * MS],  # the trace's own spelling
            ["cgx_dequantize_flat.7", 80 * MS, 5 * MS],
            ["fusion.1", 150 * MS, 10 * MS],  # outside the window
        ]},
        "host": [
            ["bench.window", 0, 100 * MS],
            ["bench.step", 0, 50 * MS], ["bench.admit/prefill", 41 * MS, 9 * MS],
            ["bench.step", 50 * MS, 50 * MS], ["bench.decode", 55 * MS, 40 * MS],
        ],
    }


def test_op_name_keeps_the_instruction_name():
    text = ('%cgx_dequantize_flat.73 = f32[327680,128]{1,0:T(8,128)} '
            'custom-call(s32[81920,128]{1,0} %reshape.3498), '
            'custom_call_target="tpu_custom_call"')
    assert tr.op_name(text) == "cgx_dequantize_flat.73"
    assert tr.op_name("fusion.5") == "fusion.5"
    assert tr.family("cgx_dequantize_flat.73") == "cgx_dequantize_flat"
    assert tr.family("all-gather-done.3") == "all-gather-done"


def test_busy_is_the_union_and_gaps_are_what_is_left():
    t = hand_made()
    assert tr.window(t) == (0, 100 * MS)
    busy, gaps = tr.busy_and_gaps(t["devices"]["0"], 0, 100 * MS)
    assert busy == pytest.approx(0.045)  # 30 + 10 + 5 ms
    assert gaps == [(0, 10 * MS), (40 * MS, 60 * MS), (70 * MS, 80 * MS),
                    (85 * MS, 100 * MS)]
    assert sum(b - a for a, b in gaps) / 1e9 + busy == pytest.approx(0.1)


def test_events_are_clipped_to_the_window():
    t = hand_made()
    busy, _ = tr.busy_and_gaps(t["devices"]["0"], 25 * MS, 65 * MS)
    assert busy == pytest.approx(0.020)  # 25-40 and 60-65


def test_kernel_and_collective_seconds():
    ev = hand_made()["devices"]["0"]
    assert tr.seconds_where(ev, 0, 100 * MS, tr.is_collective) == (
        pytest.approx(0.010), 2)
    assert tr.is_collective("all-to-all.2") and tr.is_collective("all_to_all")
    assert not tr.is_collective("fusion.3")
    assert tr.seconds_where(ev, 0, 100 * MS, tr.is_codec_kernel) == (
        pytest.approx(0.005), 1)
    assert tr.top_ops(ev, 0, 100 * MS, 2) == [
        ["fusion", pytest.approx(0.040)],
        ["all-gather-done", pytest.approx(0.006)]]


def test_gaps_are_labelled_by_the_innermost_host_span():
    t = hand_made()
    _, gaps = tr.busy_and_gaps(t["devices"]["0"], 0, 100 * MS)
    labelled = dict(tr.idle_by_label(gaps, t["host"]))
    # 0-10 (mid 5): step; 40-60 (mid 50): second step only (decode starts at
    # 55); 70-80 and 85-100: decode (until 95), mid 92.5 is inside decode.
    assert labelled == {"step": pytest.approx(0.030),
                        "decode": pytest.approx(0.025)}
    assert tr.label_at(t["host"], 45 * MS) == "admit/prefill"
    assert tr.label_at(t["host"], 200 * MS) == "outside-any-span"


def test_device_summary_and_layer_metric_readers_on_the_hand_made_trace():
    t = hand_made()
    s = tr.device_summary(t, [0])
    assert s["busy_s"] == pytest.approx(0.045)
    assert s["window_s"] == pytest.approx(0.1)
    assert len(s["breakdown"]["device_ops"]) <= 10
    ctx = {"trace": t, "device_ids": [0], "loop": {"traced_decode_steps": 1,
                                                   "traced_steps": 2},
           "config": {"n_embd": 1280, "serve": {"max_batch": 32,
                                                "max_seq": 1024},
                      "precision": {"kv_page_bits": 8, "kv_bucket": 512}},
           "peaks": spec.peaks_for("TPU v5 lite")}
    read = lambda name: spec.load_reader(name).read(ctx)
    assert read("kv_read_ms") == pytest.approx(5.0)
    assert read("device_idle_pct.serve") == pytest.approx(55.0)
    assert read("device_idle_pct.train") == pytest.approx(55.0)
    assert read("collective_exposed_ms") == pytest.approx(5.0)
    assert read("codec_kernel_ms") == pytest.approx(2.5)
    # 210,370,560 B / 819e9 B/s = 0.25686 ms least; the call took 5 ms.
    assert read("cgx_dequantize_flat_roofline") == pytest.approx(
        100 * 210_370_560 / 819e9 / 0.005)
    empty = dict(ctx, trace={"devices": {}, "host": t["host"]})
    assert spec.load_reader("kv_read_ms").read(empty) is None


RECORDED = Path(__file__).parent / "data" / "decode_trace_small.json"


def test_recorded_chip_trace():
    """One decode step of a traced run of gpt2l-serve-decode on a TPU v5
    lite (PR 24), with the 30 ms before it, in the reduced form
    ``load_profile`` gives (written by ``benchmark/trace_list.py``)."""
    t = json.loads(RECORDED.read_text())
    t0, t1 = tr.window(t)
    events = t["devices"]["0"]
    busy, gaps = tr.busy_and_gaps(events, t0, t1)
    assert 0 < busy < (t1 - t0) / 1e9
    assert sum(b - a for a, b in gaps) / 1e9 + busy == pytest.approx(
        (t1 - t0) / 1e9)
    seconds, count = tr.seconds_where(
        events, t0, t1, lambda n: n.startswith("cgx_dequantize_flat"))
    assert count == 72  # K and V of 36 layers in one decode step
    assert 0.4e-3 < seconds / count < 1.5e-3  # about half a millisecond
    ctx = {"trace": t, "device_ids": [0], "loop": {"traced_decode_steps": 1},
           "config": {"n_embd": 1280, "serve": {"max_batch": 32,
                                                "max_seq": 1024},
                      "precision": {"kv_page_bits": 8, "kv_bucket": 512}},
           "peaks": spec.peaks_for("TPU v5 lite")}
    share = spec.load_reader("cgx_dequantize_flat_roofline").read(ctx)
    assert 20 < share < 100
    labels = {label for label, _ in tr.idle_by_label(gaps, t["host"])}
    assert "decode" in labels
    assert labels <= {"admit/prefill", "decode", "step", "bookkeeping",
                      "outside-any-span"}
    top = dict(tr.top_ops(events, t0, t1))
    assert "cgx_dequantize_flat" in top


RECORDED_TRAIN = Path(__file__).parent / "data" / "train_trace_small.json"


def test_recorded_training_trace_counts_the_chips_own_spelling():
    """6 ms of one step of gpt2s-dp4-q4 on four TPU v5 lite chips (PR 24):
    the last 1,200 ops of the stretch ``benchmark/trace_list.py`` wrote of
    the first chip's op line. The quantized reduce-scatter leg is spelt
    ``all_to_all`` there (17 of them, between the codec's kernels) beside an
    ``all-reduce``; ``collective_exposed_ms`` has to count both spellings.
    The step's ``all-gather`` leg lies later and is in the hand-made trace."""
    t = json.loads(RECORDED_TRAIN.read_text())
    t0, t1 = tr.window(t)
    events = t["devices"]["0"]
    counted = [tr.family(name) for name, _, _ in events
               if tr.is_collective(name)]
    assert sorted(set(counted)) == ["all-reduce", "all_to_all"]
    assert counted.count("all_to_all") == 17
    by_family = {
        f: tr.seconds_where(events, t0, t1,
                            lambda n, f=f: tr.family(n) == f)[0]
        for f in set(counted)}
    assert all(s > 0 for s in by_family.values())
    ctx = {"trace": t, "device_ids": [0], "loop": {"traced_steps": 1}}
    assert spec.load_reader("collective_exposed_ms").read(ctx) == (
        pytest.approx(1e3 * sum(by_family.values())))
    assert by_family["all_to_all"] > 5 * by_family["all-reduce"]
    kernels = {tr.family(name) for name, _, _ in events
               if tr.is_codec_kernel(name)}
    assert kernels == {"cgx_quantize_flat", "cgx_dequantize_flat",
                       "cgx_quantize_chunks"}
    assert spec.load_reader("codec_kernel_ms").read(ctx) > 0
