"""The reader PR 32 added: ``decode_ahead_pct`` reduces two of the program's
counters over the measured loop, and returns nothing (never raises) for a
program that does not count ``cgx.serve.decode.ahead``, as the parent of PR 32
does not, or for a loop without a decode step."""

import pytest

from benchmark import spec

SERVING_CELLS = ["gpt2l-serve-decode", "gpt2l-serve-prefill",
                 "joyai-serve-decode3k", "granite-serve-chat64"]


def read(start, end):
    ctx = {"config": {}, "counters": {"start": start, "end": end},
           "trace": None, "loop": {}, "peaks": None, "device_ids": [0]}
    return spec.load_reader("decode_ahead_pct").read(ctx)


def counters(steps, ahead=None):
    found = {"cgx.serve.decode_steps": steps}
    if ahead is not None:
        found["cgx.serve.decode.ahead"] = ahead
    return found


def test_share_of_the_steps_of_the_loop():
    assert read(counters(10.0, 4.0), counters(50.0, 34.0)) == pytest.approx(75.0)
    # a counter first bumped inside the loop has no entry at its start
    assert read(counters(10.0), counters(30.0, 5.0)) == pytest.approx(25.0)
    assert read(counters(10.0, 4.0), counters(50.0, 4.0)) == 0.0


@pytest.mark.parametrize("start,end", [
    (counters(10.0), counters(50.0)),  # the parent: no such counter
    (counters(10.0, 4.0), counters(10.0, 4.0)),  # no step in the loop
    ({}, {}),
])
def test_nothing_to_read_is_none(start, end):
    assert read(start, end) is None


def test_no_counters_at_all_is_none():
    assert spec.load_reader("decode_ahead_pct").read({"config": {}}) is None


def test_benchmark_lists_it_for_the_four_serving_cells():
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "decode_ahead_pct"]
    assert entry == {
        "name": "decode_ahead_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": SERVING_CELLS,
    }
    assert bench["per_layer"][-1] is entry  # appended, nothing moved
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert moved["workloads"] == SERVING_CELLS
