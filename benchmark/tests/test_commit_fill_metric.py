"""The reader PR 34 added: ``commit_fill_pct`` reduces two of the program's
counters over the measured loop, and returns nothing (never raises) for a
program that does not count ``cgx.serve.commit.*``, as the parent of PR 34
does not, or for a loop in which no tail filled."""

import pytest

from benchmark import spec


def read(start, end):
    ctx = {"config": {}, "counters": {"start": start, "end": end},
           "trace": None, "loop": {}, "peaks": None, "device_ids": [0]}
    return spec.load_reader("commit_fill_pct").read(ctx)


def counters(lanes=None, rows=None):
    found = {"cgx.serve.decode_steps": 10.0}
    if lanes is not None:
        found["cgx.serve.commit.lanes"] = lanes
    if rows is not None:
        found["cgx.serve.commit.rows"] = rows
    return found


def test_share_of_the_rows_quantized_in_the_loop():
    assert read(counters(3.0, 8.0), counters(15.0, 56.0)) == pytest.approx(25.0)
    # counters first bumped inside the loop have no entry at its start
    assert read(counters(), counters(4.0, 8.0)) == pytest.approx(50.0)


@pytest.mark.parametrize("start,end", [
    (counters(), counters()),  # the parent: no such counters
    (counters(3.0, 8.0), counters(3.0, 8.0)),  # no tail filled in the loop
    (counters(), counters(lanes=4.0)),
    ({}, {}),
])
def test_nothing_to_read_is_none(start, end):
    assert read(start, end) is None


def test_no_counters_at_all_is_none():
    assert spec.load_reader("commit_fill_pct").read({"config": {}}) is None


def test_benchmark_lists_it_for_the_serving_cells():
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "commit_fill_pct"]
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert entry == {
        "name": "commit_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": moved["workloads"],
    }
