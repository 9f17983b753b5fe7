"""The nine readers PR 35 added over the tick's account: each reduces the
program's ``cgx.serve.*`` histograms (one counter) over the measured loop,
and returns nothing (never raises) for a program that does not write them, as
the parent of PR 35 does not, or for a loop that gives it a zero divisor."""

import pytest

from benchmark import spec

CELLS = ["gpt2l-serve-decode", "gpt2l-serve-prefill", "joyai-serve-decode3k",
         "granite-serve-chat64", "olmoh-serve-chat96"]
# name -> (unit, better, source, moves)
ENTRIES = {
    "step_dispatch_ms": ("ms", "lower", "program_span", "serve_tokens_per_s"),
    "commit_dispatch_ms": ("ms", "lower", "program_span",
                           "serve_tokens_per_s"),
    "caller_ms": ("ms", "lower", "program_span", "serve_tokens_per_s"),
    "host_blocked_pct": ("%", "higher", "program_span", "serve_tokens_per_s"),
    "device_unfed_pct": ("%", "lower", "program_span", "serve_tokens_per_s"),
    "ttft_behind_ms": ("ms", "lower", "program_span", "serve_ttft_p90_ms"),
    "prefill_first_token_ms": ("ms", "lower", "program_span",
                               "serve_ttft_p90_ms"),
    "tick_stall_pct": ("%", "lower", "program_span", "serve_tokens_per_s"),
    "loop_compiles": ("count", "lower", "program_counter",
                      "serve_tokens_per_s"),
}
# The readers that are the mean of one histogram, in milliseconds.
MEANS = {
    "step_dispatch_ms": "dispatch_step_s",
    "commit_dispatch_ms": "dispatch_commit_s",
    "caller_ms": "between_steps_s",
    "ttft_behind_ms": "ttft_behind_s",
    "prefill_first_token_ms": "prefill_first_token_s",
}
SHARES = ("host_blocked_pct", "device_unfed_pct", "tick_stall_pct")


def read(name, start, end):
    ctx = {"config": {}, "counters": {"start": start, "end": end},
           "trace": None, "loop": {}, "peaks": None, "device_ids": [0]}
    return spec.load_reader(name).read(ctx)


def hist(name, count, total):
    return {f"cgx.serve.{name}.count": float(count),
            f"cgx.serve.{name}.sum": float(total)}


def loop(ticks=100, tick_s=0.05, between_s=0.0005, **hists):
    """A loop's counters after ``ticks`` ticks: the wall's two histograms,
    and ``name=(count, sum)`` for every other one."""
    found = {"cgx.serve.decode_steps": float(ticks),
             **hist("step_s", ticks, ticks * tick_s),
             **hist("between_steps_s", ticks, ticks * between_s)}
    for name, (count, total) in hists.items():
        found.update(hist(name, count, total))
    return found


# What the parent's program leaves: ticks, no time between them, and the one
# of the five histograms it has written since PR 26.
PARENT = {"cgx.serve.decode_steps": 100.0, **hist("step_s", 100, 5.0),
          **hist("prefill_first_token_s", 40, 0.02)}


@pytest.mark.parametrize("name", sorted(MEANS))
def test_a_mean_is_the_sums_growth_over_the_counts(name):
    h = MEANS[name]
    start, end = loop(**{h: (10, 0.5)}), loop(**{h: (30, 0.54)})
    assert read(name, start, end) == pytest.approx(2.0)  # 40 ms over 20
    # a histogram first observed inside the loop has no entry at its start
    assert read(name, {}, hist(h, 4, 0.002)) == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(MEANS))
def test_a_mean_with_nothing_observed_in_the_loop_is_none(name):
    h = MEANS[name]
    assert read(name, loop(**{h: (10, 0.5)}), loop(**{h: (10, 0.5)})) is None
    assert read(name, {}, {}) is None
    if name != "prefill_first_token_ms":  # the one the parent writes
        assert read(name, PARENT, PARENT) is None


def test_host_blocked_is_the_two_waits_over_the_wall():
    start = loop(ticks=100, wait_step_s=(100, 4.0),
                 prefill_first_token_s=(30, 0.3))
    end = loop(ticks=300, wait_step_s=(300, 13.0),
               prefill_first_token_s=(90, 0.39))
    # 9.09 s of waits over 200 x (50 + 0.5) ms of wall
    assert read("host_blocked_pct", start, end) == pytest.approx(90.0)
    # a loop that admitted nothing has no first-token reads
    bare = loop(ticks=300, wait_step_s=(300, 14.1))
    assert read("host_blocked_pct", loop(ticks=100, wait_step_s=(100, 4.0)),
                bare) == pytest.approx(100.0)


@pytest.mark.parametrize("name,hist_name", [
    ("device_unfed_pct", "device_unfed_s"), ("tick_stall_pct", "stall_s")])
def test_a_share_of_the_wall(name, hist_name):
    start = loop(ticks=100, **{hist_name: (29, 0.1)})
    end = loop(ticks=300, **{hist_name: (87, 0.403)})
    assert read(name, start, end) == pytest.approx(3.0)
    # nothing observed in the loop, or ever, is a share of zero
    assert read(name, start, loop(ticks=300, **{hist_name: (29, 0.1)})) == 0.0
    assert read(name, loop(ticks=100), loop(ticks=300)) == 0.0


@pytest.mark.parametrize("name", SHARES)
def test_a_share_without_a_wall_is_none(name):
    waits = {"wait_step_s": (100, 4.0)}
    assert read(name, PARENT, PARENT) is None  # no time between ticks
    assert read(name, {}, {}) is None
    # a zero divisor: the loop made no tick
    assert read(name, loop(**waits), loop(**waits)) is None
    assert spec.load_reader(name).read({"config": {}}) is None


def test_host_blocked_without_the_step_wait_is_none():
    assert read("host_blocked_pct", loop(ticks=100), loop(ticks=300)) is None


def test_loop_compiles_is_the_counters_growth():
    counted = lambda n: {**loop(), "cgx.serve.compiles": n}
    assert read("loop_compiles", counted(21.0), counted(21.0)) == 0.0
    assert read("loop_compiles", counted(21.0), counted(23.0)) == 2.0
    assert read("loop_compiles", PARENT, PARENT) is None
    assert read("loop_compiles", {}, {}) is None
    assert spec.load_reader("loop_compiles").read({"config": {}}) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_lists_it_for_the_serving_cells(name):
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, source, moves = ENTRIES[name]
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "scheduler", "moves": moves, "workloads": CELLS,
    }
    moved = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(CELLS) <= set(moved["workloads"])
    assert (spec.ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
