"""The five readers PR 50 added over the device's account: each reduces the
program's ``cgx.serve.device.*`` histograms and counters over the measured
loop, and returns nothing (never raises) for a program that does not write
them, as the parent of PR 50 does not, or for a loop that closed no clean
interval of the class."""

import pytest

from benchmark import spec

SERVING = {"gpt2l-serve-decode", "gpt2l-serve-prefill", "joyai-serve-decode3k",
           "granite-serve-chat64", "olmoh-serve-chat96",
           "ling3-serve-reason128", "smallthinker-serve-mix8k",
           "trinity-serve-agent64", "xing4-serve-doc16k"}
# name -> (unit, better, layer, moves)
ENTRIES = {
    "step_device_ms": ("ms", "lower", "device", "serve_tokens_per_s"),
    "commit_device_ms": ("ms", "lower", "device", "serve_tokens_per_s"),
    "prefill_device_ms": ("ms", "lower", "prefill", "serve_ttft_p90_ms"),
    "prefill_device_us_per_token": ("us", "lower", "prefill",
                                    "serve_ttft_p90_ms"),
    "device_account_pct": ("%", "higher", "scheduler", "serve_tokens_per_s"),
}


def read(name, start, end):
    ctx = {"config": {}, "counters": {"start": start, "end": end},
           "trace": None, "loop": {}, "peaks": None, "device_ids": [0]}
    return spec.load_reader(name).read(ctx)


def hist(name, count, total):
    return {f"cgx.serve.{name}.count": float(count),
            f"cgx.serve.{name}.sum": float(total)}


def loop(ticks, steps=(0, 0.0), both=(0, 0.0), calls=0.0, prefills=(0, 0.0),
         tokens=0.0, accounted=0.0):
    """A loop's counters after ``ticks`` ticks of 50 ms with 0.5 ms between
    them: the wall's two histograms and the device's account."""
    found = {**hist("step_s", ticks, ticks * 0.05),
             **hist("between_steps_s", ticks, ticks * 0.0005),
             "cgx.serve.device.accounted_s": accounted,
             "cgx.serve.device.commit_calls": calls,
             "cgx.serve.device.prefill_tokens": tokens}
    for name, (count, total) in (("device.step_s", steps),
                                 ("device.commit_step_s", both),
                                 ("device.prefill_s", prefills)):
        if count:
            found.update(hist(name, count, total))
    return found


# The recorded pair: 200 ticks of the loop between them, 120 pure steps of
# 48 ms, 40 intervals of a step and 50 commit calls (0.4 ms a call), 20
# prefills of 8,192 and 16,384 padded tokens, 9.595 s accounted of 10.1.
START = loop(100, steps=(60, 2.88), both=(20, 0.97), calls=25.0,
             prefills=(10, 3.0), tokens=122880.0, accounted=4.9)
END = loop(300, steps=(180, 8.64), both=(60, 2.91), calls=75.0,
           prefills=(30, 9.3), tokens=368640.0, accounted=14.495)
# What the parent's program leaves: the wall, and no account.
PARENT = {**hist("step_s", 300, 15.0), **hist("between_steps_s", 300, 0.15),
          **hist("prefill_s", 30, 9.9)}


def test_step_device_ms_is_the_pure_intervals_mean():
    assert read("step_device_ms", START, END) == pytest.approx(48.0)
    # a histogram first observed inside the loop has no entry at its start
    assert read("step_device_ms", {}, hist("device.step_s", 4, 0.2)
                ) == pytest.approx(50.0)


def test_commit_device_ms_is_what_a_call_adds_to_a_step():
    # 48.5 ms an interval of both, 48 a step alone, 1.25 calls an interval
    assert read("commit_device_ms", START, END) == pytest.approx(0.4)
    no_steps = loop(300, both=(60, 2.91), calls=75.0)
    assert read("commit_device_ms", START, no_steps) is None
    no_commits = loop(300, steps=(180, 8.64), both=(20, 0.97), calls=25.0)
    assert read("commit_device_ms", START, no_commits) is None


def test_prefill_device_ms_and_its_length_normalised_figure():
    assert read("prefill_device_ms", START, END) == pytest.approx(315.0)
    # 6.3 s over 245,760 padded tokens, whatever the mix of the two lengths
    assert read("prefill_device_us_per_token", START, END) == pytest.approx(
        6.3e6 / 245760)
    late = loop(300, steps=(180, 8.64), prefills=(10, 3.0), tokens=122880.0)
    assert read("prefill_device_ms", START, late) is None
    assert read("prefill_device_us_per_token", START, late) is None


def test_device_account_pct_is_the_accounted_seconds_over_the_wall():
    assert read("device_account_pct", START, END) == pytest.approx(95.0)
    assert read("device_account_pct", START, START) is None  # no tick
    quiet = loop(300, accounted=4.9)
    assert read("device_account_pct", START, quiet) == 0.0


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_program_without_the_account_reads_nothing(name):
    assert read(name, PARENT, PARENT) is None
    assert read(name, {}, PARENT) is None
    assert read(name, {}, {}) is None
    assert spec.load_reader(name).read({"config": {}}) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_lists_it_for_the_serving_cells(name):
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, layer, moves = ENTRIES[name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span", "layer": layer, "moves": moves,
    }
    assert SERVING <= set(entry["workloads"])  # these are among them
    moved = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert (spec.ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
