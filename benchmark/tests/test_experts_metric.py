"""What PR 40 added: ``experts_matmul_ms`` reads the experts' grouped
products' device time a decode step, the same number whether XLA's
``ragged-dot-none`` or the repo's ``cgx_grouped_matmul`` implements them, and
nothing (never raising) for a program without experts."""

import pytest

from benchmark import spec

MS = 1_000_000
CELLS = ["joyai-serve-decode3k", "ling3-serve-reason128"]


def traced(kernel, steps=2):
    # Window 0..100 ms; two decode steps of three products each, one product
    # outside the window; other kernels beside them.
    ops = [[f"{kernel}.{i}", (10 + 10 * i) * MS, 3 * MS] for i in range(6)]
    ops += [[f"{kernel}.3", 150 * MS, 3 * MS],
            ["cgx_kda_update.3", 80 * MS, 5 * MS],
            ["fusion.1", 90 * MS, 5 * MS]]
    return {
        "config": {}, "loop": {"traced_decode_steps": steps},
        "trace": {"devices": {"0": ops},
                  "host": [["bench.window", 0, 100 * MS]]},
        "device_ids": [0], "counters": {"start": {}, "end": {}},
    }


def test_either_implementation_reads_the_same_number():
    read = spec.load_reader("experts_matmul_ms").read
    parents = read(traced("ragged-dot-none"))
    ours = read(traced("cgx_grouped_matmul"))
    assert parents == ours == pytest.approx(9.0)  # 6 calls x 3 ms, 2 steps
    both = traced("ragged-dot-none")
    both["trace"]["devices"]["0"].append(["cgx_grouped_matmul.7", 70 * MS,
                                          2 * MS])
    assert read(both) == pytest.approx(10.0)


def test_nothing_where_there_is_nothing():
    read = spec.load_reader("experts_matmul_ms").read
    assert read(traced("cgx_gdn_update")) is None
    assert read(traced("ragged-dot-none", steps=0)) is None
    assert read(dict(traced("ragged-dot-none"), trace=None)) is None


def test_the_metric_is_listed_for_the_expert_cells_alone():
    bench = spec.load_benchmark()
    entry = spec.find(bench["per_layer"], "experts_matmul_ms", "metric")
    assert entry == {
        "name": "experts_matmul_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "experts",
        "moves": "serve_tokens_per_s", "workloads": CELLS,
    }
    assert bench["per_layer"][-1] is entry
    for cell in bench["workloads"]:
        names = {m["name"] for m in spec.per_layer_for(bench, cell["name"])}
        assert ("experts_matmul_ms" in names) == (cell["name"] in CELLS)
