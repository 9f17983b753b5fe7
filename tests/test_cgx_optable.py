"""``tools/cgx_optable.py`` (ISSUE 50): the op line of a kept trace cut by its
module line, on events made by hand in the form the loader returns them
(name, start ns, duration ns); the loader itself needs a chip's trace."""

from __future__ import annotations

import importlib.util
import os

import pytest

_TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "cgx_optable.py")
spec = importlib.util.spec_from_file_location("cgx_optable", _TOOL)
optable = importlib.util.module_from_spec(spec)
spec.loader.exec_module(optable)

MS = 1_000_000
# Two steps back to back, then an idle device, a long prefill and a short
# one (two compiled variants), a commit and a step behind it.
MODS = [
    ("jit_decode_step(11)", 0, 10 * MS),
    ("jit_decode_step(11)", 10 * MS, 12 * MS),
    ("jit_prefill_pages(21)", 40 * MS, 30 * MS),
    ("jit_prefill_pages(22)", 70 * MS, 6 * MS),
    ("jit_commit(31)", 76 * MS, 1 * MS),
    ("jit_decode_step(11)", 77 * MS, 11 * MS),
]
OPS = [
    ("%fusion.3 = f32[8]{0} fusion(%p0)", 0, 4 * MS),
    ("%cgx_dequantize_flat.7 = bf16[8,128] custom-call(%p1)", 4 * MS, 5 * MS),
    ("%fusion.9 = f32[8]{0} fusion(%p0)", 10 * MS, 6 * MS),
    ("%cgx_dequantize_flat.7 = bf16[8,128] custom-call(%p1)", 16 * MS,
     5 * MS),
    ("%convolution.2 = f32[8]{0} convolution(%p0)", 41 * MS, 20 * MS),
    ("%fusion.3 = f32[8]{0} fusion(%p0)", 78 * MS, 9 * MS),
]
HOST = [
    ("cgx.serve.dispatch.step", -3 * MS, 2 * MS),
    ("cgx.serve.prefill.forward", 37 * MS, int(3.2 * MS)),
    ("cgx.serve.prefill.forward", 42 * MS, 2 * MS),
    ("cgx.serve.dispatch.commit", 45 * MS, 1 * MS),
    ("cgx.serve.dispatch.step", 46 * MS, 2 * MS),
]


def test_a_programs_calls_and_its_ops_a_call():
    found = optable.table(MODS, OPS)
    step = found["jit_decode_step"]
    assert step["calls"] == 3 and step["ms_mean"] == pytest.approx(11.0)
    assert step["ms_median"] == pytest.approx(11.0)
    assert (step["ms_min"], step["ms_max"]) == (10.0, 12.0)
    assert step["ops_ms_per_call"] == {
        "fusion": pytest.approx(19 / 3),
        "cgx_dequantize_flat": pytest.approx(10 / 3)}
    assert step["kernels_ms_and_calls_per_call"] == {
        "cgx_dequantize_flat": [pytest.approx(10 / 3), pytest.approx(2 / 3)]}
    prefill = found["jit_prefill_pages"]
    assert prefill["calls"] == 2 and prefill["ms_mean"] == pytest.approx(18.0)
    assert {v: s["ms_mean"] for v, s in prefill["variants"].items()} == {
        "jit_prefill_pages(21)": 30.0, "jit_prefill_pages(22)": 6.0}
    assert step["variants"]["jit_decode_step(11)"]["calls_ms"] == [
        10.0, 12.0, 11.0]
    assert prefill["ops_ms_per_call"] == {"convolution": 10.0}
    assert found["jit_commit"]["calls"] == 1


def test_the_programs_line_names_each_variant_of_a_program_with_two():
    lines = optable.program_lines(optable.table(MODS, OPS))
    assert lines[0] == ("jit_prefill_pages: 2 calls, mean 18.000 ms, "
                        "median 18.000")
    assert lines[1:3] == [
        "  jit_prefill_pages(21): 1 calls, mean 30.000 ms, median 30.000",
        "  jit_prefill_pages(22): 1 calls, mean 6.000 ms, median 6.000"]
    assert lines[3].startswith("jit_decode_step: 3 calls, mean 11.000 ms")
    assert not any(line.startswith("  jit_decode_step") for line in lines)


def test_the_dispatch_line_places_an_idle_devices_start_by_the_host_span():
    """The first step and the long prefill found the device idle; the
    prefill began 3 ms after its host span's start, 0.2 ms before its
    return. The programs queued behind them say nothing of the stamps."""
    lines = optable.dispatch_lines(MODS, HOST)
    assert lines == [
        "jit_decode_step: 1 calls on an idle device began 3.000 ms (median) "
        "after the host span's start, 1.000 ms after its end",
        "jit_prefill_pages: 1 calls on an idle device began 3.000 ms "
        "(median) after the host span's start, -0.200 ms after its end",
    ]
