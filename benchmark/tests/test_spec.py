"""BENCHMARK.json against its contract, and the files it names."""

import json
import re

import pytest

from benchmark import bytes as byte_counts
from benchmark import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def all_metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units_use_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for m in all_metrics(bench):
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_entries_have_just_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_configs_and_metrics_hang_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in all_metrics(bench):
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for cell in cells:
        reported = {m["name"] for m in spec.end_to_end_for(bench, cell)}
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.per_layer_for(bench, cell)
        assert layer
        assert all(m["moves"] in reported for m in layer)


def test_files_named_by_the_benchmark_exist_and_agree(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        config = json.loads((spec.ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        assert config["layer_norm_epsilon"] == 1e-5  # as published
    for w in bench["workloads"]:
        for rehearse in (False, True):
            loaded = spec.load_cell(bench, w["name"], rehearse=rehearse)
            assert "rehearsal" not in loaded["config"]
            assert all(isinstance(v, (int, float))
                       for v in loaded["config"]["limits"].values())
        spec.load_module("drivers", loaded["traffic"]["driver"])
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]).read)
    # a split quantity is read by the file of its stem
    assert spec.load_reader("device_idle_pct.serve").__file__.endswith(
        "device_idle_pct.py")


def test_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        spec.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


def test_kv_dequant_bytes_against_hand_worked_values():
    # 32 lanes x 1024 tokens x 1280 values = 41,943,040 values.
    # 8 bits: 41,943,040 B packed; 81,920 buckets of 512 x 8 B = 655,360 B;
    # float32 out: 167,772,160 B.
    got = byte_counts.kv_dequant_call_bytes(32, 1024, 1280, 8, 512)
    assert got == {"in": 41_943_040 + 655_360, "out": 167_772_160,
                   "total": 210_370_560}
    # 4 bits halves the packed words only.
    got = byte_counts.kv_dequant_call_bytes(32, 1024, 1280, 4, 512)
    assert got["in"] == 20_971_520 + 655_360
    # one lane, one page of 64 tokens at width 128, buckets of 512:
    # 8192 values -> 8192 B + 16 buckets x 8 B in, 32768 B out.
    got = byte_counts.kv_dequant_call_bytes(1, 64, 128, 8, 512)
    assert got == {"in": 8192 + 128, "out": 32768, "total": 41088}
