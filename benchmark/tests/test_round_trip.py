"""A later PR adds a configuration, a traffic mix, a cell's limits and a
per-layer metric by adding files and entries only: shown by adding a dummy
of each to a copy of the benchmark's data, running the new cell, and taking
them away again."""

import hashlib
import json
import shutil

from benchmark import run as harness, spec


def digest(root):
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
    }


def test_dummy_configuration_mix_and_metric_round_trip(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(
        spec.ROOT / "benchmark", tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = digest(tmp_path / "benchmark")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cells_before = [w["name"] for w in bench["workloads"]]

    # -- what the later PR adds: four files and four entries - ---------------
    data = tmp_path / "benchmark"
    config = json.loads(
        (data / "configs" / "gpt2-large-serve-kv8.json").read_text())
    config["name"] = "dummy-config"
    (data / "configs" / "dummy-config.json").write_text(json.dumps(config))
    mix = json.loads((data / "traffic" / "decode.json").read_text())
    mix["rehearsal"]["clients"] = 3
    (data / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    shutil.copy(data / "limits" / "gpt2l-serve-decode.json",
                data / "limits" / "dummy.cell.json")
    (data / "layer_metrics" / "dummy_metric.py").write_text(
        'def read(ctx):\n'
        '    return float(ctx["traffic"]["clients"])\n')
    bench["configs"].append({
        "name": "dummy-config", "source": config["source"],
        "file": "benchmark/configs/dummy-config.json",
        "reduced": config["reduced"], "why": "round-trip test"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "round-trip test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"].startswith("serve_"):
            m["workloads"].append("dummy.cell")
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # No file that was there has changed, and the new cell runs.
    after = digest(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
    result = harness.run(
        ["--workload", "dummy.cell", "--seed", "5", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu", "1"], root=tmp_path)
    assert result["metrics"]["dummy_metric"] == {"value": 3.0, "unit": "count"}
    assert "decode_step_ms" not in result["metrics"]  # lists other cells
    assert result["correct"], result["checks"]
    end_to_end = harness.run(
        ["--workload", "dummy.cell", "--seed", "5", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu", "1"], root=tmp_path)
    assert "serve_tokens_per_s" in end_to_end["metrics"]

    # -- and takes away again -----------------------------------------------
    for added in set(after) - set(before):
        (tmp_path / "benchmark" / added).unlink()
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    assert digest(tmp_path / "benchmark") == before
    bench = spec.load_benchmark(tmp_path)
    assert [w["name"] for w in bench["workloads"]] == cells_before
    for cell in cells_before:
        spec.load_cell(bench, cell, rehearse=True, root=tmp_path)
