"""What ``BENCHMARK.json`` names, found by name.

A cell names a configuration and a traffic mix; the configuration is the
file ``BENCHMARK.json`` gives, the mix is ``benchmark/traffic/<traffic>.json``
and names its driver (``benchmark/drivers/<driver>.py``); the limits of the
cell's ``correct`` are ``benchmark/limits/<cell>.json``; a per-layer metric's
reader is ``benchmark/layer_metrics/<name>.py`` and everything else about it
(layer, unit, source, ``moves``) is its entry in ``BENCHMARK.json``. A later
PR adds a configuration, a mix, a cell or a metric by adding files and
entries; nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SystemExit(f"benchmark: no {what} named {name!r} (known: {known})")


def merge(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


def load_cell(bench: dict, workload: str, rehearse: bool,
              root: Path = ROOT) -> dict:
    """The cell with its configuration, traffic and limits files read. With
    ``rehearse`` each file's ``rehearsal`` block (tiny sizes for the CPU,
    and the limits read at them) is laid over it; the real path never reads
    that block."""
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "configuration")
    data = Path(root) / "benchmark"
    loaded = {
        "config": json.loads((Path(root) / entry["file"]).read_text()),
        "traffic": json.loads(
            (data / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (data / "limits" / f"{cell['name']}.json").read_text()),
    }
    for key, value in loaded.items():
        tiny = value.pop("rehearsal", {})
        if rehearse:
            loaded[key] = merge(value, tiny)
    loaded["config"]["limits"] = loaded.pop("limits")["limits"]
    return dict(loaded, cell=cell)


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in reported


def end_to_end_for(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_for(bench: dict, workload: str) -> list:
    reported = {m["name"] for m in end_to_end_for(bench, workload)}
    return [m for m in bench["per_layer"] if _applies(m, workload, reported)]


def load_module(kind: str, name: str, root: Path = ROOT):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = Path(root) / "benchmark" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"benchmark: benchmark/{kind}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, root: Path = ROOT):
    """The reader of the per-layer metric ``name``. A quantity split by the
    end-to-end metric it moves (``device_idle_pct.serve``,
    ``device_idle_pct.train``) is read by the one file of its stem unless a
    file of the whole name exists."""
    own = Path(root) / "benchmark" / "layer_metrics" / f"{name}.py"
    stem = name if own.is_file() else name.split(".", 1)[0]
    return load_module("layer_metrics", stem, root)


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of the device; an unknown device is an error."""
    table = json.loads(
        (Path(root) / "benchmark" / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})"
        )
    return table[device_kind]
