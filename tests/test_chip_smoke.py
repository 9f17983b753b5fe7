"""chip_smoke.py's contract, as far as a machine without a chip can check
it: it fails — non-zero exit, no result line — off-TPU and alone, the
compile cache goes where the environment says, and the CPU rehearsal of the
same code passes (slow tier)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd=_REPO, script=_SMOKE, **env):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600, env={**os.environ, **env},
    )


def test_fails_without_a_tpu_and_prints_no_result():
    proc = _run([], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not tpu" in proc.stderr


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    alone = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    proc = _run([], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_compile_cache_is_placed_from_outside(tmp_path, monkeypatch):
    import jax

    from torch_cgx_tpu.utils import entry

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert entry.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        fixed = entry.setup_compile_cache()
        assert fixed == os.path.join(_REPO, ".cgx_cache", "xla")
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.slow
def test_cpu_rehearsal_passes_and_says_cpu():
    proc = _run(["--rehearse-cpu", "--devices", "4"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    summary_line, result_line = proc.stdout.strip().splitlines()[-2:]
    # The driver's contract: exactly these keys on the last line.
    result = json.loads(result_line)
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["count"], int)
    tag = "[chip_smoke] summary "
    assert summary_line.startswith(tag)
    summary = json.loads(summary_line[len(tag):])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(summary["phases"]) == {"train", "serve"}
