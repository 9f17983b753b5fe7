"""What an entry script settles before the library runs: where built and
compiled artifacts live, and which device it got.

Everything this program builds at run time — XLA executables (JAX's
persistent compilation cache) and the native host codec — goes under ONE
fixed, git-ignored directory of the checkout, ``.cgx_cache/``. The path is
part of the compile cache's key, so it is never a temporary name, a pid or a
time. The library itself configures no cache: :func:`setup_compile_cache` is
called by entry scripts only (``chip_smoke.py``, ``examples/``, ``bench.py``,
``tools/qbench.py``).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_root() -> Path:
    """``<checkout>/.cgx_cache`` (the directory holding ``torch_cgx_tpu/``)."""
    return Path(__file__).resolve().parents[2] / ".cgx_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its place and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set in code — a machine that comes with the variable set
    keeps its cache across calls only if the program leaves it alone.
    Otherwise the cache is ``.cgx_cache/xla`` in the checkout."""
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    path = str(cache_root() / "xla")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> Dict[str, object]:
    """The device as JAX reports it — attached to every result printed."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_accelerator(cpu_requested: bool = False) -> Dict[str, object]:
    """The device summary, or exit when JAX silently fell back to the CPU.

    With ``JAX_PLATFORMS`` unset, a host whose accelerator fails to come up
    gets the CPU backend and a warning; an entry script that did not ask
    for the CPU (``cpu_requested``, or ``JAX_PLATFORMS`` naming it) must
    not run the CPU program under the accelerator's name."""
    dev = device_summary()
    asked = cpu_requested or "cpu" in os.environ.get(
        "JAX_PLATFORMS", ""
    ).lower().split(",")
    if dev["platform"] == "cpu" and not asked:
        raise SystemExit(
            "no accelerator: JAX resolved to the CPU backend "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
            "Pass --cpu (or set JAX_PLATFORMS=cpu) to run on the CPU on "
            "purpose."
        )
    return dev


def native_build_dir() -> Optional[Path]:
    """``.cgx_cache/native`` (created), or None when the checkout is not
    writable — the native core then does not build and the numpy host
    codec serves (``runtime.native.status`` says which)."""
    path = cache_root() / "native"
    try:
        path.mkdir(parents=True, exist_ok=True)
    except OSError:
        return None
    return path
