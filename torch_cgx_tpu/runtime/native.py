"""ctypes bindings for the native C++ core (``csrc/cgx_core.cpp``).

The shared library is built on demand with ``g++`` (this image has no
pybind11; the C ABI + ctypes replaces the reference's pybind11 module,
/root/reference/setup.py). If no compiler is available the callers
(:mod:`..ops.codec_host`, :mod:`.executor`) fall back to numpy/Python — the
framework stays fully functional, just slower on the host staging path;
:func:`status` says which of the two is live.

The build is keyed on what it is built FROM — the source bytes and the
compiler flags — and lands in the checkout's git-ignored cache directory
(``utils.entry.native_build_dir``), never beside the source: a library
built on another machine cannot ride along in a copied tree and be
reused for being newer than the source. The flags name no host CPU
(no ``-march=native``), so every machine builds the same program.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sysconfig
import threading
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

from ..utils.entry import native_build_dir

_SRC = Path(__file__).parent / "csrc" / "cgx_core.cpp"
_FLAGS = (
    "-O3", "-ffp-contract=off", "-shared", "-fPIC", "-std=c++17", "-pthread",
)
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_WHY = "not tried yet"  # why numpy serves, once _load has run


def _lib_path() -> Optional[Path]:
    out_dir = native_build_dir()
    if out_dir is None:
        return None
    key = hashlib.sha256(
        _SRC.read_bytes() + "\0".join(_FLAGS).encode()
    ).hexdigest()[:16]
    tag = sysconfig.get_config_var("SOABI") or "generic"
    return out_dir / f"_cgx_core.{key}.{tag}.so"


def build(force: bool = False) -> Optional[Path]:
    """Compile the core with g++; returns the .so path or None."""
    global _WHY
    out = _lib_path()
    if out is None:
        _WHY = "build directory not writable"
        return None
    if out.exists() and not force:
        return out
    # Unique temporary + rename: concurrent ranks may build at once.
    tmp = out.with_suffix(f".tmp{os.getpid()}")
    cmd = ["g++", *_FLAGS, str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError) as e:
        _WHY = f"g++ build failed: {type(e).__name__}"
        return None
    return out


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED, _WHY
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("CGX_DISABLE_NATIVE", "0") == "1":
            _WHY = "CGX_DISABLE_NATIVE=1"
            return None
        path = build()
        if path is None:
            return None
        lib = ctypes.CDLL(str(path))
        u32p = ctypes.POINTER(ctypes.c_uint32)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.cgx_packed_words.restype = ctypes.c_int64
        lib.cgx_packed_words.argtypes = [ctypes.c_int64, ctypes.c_int32]
        lib.cgx_num_buckets.restype = ctypes.c_int64
        lib.cgx_num_buckets.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.cgx_quantize_f32.restype = None
        lib.cgx_quantize_f32.argtypes = [
            f32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, u32p, f32p,
        ]
        lib.cgx_dequantize_f32.restype = None
        lib.cgx_dequantize_f32.argtypes = [
            u32p, f32p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, f32p,
            ctypes.c_int32,
        ]
        lib.cgx_add_f32.restype = None
        lib.cgx_add_f32.argtypes = [f32p, f32p, ctypes.c_int64]
        lib.cgx_executor_create.restype = ctypes.c_void_p
        lib.cgx_executor_create.argtypes = [ctypes.c_int32]
        lib.cgx_executor_destroy.restype = None
        lib.cgx_executor_destroy.argtypes = [ctypes.c_void_p]
        lib.cgx_submit_quantize_f32.restype = ctypes.c_uint64
        lib.cgx_submit_quantize_f32.argtypes = [
            ctypes.c_void_p, f32p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int64, u32p, f32p,
        ]
        lib.cgx_submit_dequantize_f32.restype = ctypes.c_uint64
        lib.cgx_submit_dequantize_f32.argtypes = [
            ctypes.c_void_p, u32p, f32p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64, f32p, ctypes.c_int32,
        ]
        lib.cgx_wait.restype = ctypes.c_int32
        lib.cgx_wait.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.cgx_test.restype = ctypes.c_int32
        lib.cgx_test.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def status() -> Dict[str, str]:
    """Which host codec is live: ``{"host_codec": "native", "lib": path}``
    or ``{"host_codec": "numpy", "why": reason}`` (loads on first call)."""
    lib = _load()
    if lib is not None:
        return {"host_codec": "native", "lib": lib._name}
    return {"host_codec": "numpy", "why": _WHY}


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def quantize_f32(
    x: np.ndarray, bits: int, bucket_size: int
) -> Tuple[np.ndarray, np.ndarray]:
    """f32[n] -> (packed u32[words], meta f32[nb, 2] pairs); deterministic."""
    lib = _load()
    assert lib is not None
    x = np.ascontiguousarray(x, dtype=np.float32)
    n = x.shape[0]
    nb = int(lib.cgx_num_buckets(n, bucket_size))
    words = int(lib.cgx_packed_words(nb * bucket_size, bits))
    packed = np.empty(words, np.uint32)
    meta = np.empty((nb, 2), np.float32)
    lib.cgx_quantize_f32(_f32p(x), n, bits, bucket_size, _u32p(packed),
                         _f32p(meta))
    return packed, meta


def dequantize_f32(
    packed: np.ndarray,
    meta: np.ndarray,
    bits: int,
    bucket_size: int,
    n: int,
    add_to: Optional[np.ndarray] = None,
) -> np.ndarray:
    lib = _load()
    assert lib is not None
    packed = np.ascontiguousarray(packed, dtype=np.uint32)
    meta = np.ascontiguousarray(meta, dtype=np.float32)
    if add_to is not None:
        out = np.ascontiguousarray(add_to, dtype=np.float32)
        add = 1
    else:
        out = np.empty(n, np.float32)
        add = 0
    lib.cgx_dequantize_f32(_u32p(packed), _f32p(meta), bits, bucket_size, n,
                           _f32p(out), add)
    return out


def add_f32(src: np.ndarray, acc: np.ndarray) -> np.ndarray:
    """acc += src in the native core; returns acc."""
    lib = _load()
    assert lib is not None
    lib.cgx_add_f32(_f32p(src), _f32p(acc), src.shape[0])
    return acc


class NativeExecutor:
    """Handle to a C++ worker-thread pool with future-style job ids —
    the rebuilt analogue of the reference's background runLoop
    (ProcessGroupCGX.cc:300-339)."""

    def __init__(self, nthreads: int = 1):
        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._handle = lib.cgx_executor_create(nthreads)
        # Jobs reference numpy buffers; keep them alive until waited on.
        self._pins: dict = {}

    def close(self) -> None:
        if self._handle is not None:
            self._lib.cgx_executor_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass

    def submit_quantize(self, x, bits, bucket_size, packed, meta) -> int:
        jid = int(
            self._lib.cgx_submit_quantize_f32(
                self._handle, _f32p(x), x.shape[0], bits, bucket_size,
                _u32p(packed), _f32p(meta),
            )
        )
        self._pins[jid] = (x, packed, meta)
        return jid

    def submit_dequantize(self, packed, meta, bits, bucket_size, n, out,
                          add: bool) -> int:
        jid = int(
            self._lib.cgx_submit_dequantize_f32(
                self._handle, _u32p(packed), _f32p(meta), bits, bucket_size,
                n, _f32p(out), 1 if add else 0,
            )
        )
        self._pins[jid] = (packed, meta, out)
        return jid

    def wait(self, jid: int) -> None:
        st = int(self._lib.cgx_wait(self._handle, jid))
        self._pins.pop(jid, None)
        if st < 0:
            raise RuntimeError("native job failed")

    def test(self, jid: int) -> bool:
        """Peek at completion; buffers stay pinned until :meth:`wait`."""
        st = int(self._lib.cgx_test(self._handle, jid))
        if st < 0:
            raise RuntimeError("native job failed")
        return st != 0
