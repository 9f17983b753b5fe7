"""Tracing / profiling spans.

The reference's only tracing is c10d ``profilingTitle`` strings surfaced to
torch.profiler (SURVEY.md §5.1). The TPU-native equivalent is
:func:`trace_span`, the package's one way to time host-side work: a
``jax.profiler.TraceAnnotation`` on the profiler's host plane (the same
clock as the device's op line), one histogram in the metrics registry, and
a record in the cross-rank timeline. :func:`named_scope` names regions of
traced (jitted) code; :func:`profile_capture` writes a device profile.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax

from ..observability import timeline
from .logging import get_logger, metrics

log = get_logger()


@contextlib.contextmanager
def trace_span(name: str, *, hist: str | None = None, **fields):
    """The package's one host-side span. Three sinks, one pair of clock
    reads:

    * ``jax.profiler.TraceAnnotation("cgx." + name, **fields)`` — an event
      on the host plane of the profiler's trace, on the same clock as the
      device's op line (free while no profiler session runs);
    * ONE histogram of the duration in seconds: ``hist``, or
      ``cgx.<name>_s``;
    * with ``CGX_METRICS_DIR`` set, a record under ``name`` with
      ``fields`` in the cross-rank timeline (``observability.timeline``).

    The sample is recorded in a ``finally`` so a span whose body raises
    still lands in the registry — failed operations are the interesting
    ones; ``span.<name>.errors`` counts them. The span never waits for
    the device and copies nothing from it: it times what the body itself
    blocks on.
    """
    start = time.perf_counter()
    ok = True
    try:
        with jax.profiler.TraceAnnotation("cgx." + name, **fields):
            yield
    except BaseException:
        ok = False
        raise
    finally:
        observe_span(name, start, hist=hist, ok=ok, **fields)


def observe_span(name: str, start: float, *, hist: str | None = None,
                 ok: bool = True, end: float | None = None,
                 **fields) -> None:
    """Close a span that began at ``start`` (a ``time.perf_counter()``
    reading) and ends now, or at the reading ``end`` the caller took:
    :func:`trace_span`'s histogram and timeline record, for a
    span whose two ends lie in different calls and so in no one ``with``
    block (``serve.prefill.local``: the dispatch in one phase of a tick,
    the read of its first token in another; the device's intervals,
    ``serve.device.*``: from one read's return to the next's). It has no
    annotation in the profiler's trace: a thread's annotations nest, and
    such a span overlaps its neighbours."""
    dur = (time.perf_counter() if end is None else end) - start
    if not ok:
        metrics.add(f"span.{name}.errors", 1.0)
    metrics.observe(hist or f"cgx.{name}_s", dur)
    if timeline.enabled():
        timeline.record(name, timeline.CAT_SPAN, start, dur, ok=ok, **fields)


class GcPauses:
    """``gc.callbacks`` hook: the pauses of full (generation 2)
    collections, each under a ``cgx.host.gc`` annotation so that it shows
    in a profiler trace beside the device's idle gap it causes.

    A collection starts at whatever allocation tripped it, possibly one
    made under the metric registry's own (non-reentrant) lock, so the
    callback only stamps; :meth:`publish`, called from a safe point (the
    scheduler's tick), moves the pauses into ``cgx.serve.host_gc_s``."""

    def __init__(self):
        self._span = None
        self._start = 0.0
        self._pending: list = []

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._span = jax.profiler.TraceAnnotation("cgx.host.gc")
            self._span.__enter__()
            self._start = time.perf_counter()
        elif self._span is not None:
            self._pending.append(time.perf_counter() - self._start)
            self._span.__exit__(None, None, None)
            self._span = None

    def publish(self) -> None:
        while self._pending:
            metrics.observe("cgx.serve.host_gc_s", self._pending.pop())


def install_gc_hook() -> GcPauses:
    """The process's :class:`GcPauses`, installed on first call; later
    calls return the one already in ``gc.callbacks``."""
    for cb in gc.callbacks:
        if isinstance(cb, GcPauses):
            return cb
    hook = GcPauses()
    gc.callbacks.append(hook)
    return hook


_compile_listener_installed = False


def install_compile_listener() -> None:
    """Count the process's backend compiles, as JAX reports them, into
    ``cgx.serve.compiles`` and the histogram ``cgx.serve.compile_s``: a
    ``jax.monitoring`` duration listener, registered on first call (JAX
    keeps listeners for the life of the process). The event ends every
    program build, a persistent-cache retrieval included, and fires on the
    thread that built it, so a tick that grew the count is the tick that
    compiled."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True

    def on_duration(event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            metrics.add("cgx.serve.compiles")
            metrics.observe("cgx.serve.compile_s", duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def named_scope(name: str):
    """Annotation for traced (jitted) code regions — shows up in the XLA HLO
    and device profile."""
    return jax.named_scope(name)


@contextlib.contextmanager
def profile_capture(subdir: str = "cgx"):
    """Write a device profile (Perfetto/XPlane, viewable in TensorBoard or
    ui.perfetto.dev) for the enclosed region when ``CGX_TRACE_DIR`` is set;
    a no-op otherwise. Wrap a few training steps:

        with profile_capture("step100"):
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, batch, i)
            jax.block_until_ready(params)
    """
    import os

    base = os.environ.get("CGX_TRACE_DIR")
    if not base:
        yield
        return
    path = os.path.join(base, subdir)
    # A nonexistent CGX_TRACE_DIR used to make jax.profiler.trace fail
    # (or silently drop the capture, backend-dependent) — create it and
    # say where the capture went.
    os.makedirs(path, exist_ok=True)
    log.info("cgx: writing device profile capture to %s", path)
    with jax.profiler.trace(path):
        yield
