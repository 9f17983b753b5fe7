"""From a profiler trace to numbers: the reduction every PR shares.

:func:`load_profile` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(with nothing but ``jax.profiler.ProfileData``) into a small plain
structure::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},   # op line
     "host": [[name, start_ns, dur_ns], ...]}                  # bench.* spans

and the functions below reduce that structure. The tests hold them to a
small recorded trace kept beside them in the same form.

What was seen in a real trace (TPU v5 lite, jax 0.9.0; looked at by hand,
PR 24) is written beside :data:`DEVICE_PLANE` and :data:`OP_LINE`.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

# Planes are named "/device:TPU:<n>"; each has the lines "XLA Modules" (one
# event per executed program), "XLA Ops" (one event per executed HLO
# instruction), "Async XLA Ops" and two empty ones. An op event's name is
# the instruction's whole HLO text, "%fusion.12 = f32[...] fusion(...)";
# :func:`op_name` keeps the instruction's own name. A Pallas kernel's
# instruction is named by its ``pallas_call(name=...)``:
# "%cgx_dequantize_flat.73 = f32[327680,128] custom-call(...)". The host's
# threads are lines of the plane "/host:CPU"; ``TraceAnnotation`` spans of
# the benchmark's loop are on the line "python3", on the same clock.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

# Collectives by the HLO spelling of the opcode. The trace spells some with
# underscores: a four-chip training step (PR 24) shows "all-gather.12" and
# "all_to_all.7" side by side, so :func:`is_collective` reads "_" as "-".
COLLECTIVE_PREFIXES = (
    "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute", "collective-broadcast",
)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_profile(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[m.group(1)] = [
                        [op_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)]
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        )
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def window(trace: dict):
    """(start_ns, end_ns) of the benchmark's traced window span."""
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    _, start, dur = max(spans, key=lambda e: e[2])
    return start, start + dur


def clipped(events, t0: int, t1: int):
    """Events cut to ``[t0, t1]``, as (name, start, end), in start order."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b))
    out.sort(key=lambda e: e[1])
    return out


def busy_and_gaps(events, t0: int, t1: int):
    """Seconds in which some op ran (the union of the intervals), and the
    idle gaps as (start_ns, end_ns), the window's edges included."""
    busy, gaps, cursor = 0, [], t0
    for _, a, b in clipped(events, t0, t1):
        if a > cursor:
            gaps.append((cursor, a))
        if b > cursor:
            busy += b - max(a, cursor)
            cursor = b
    if t1 > cursor:
        gaps.append((cursor, t1))
    return busy / 1e9, gaps


def seconds_where(events, t0: int, t1: int, match) -> tuple:
    """(total seconds, count) of the events whose name ``match`` accepts."""
    total, count = 0, 0
    for name, a, b in clipped(events, t0, t1):
        if match(name):
            total += b - a
            count += 1
    return total / 1e9, count


def is_collective(name: str) -> bool:
    return name.replace("_", "-").startswith(COLLECTIVE_PREFIXES)


def is_codec_kernel(name: str) -> bool:
    return name.startswith("cgx_")


def family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the numbering changes with every
    compile, the family does not."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(events, t0: int, t1: int, k: int = 10):
    """The ``k`` op families with most device time: [[name, seconds]]."""
    total = defaultdict(int)
    for name, a, b in clipped(events, t0, t1):
        total[family(name)] += b - a
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def label_at(host, t: int) -> str:
    """The innermost ``bench.*`` span (the window's own aside) that covers
    instant ``t``; what the host was doing then."""
    best = None
    for name, start, dur in host:
        if name != WINDOW_SPAN and start <= t < start + dur:
            if best is None or dur < best[1]:
                best = (name, dur)
    return best[0][len(HOST_PREFIX):] if best else "outside-any-span"


def idle_by_label(gaps, host, k: int = 10):
    """Idle seconds by what the host was doing at each gap's middle:
    [[label, seconds]], largest first."""
    total = defaultdict(int)
    for a, b in gaps:
        total[label_at(host, (a + b) // 2)] += b - a
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def device_summary(trace: dict, device_ids) -> dict:
    """``busy_s`` averaged over the chips used, ``window_s``, and the
    breakdown of the first chip."""
    t0, t1 = window(trace)
    busy = []
    for d in device_ids:
        seconds, _ = busy_and_gaps(trace["devices"][str(d)], t0, t1)
        busy.append(seconds)
    first = trace["devices"][str(device_ids[0])]
    _, gaps = busy_and_gaps(first, t0, t1)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (t1 - t0) / 1e9,
        "breakdown": {
            "device_ops": top_ops(first, t0, t1),
            "idle_gaps": idle_by_label(gaps, trace["host"]),
        },
    }
