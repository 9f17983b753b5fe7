"""Look at a profiler trace by hand before trusting a reduction of it.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace
    python3 benchmark/trace_list.py .cgx_cache/bench_trace/<cell> [<out.json>]

Prints every plane with its lines and event counts and, for each device's
op line, the op families with most time. With a second argument it also
writes a small slice in the reduced form the tests keep
(``benchmark/tests/data``): of a serving trace one decode step with the
30 ms before it; of a training trace the stretch of the first chip's op
line from its first collective to the first collective of another family,
with some hundred ops on either side.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import trace_reduce  # noqa: E402


def within(trace: dict, lo: int, hi: int, devices=None) -> dict:
    """The part of ``trace`` inside ``[lo, hi]``, which becomes its window."""
    return {
        "devices": {d: [e for e in ev if lo <= e[1] and e[1] + e[2] <= hi]
                    for d, ev in trace["devices"].items()
                    if devices is None or d in devices},
        "host": [[trace_reduce.WINDOW_SPAN, lo, hi - lo]] + [
            e for e in trace["host"]
            if e[0] != trace_reduce.WINDOW_SPAN and e[1] < hi
            and e[1] + e[2] > lo],
    }


def small_slice(trace: dict, margin: int = 100) -> dict:
    decode = next((e for e in trace["host"] if e[0] == "bench.decode"), None)
    if decode is not None:  # one decode step with what came just before it
        return within(trace, decode[1] - 30_000_000,
                      decode[1] + decode[2] + 2_000_000)
    first = min(trace["devices"], key=int)
    t0, t1 = trace_reduce.window(trace)
    events = [e for e in trace["devices"][first] if t0 <= e[1] < t1]
    at = [i for i, e in enumerate(events) if trace_reduce.is_collective(e[0])]
    other = next(i for i in at if trace_reduce.family(events[i][0])
                 != trace_reduce.family(events[at[0]][0]))
    lo = events[max(at[0] - margin, 0)]
    hi = events[min(other + margin, len(events) - 1)]
    return within(trace, lo[1], hi[1] + hi[2], devices=[first])


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(argv[0])
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events[:2000]})[:12]
            print(f"  line {line.name!r}: {len(events)} events, e.g. {names}")
    trace = trace_reduce.load_profile(argv[0])
    t0, t1 = trace_reduce.window(trace)
    print(f"window {(t1 - t0) / 1e9:.3f} s; host spans "
          f"{sorted({e[0] for e in trace['host']})}")
    for dev, events in trace["devices"].items():
        busy, gaps = trace_reduce.busy_and_gaps(events, t0, t1)
        print(f"device {dev}: {len(events)} ops, busy {busy:.3f} s, "
              f"{len(gaps)} gaps")
        for name, s in trace_reduce.top_ops(events, t0, t1, 25):
            print(f"    {s:9.4f} s  {name}")
        print("  idle by host span:",
              trace_reduce.idle_by_label(gaps, trace["host"]))
    if len(argv) > 1:
        small = small_slice(trace)
        Path(argv[1]).write_text(json.dumps(small, separators=(",", ":")))
        print(f"wrote {argv[1]}: "
              f"{sum(len(v) for v in small['devices'].values())} ops")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
