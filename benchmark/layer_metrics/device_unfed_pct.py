"""Share of the loop's wall time in which the host had left the device
nothing to run, by the host's own count: growth of the sum of the program's
histogram ``cgx.serve.device_unfed_s`` (from a blocking read that leaves
nothing dispatched and unread to the return of the next ``prefill_pages``,
``commit`` or ``decode_step`` call) over the wall, which is the growth of
``cgx.serve.step_s`` plus ``cgx.serve.between_steps_s``. The profiler's
``device_idle_pct.serve`` is its yardstick. 0 where every step was queued
ahead. Over the untraced measured loop. A program that times no time between
ticks reads nothing."""

WALL = ("cgx.serve.step_s", "cgx.serve.between_steps_s")


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    if any(f"{name}.sum" not in end for name in WALL):
        return None

    def grown(name):
        return end.get(f"{name}.sum", 0.0) - start.get(f"{name}.sum", 0.0)

    wall = sum(grown(name) for name in WALL)
    if wall <= 0:
        return None
    return 100.0 * grown("cgx.serve.device_unfed_s") / wall
