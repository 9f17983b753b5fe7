"""Share of the loop's wall time spent in stalled ticks: growth of the sum
of the program's histogram ``cgx.serve.stall_s`` (a tick, with the caller's
time before it, over 0.5 s and over 8 times the running mean; the program
logs where each one's time went) over the wall, which is the growth of
``cgx.serve.step_s`` plus ``cgx.serve.between_steps_s``. 0 in a sound run.
Over the untraced measured loop. A program that times no time between ticks
reads nothing."""

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
    return 100.0 * grown("cgx.serve.stall_s") / wall
