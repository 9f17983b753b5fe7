"""Share of the loop's wall time that the program's own account of the device
covers: growth of its counter ``cgx.serve.device.accounted_s`` (the seconds
of every sound interval, whatever programs it held) over the wall, which is
the growth of ``cgx.serve.step_s`` plus ``cgx.serve.between_steps_s``. The
rest is the unfed device (``device_unfed_pct``) and the intervals dropped: a
late read that left nothing queued, a caller's absence. Over the untraced
measured loop. A program without the account reads nothing."""

WALL = ("cgx.serve.step_s", "cgx.serve.between_steps_s")
ACCOUNTED = "cgx.serve.device.accounted_s"


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    if ACCOUNTED not in end or any(f"{name}.sum" not in end for name in WALL):
        return None
    wall = sum(end[f"{name}.sum"] - start.get(f"{name}.sum", 0.0)
               for name in WALL)
    if wall <= 0:
        return None
    return 100.0 * (end[ACCOUNTED] - start.get(ACCOUNTED, 0.0)) / wall
