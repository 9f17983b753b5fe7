"""Mean host time of one lane write (program's span ``cgx.serve.admit_lane``):
the eager per-lane updates of the decode state and the first-token stamp.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.admit_lane_s")
    return None if mean is None else mean * 1e3
