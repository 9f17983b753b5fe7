"""Device time of one ``commit`` call by the program's own account: the mean
of ``cgx.serve.device.commit_step_s`` (fed-through intervals that held one
tick's commit calls and one decode step) less the mean of
``cgx.serve.device.step_s`` (those that held the step alone), over the calls
such an interval held (``cgx.serve.device.commit_calls`` over the intervals).
Its yardstick is ``jit_commit`` on a kept trace's ``XLA Modules`` line. Over
the untraced measured loop. A program without the account, or a loop with no
clean interval of either class, reads nothing."""

from benchmark import readers

BOTH = "cgx.serve.device.commit_step_s"
CALLS = "cgx.serve.device.commit_calls"


def read(ctx):
    if not ctx.get("counters"):
        return None
    both = readers.histogram_mean(ctx, BOTH)
    step = readers.histogram_mean(ctx, "cgx.serve.device.step_s")
    if both is None or step is None:
        return None
    start, end = ctx["counters"]["start"], ctx["counters"]["end"]
    calls = end.get(CALLS, 0.0) - start.get(CALLS, 0.0)
    if calls <= 0:
        return None
    intervals = end[f"{BOTH}.count"] - start.get(f"{BOTH}.count", 0)
    return (both - step) / (calls / intervals) * 1e3
