"""Device time of one decode step by the program's own account, no profiler:
mean of the histogram ``cgx.serve.device.step_s``, the intervals between two
blocked reads of the scheduler through which the device was fed and which
held exactly one ``decode_step`` (the program's time plus one program
boundary). Its yardstick is the mean ``jit_decode_step`` on a kept trace's
``XLA Modules`` line (``tools/cgx_optable.py --programs``). Over the untraced
measured loop. A program without the account reads nothing."""

from benchmark import readers


def read(ctx):
    if not ctx.get("counters"):
        return None
    mean = readers.histogram_mean(ctx, "cgx.serve.device.step_s")
    return None if mean is None else mean * 1e3
