"""Mean host time of one decode step's dispatch (program's span
``cgx.serve.dispatch.step``): the call of the ``decode_step`` program, fresh
or queued ahead, which returns once the program is dispatched.
Over the untraced measured loop. A program without the span reads nothing."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.dispatch_step_s")
    return None if mean is None else mean * 1e3
