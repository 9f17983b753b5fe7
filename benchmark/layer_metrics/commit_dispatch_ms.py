"""Mean host time of one tick's commit dispatch (program's span
``cgx.serve.dispatch.commit``): the calls of the ``commit`` program that
promote the tick's full tails, the fresh step's and the run-ahead's alike;
a tick that promotes none has no sample.
Over the untraced measured loop. A program without the span reads nothing."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.dispatch_commit_s")
    return None if mean is None else mean * 1e3
