"""Mean time a first token stood behind other dispatches (program's
histogram ``cgx.serve.ttft_behind_s``): from the return of the request's own
``admit_lane`` dispatch to the start of the read of its first token; the
tick's other admissions, the commit and the step, and the run-ahead's commit
and step lie in it.
Over the untraced measured loop. A program without the histogram reads
nothing."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.ttft_behind_s")
    return None if mean is None else mean * 1e3
