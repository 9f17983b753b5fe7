"""Mean host-clock time of one decode step (program's histogram
``cgx.serve.decode_step_s``: around ``decode_step`` + the token copy, which
blocks), over the untraced measured loop: difference of ``.sum`` over
difference of ``.count``. The histogram's quantiles span warm-up and are
not used."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.decode_step_s")
    return None if mean is None else mean * 1e3
