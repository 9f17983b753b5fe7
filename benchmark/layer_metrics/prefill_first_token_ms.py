"""Mean host time of the read of a first token (program's span
``cgx.serve.prefill.first_token``): the blocking copy that waits for what the
device still has queued up to the request's prefill and lane write.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_first_token_s")
    return None if mean is None else mean * 1e3
