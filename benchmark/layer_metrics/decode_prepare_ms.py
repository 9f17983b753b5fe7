"""Mean host time of a decode tick before its step (program's span
``cgx.serve.decode.prepare``): the blocking copy of the lanes' tail lengths,
evictions, and the commit of full tails.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.decode_prepare_s")
    return None if mean is None else mean * 1e3
