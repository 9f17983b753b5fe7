"""Mean host time of a prefill's third phase (program's span
``cgx.serve.prefill.ingest``): the call of the ``ingest`` program that
scatters the quantized rows into the pools.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_ingest_s")
    return None if mean is None else mean * 1e3
