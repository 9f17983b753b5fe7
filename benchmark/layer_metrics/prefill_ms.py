"""Mean host-clock time of one local prefill (program's histogram
``cgx.serve.prefill_s``: the prefill program, the page quantize and ingest,
and the tail copies to the host), over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_s")
    return None if mean is None else mean * 1e3
