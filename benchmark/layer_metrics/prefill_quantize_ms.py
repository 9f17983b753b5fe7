"""Mean host time of a prefill's second phase (program's span
``cgx.serve.prefill.quantize``): the per-layer loop of eager slices and
``quantize_page_rows`` calls with their page accounting, all dispatch.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_quantize_s")
    return None if mean is None else mean * 1e3
