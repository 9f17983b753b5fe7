"""Device time per decode step spent in the paged-cache read: the custom
calls whose name starts ``cgx_dequantize`` (K and V of every layer), summed
over the traced window on the first chip, over its decode steps."""

from benchmark import readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_dequantize"), "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
