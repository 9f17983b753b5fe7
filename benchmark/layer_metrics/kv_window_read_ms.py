"""Device time per decode step in the window layers' page reads: the custom
calls whose name starts ``cgx_dequantize_window`` (K and V of every window
layer, each over the lanes' rings), summed over the traced window on the
first chip, over its decode steps. Nothing where the trace holds no such
kernel (a program without window layers)."""

from benchmark import readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_dequantize_window"),
        "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
