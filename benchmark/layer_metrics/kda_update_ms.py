"""Device time per decode step spent rewriting the KDA layers' recurrent
state: the custom calls whose name starts ``cgx_kda_update`` (one a KDA
layer), summed over the traced window on the first chip, over its decode
steps. Nothing where the trace holds no such kernel (a program without
one)."""

from benchmark import readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_kda_update"), "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
