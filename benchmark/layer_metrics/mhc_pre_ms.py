"""Device time per decode step in front of the hyper-connected sublayers:
the custom calls whose name starts ``cgx_mhc_pre_decode`` (two a layer and
the read-out; a prefill's calls are named ``cgx_mhc_pre_prefill`` and are
not in it), summed over the traced window on the first chip, over its decode
steps. Nothing where the trace holds no such kernel (a program without
hyper-connections)."""

from benchmark import readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_mhc_pre_decode"),
        "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
