"""Device time per step in the codec's Pallas kernels (custom calls named
``cgx_*``), first chip, over the traced window."""

from benchmark import readers, trace_reduce


def read(ctx):
    seconds = readers.seconds_per(ctx, trace_reduce.is_codec_kernel,
                                  "traced_steps")
    return None if seconds is None else seconds * 1e3
