"""Mean host time of a decode tick after its step (program's span
``cgx.serve.decode.emit``): the per-lane token loop, the lanes that finish,
and the token counters.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.decode_emit_s")
    return None if mean is None else mean * 1e3
