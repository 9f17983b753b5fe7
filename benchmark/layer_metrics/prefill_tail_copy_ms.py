"""Mean host time of a prefill's last phase (program's span
``cgx.serve.prefill.tail_copy``): the per-layer copies of the tail rows to
the host, which block on the device and so hold the device time of the
three phases before them.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_tail_copy_s")
    return None if mean is None else mean * 1e3
