"""Mean host time of a prefill's first phase (program's span
``cgx.serve.prefill.forward``): padding the prompt and the call of the
``prefill`` program, which returns once the program is dispatched.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.prefill_forward_s")
    return None if mean is None else mean * 1e3
