"""Device time per decode step in the experts' grouped products, whatever
implements them: the ops whose name starts ``ragged-dot`` (XLA's own kernel
behind ``jax.lax.ragged_dot``) or ``cgx_grouped_matmul`` (the repo's), summed
over the traced window on the first chip, over its decode steps. The
window's prefills run the same products, and their share is in the number
(as it is in ``kda_update_ms``): it follows the decode step where few ticks
admit. Nothing where the trace holds neither (a program without experts)."""

from benchmark import readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith(("ragged-dot", "cgx_grouped_matmul")),
        "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
