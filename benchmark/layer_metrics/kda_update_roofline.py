"""Share of the HBM roofline that a decode step's KDA state update reaches:
the bytes its ``cgx_kda_update`` calls must move (one a KDA layer,
``benchmark/bytes_kda.py``: the state read and written, the small operands)
over the published HBM rate, over the device time those calls took per decode
step in the traced window. Bound: HBM (some ten vector operations a value).
Nothing for a configuration without KDA layers or a trace without the
kernel."""

from benchmark import bytes_kda, readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_kda_update"), "traced_decode_steps")
    if seconds is None or "kda_lower_bound" not in ctx["config"]:
        return None
    least = (bytes_kda.step_bytes(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
