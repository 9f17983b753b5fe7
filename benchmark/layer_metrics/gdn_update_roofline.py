"""Share of the HBM roofline that a decode step's delta-rule state update
reaches: the bytes its ``cgx_gdn_update`` calls must move (one a delta-rule
layer, ``benchmark/bytes_gdn.py``: the state read and written, the small
operands) over the published HBM rate, over the device time those calls
took per decode step in the traced window. Bound: HBM (some ten vector
operations a value). Nothing for a configuration without delta-rule layers
or a trace without the kernel."""

from benchmark import bytes_gdn, readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_gdn_update"), "traced_decode_steps")
    if seconds is None or "linear_key_head_dim" not in ctx["config"]:
        return None
    least = (bytes_gdn.step_bytes(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
