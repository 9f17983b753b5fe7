"""Share of the HBM roofline that a decode step's recurrent-state update
reaches: the bytes its ``cgx_ssm_update`` calls must move (one a Mamba
layer, ``benchmark/bytes_ssm.py``: the state read and written, the small
operands) over the published HBM rate, over the device time those calls
took per decode step in the traced window. Bound: HBM (five vector
operations a value). Nothing for a configuration without state-space layers
or a trace without the kernel."""

from benchmark import bytes_ssm, readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_ssm_update"), "traced_decode_steps")
    if seconds is None or "mamba_d_state" not in ctx["config"]:
        return None
    least = (bytes_ssm.step_bytes(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
