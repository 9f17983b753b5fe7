"""Share of the HBM roofline that a decode step's hyper-connection reads
reach: the bytes its ``cgx_mhc_pre_decode`` calls must move (two a layer and
the read-out, ``benchmark/bytes_mhc.py``: the lanes' streams read once,
``phi`` once in float32, the sublayer's input and the mixes written) over
the published HBM rate, over the device time those calls took per decode
step in the traced window. Bound: HBM (a 24-column product and some hundred
vector operations a lane). Nothing for a configuration without
hyper-connections or a trace without the kernel."""

from benchmark import bytes_mhc, readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_mhc_pre_decode"),
        "traced_decode_steps")
    if seconds is None or "hc_mult" not in ctx["config"]:
        return None
    least = bytes_mhc.step_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
