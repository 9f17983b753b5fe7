"""Share of the HBM roofline that a decode step's expert products reach: the
bytes of the held experts the step touched (``benchmark/bytes_experts.py``:
the program's count ``cgx.serve.moe.experts_touched`` over the traced steps,
three matrices an expert in the parameters' type) over the published HBM
rate, over the device time of the ops ``experts_matmul_ms`` reads
(``ragged-dot*`` or ``cgx_grouped_matmul*``) per decode step in the traced
window. Bound: HBM (a row or two an expert). The bytes are the same whichever
kernel runs the products, and the window's prefills add time and no bytes,
so the share cannot pass 100 %. Nothing for a trace without such an op or a
driver that leaves no count of the traced steps' touched experts."""

from benchmark import bytes_experts, readers


def read(ctx):
    loop = ctx["loop"]
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith(("ragged-dot", "cgx_grouped_matmul")),
        "traced_decode_steps")
    touched = loop.get("traced_experts_touched")
    if seconds is None or not touched:
        return None
    need = bytes_experts.step_bytes(
        ctx["config"], touched / loop["traced_decode_steps"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
