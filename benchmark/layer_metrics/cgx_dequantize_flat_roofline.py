"""Share of the HBM roofline that the decode step's ``cgx_dequantize_flat``
calls reach: the bytes one call must move (``benchmark/bytes.py``, from the
serve geometry) over the published HBM rate, over the call's mean device
time in the traced window. Bound: HBM (no arithmetic to speak of)."""

from benchmark import bytes as byte_counts
from benchmark import readers, trace_reduce


def read(ctx):
    found = readers.first_chip(ctx)
    if found is None:
        return None
    seconds, count = trace_reduce.seconds_where(
        *found, lambda n: n.startswith("cgx_dequantize_flat"))
    if not count:
        return None
    cfg, serve = ctx["config"], ctx["config"]["serve"]
    need = byte_counts.kv_dequant_call_bytes(
        serve["max_batch"], serve["max_seq"], cfg["n_embd"],
        cfg["precision"]["kv_page_bits"], cfg["precision"]["kv_bucket"],
    )["total"]
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / count)
