"""Device time per decode step in the global layers' page reads of a program
that has window layers too: the custom calls whose name starts
``cgx_dequantize_flat`` (K and V of every global layer, each over the lanes'
whole page tables), summed over the traced window on the first chip, over
its decode steps. Nothing for a configuration without window layers (whose
every read goes by that name: ``kv_read_ms``)."""

from benchmark import readers


def read(ctx):
    if not any(ctx["config"].get("sliding_window_layout", ())):
        return None
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_dequantize_flat"),
        "traced_decode_steps")
    return None if seconds is None else seconds * 1e3
