"""Share of the HBM roofline that a decode step's window-layer page reads
reach: the bytes of the step's LIVE window pages (``benchmark/
bytes_window.py``: the program's count ``cgx.serve.kv.live_pages.window``
over the traced steps; wire words and meta read, rows written) over the
published HBM rate, over the device time of the ``cgx_dequantize_window``
calls per decode step in the traced window. Bound: HBM (no arithmetic to
speak of). The work counted is the live pages' whatever the kernel decodes
beside them, so the share cannot pass 100 %. Nothing for a configuration
without window layers, a trace without the kernel or a program without the
count."""

from benchmark import bytes_window, readers


def read(ctx):
    cfg, loop = ctx["config"], ctx["loop"]
    if not any(cfg.get("sliding_window_layout", ())):
        return None
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_dequantize_window"),
        "traced_decode_steps")
    live = loop.get("traced_live_window_pages")
    if seconds is None or not live:
        return None
    need = bytes_window.step_bytes(cfg, live / loop["traced_decode_steps"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
