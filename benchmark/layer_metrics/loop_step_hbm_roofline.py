"""Share of the HBM roofline that a looped decoder's decode step reaches: the
bytes the step has to move whatever implements it (``benchmark/
bytes_loop.py``: the layers' weights once a pass, the head, the lanes' live
pages and live tail rows in every slot) over the published HBM rate, over the
device time of one decode step by the program's own account (mean of
``cgx.serve.device.step_s``, ``step_device_ms``'s histogram), all over the
untraced measured loop. What the lanes held comes from the program's
counters over the same loop, a decode step's mean: active lanes
(``cgx.serve.loop.passes`` over the passes a lane takes), committed pages
(``cgx.serve.kv.decoded_pages.global``) and tail positions
(``cgx.serve.kv.live_tail_rows``). Bound: HBM (at 16 lanes the weights'
products are 1/15 of the chip's bf16 peak over the same time). The outputs of
the reads are not counted, so the share cannot pass 100 %. Nothing for a
configuration that is no looped decoder, a program without the account or
the counters, or a run without the chip's published peaks (a rehearsal)."""

from benchmark import bytes_loop, readers


def read(ctx):
    cfg = ctx["config"]
    if ("total_ut_steps" not in cfg or not ctx.get("counters")
            or not ctx.get("peaks")):
        return None
    seconds = readers.histogram_mean(ctx, "cgx.serve.device.step_s")
    start, end = ctx["counters"]["start"], ctx["counters"]["end"]
    names = ["cgx.serve." + name for name in (
        "decode_steps", "loop.passes", "kv.decoded_pages.global",
        "kv.live_tail_rows")]
    if not seconds or any(name not in end for name in names):
        return None
    steps, passes, pages, rows = (
        end[name] - start.get(name, 0.0) for name in names)
    if steps <= 0:
        return None
    need = bytes_loop.step_bytes(
        cfg, passes / steps / cfg["total_ut_steps"], pages / steps,
        rows / steps)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
