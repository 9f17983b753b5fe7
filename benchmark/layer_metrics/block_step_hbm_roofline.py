"""Share of the HBM roofline that a block-diffusion decoder's decode step
reaches: the bytes the step has to move whatever implements it (``benchmark/
bytes_block.py``: the layers' weights and the head once, the lanes' live
8-bit pages and meta once for a block's queries, the tails' live rows) over
the published HBM rate, over the device time of one decode step by the
program's own account (mean of ``cgx.serve.device.step_s``,
``step_device_ms``'s histogram), all over the untraced measured loop. What
the lanes held comes from the program's counters over the same loop, a
decode step's mean: active lanes (``cgx.serve.block.lane_steps``), committed
pages (``cgx.serve.kv.decoded_pages.global``) and tail positions
(``cgx.serve.kv.live_tail_rows``, less the open blocks' own positions, which
the counter holds and no read fetches). Bound: HBM (at 64 lanes the step's
products are a sixth of the chip's bf16 peak over the same time). The
outputs of the reads are not counted, so the share cannot pass 100 %; it is
the share of the whole step. Nothing for a configuration that states no
block, a program without the account or the counters, or a run without the
chip's published peaks (a rehearsal)."""

from benchmark import bytes_block, readers


def read(ctx):
    cfg = ctx["config"]
    if ("block_length" not in cfg or not ctx.get("counters")
            or not ctx.get("peaks")):
        return None
    seconds = readers.histogram_mean(ctx, "cgx.serve.device.step_s")
    start, end = ctx["counters"]["start"], ctx["counters"]["end"]
    names = ["cgx.serve." + name for name in (
        "decode_steps", "block.lane_steps", "kv.decoded_pages.global",
        "kv.live_tail_rows")]
    if not seconds or any(name not in end for name in names):
        return None
    steps, lanes, pages, rows = (
        end[name] - start.get(name, 0.0) for name in names)
    if steps <= 0:
        return None
    need = bytes_block.step_bytes(
        cfg, lanes / steps, pages / steps,
        (rows - lanes * cfg["block_length"]) / steps)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
