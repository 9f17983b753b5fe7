"""How full the window layers' rings are: the pages that hold a key some
lane's token can still see (the program's count
``cgx.serve.kv.live_pages.window``, a step's sum over the lanes, one layer's
worth) over ``lanes x ring`` slots a step, over the decode steps of the
untraced measured loop. The rest of a ring read is dead: empty slots of
short lanes, idle lanes, and the slot whose page has slid out. Nothing for a
program without the count."""


def read(ctx):
    counters, cfg = ctx.get("counters"), ctx["config"]
    if not counters or not any(cfg.get("sliding_window_layout", ())):
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.kv.live_pages.window"
    steps = (end.get("cgx.serve.decode_steps", 0)
             - start.get("cgx.serve.decode_steps", 0))
    if name not in end or steps <= 0:
        return None
    serve = cfg["serve"]
    ring = -(-cfg["sliding_window_size"] // serve["page_tokens"]) + 1
    live = end[name] - start.get(name, 0.0)
    return 100.0 * live / (steps * serve["max_batch"] * ring)
