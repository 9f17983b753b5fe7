"""Share of decode steps the scheduler dispatched before it had read the
previous step's tokens, over the untraced measured loop: the program's
counter ``cgx.serve.decode.ahead`` over ``cgx.serve.decode_steps``. Such a
step's dispatch, the emit before it and the caller's work between two ticks
ran under a step on the device, not between two."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.decode.ahead"
    steps = (end.get("cgx.serve.decode_steps", 0)
             - start.get("cgx.serve.decode_steps", 0))
    if name not in end or steps <= 0:
        return None
    return 100.0 * (end[name] - start.get(name, 0.0)) / steps
