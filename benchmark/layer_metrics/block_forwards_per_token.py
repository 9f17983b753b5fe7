"""Forwards of a lane's block a token emitted, over the untraced measured
loop: the program's counter ``cgx.serve.block.lane_steps`` (active lanes a
dispatched step, counted on the device) over ``cgx.serve.tokens_generated``.
A block of ``L`` positions costs its denoising forwards and the store: 1.25
where every block of four takes its four denoising steps, 1.0 is what an
autoregressive decoder pays, under 1 is what a trained model's confident
steps buy (``low_confidence_dynamic``). Nothing for a program that counts no
block steps (every adapter whose step is a token)."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    names = ("cgx.serve.block.lane_steps", "cgx.serve.tokens_generated")
    if any(name not in end for name in names):
        return None
    steps, tokens = (end[name] - start.get(name, 0.0) for name in names)
    return steps / tokens if tokens > 0 else None
