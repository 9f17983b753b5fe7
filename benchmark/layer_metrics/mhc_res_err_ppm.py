"""How far a decode step's stream-to-stream mixes were from doubly
stochastic: the largest distance of a row's or a column's sum of ``H_res``
from 1 over the step's sublayers and active lanes, in millionths, as the
program's step counter ``cgx.serve.mhc.res_err_ppm`` adds it up, mean over
the traced window's decode steps (the driver leaves the traced steps' sum).
What the Sinkhorn iterations the configuration states leave undone. Nothing
for a driver or a program that leaves no such sum."""


def read(ctx):
    loop = ctx["loop"]
    steps = loop.get("traced_decode_steps", 0)
    if not steps or "traced_mhc_res_err_ppm" not in loop:
        return None
    return loop["traced_mhc_res_err_ppm"] / steps
