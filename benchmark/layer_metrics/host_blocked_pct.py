"""Share of the loop's wall time the host spent blocked on the device:
growth of the sums of the program's two wait histograms,
``cgx.serve.wait_step_s`` (the copy of a step's tokens) and
``cgx.serve.prefill_first_token_s`` (the copy of a first token), over the
wall, which is the growth of ``cgx.serve.step_s`` (the ticks) plus
``cgx.serve.between_steps_s`` (the caller's time between them).
Over the untraced measured loop. A program without those histograms reads
nothing."""

WAITS = ("cgx.serve.wait_step_s", "cgx.serve.prefill_first_token_s")
WALL = ("cgx.serve.step_s", "cgx.serve.between_steps_s")


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    if any(f"{name}.sum" not in end for name in (WAITS[0], *WALL)):
        return None

    def grown(names):
        return sum(end.get(f"{n}.sum", 0.0) - start.get(f"{n}.sum", 0.0)
                   for n in names)

    wall = grown(WALL)
    return 100.0 * grown(WAITS) / wall if wall > 0 else None
