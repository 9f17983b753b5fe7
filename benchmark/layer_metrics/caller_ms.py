"""Mean time between two ticks (program's histogram
``cgx.serve.between_steps_s``): from the return of one ``step()`` to the
start of the next, the caller's own work (here the closed loop's clients:
stamping tokens and sending a finished client's next request).
Over the untraced measured loop. A program without the histogram reads
nothing."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.between_steps_s")
    return None if mean is None else mean * 1e3
