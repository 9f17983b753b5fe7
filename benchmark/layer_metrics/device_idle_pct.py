"""Share of the traced window in which no operation ran on the first chip:
1 - union of the op intervals / window."""

from benchmark import readers


def read(ctx):
    return readers.idle_pct(ctx)
