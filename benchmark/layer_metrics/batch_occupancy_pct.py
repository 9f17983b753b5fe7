"""Mean share of the decode lanes in use, over the decode steps of the
untraced measured loop (the program's gauge ``cgx.serve.batch_occupancy``,
sampled by the benchmark's loop after every step that decoded)."""


def read(ctx):
    n = ctx["loop"].get("occupancy_n", 0)
    if not n:
        return None
    return 100.0 * ctx["loop"]["occupancy_sum"] / n
