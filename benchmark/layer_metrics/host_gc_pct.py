"""Share of the scheduler's ticks spent in full (generation 2) garbage
collections: growth of the sum of the program's histogram
``cgx.serve.host_gc_s`` over growth of the sum of ``cgx.serve.step_s``.
Over the untraced measured loop. 0 where no full collection ran; nothing where
the program times no ticks."""


def read(ctx):
    start, end = ctx["counters"]["start"], ctx["counters"]["end"]

    def grown(key):
        return end.get(key, 0.0) - start.get(key, 0.0)

    ticks = grown("cgx.serve.step_s.sum")
    if ticks <= 0:
        return None
    return 100.0 * grown("cgx.serve.host_gc_s.sum") / ticks
