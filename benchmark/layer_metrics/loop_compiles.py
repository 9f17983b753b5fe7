"""Programs JAX built inside the untraced measured loop, by the program's
own count: growth of its counter ``cgx.serve.compiles`` (one a backend
compile or persistent-cache retrieval, from a ``jax.monitoring`` listener
the scheduler installs). 0 in a sound run: every shape was warmed in set-up.
A program that does not count them reads nothing (one that does has built
programs in set-up, so its counter is there)."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.compiles"
    if name not in end:
        return None
    return end[name] - start.get(name, 0.0)
