"""What the per-layer readers share: the first chip's op events inside the
traced window, and the mean of one of the program's histograms over the
untraced measured loop."""

from __future__ import annotations

from benchmark import trace_reduce


def first_chip(ctx):
    """(events, window start, window end) of the first chip, or None where
    the run has no device trace."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.window(trace)
    return trace["devices"][str(ctx["device_ids"][0])], t0, t1


def seconds_per(ctx, match, per: str):
    """Device seconds of the ops ``match`` accepts, over ``ctx["loop"][per]``
    (traced steps); None where there is nothing to read."""
    found, n = first_chip(ctx), ctx["loop"].get(per, 0)
    if found is None or not n:
        return None
    seconds, count = trace_reduce.seconds_where(*found, match)
    return seconds / n if count else None


def idle_pct(ctx):
    """Share of the traced window in which no op ran on the first chip."""
    found = first_chip(ctx)
    if found is None:
        return None
    events, t0, t1 = found
    busy, _ = trace_reduce.busy_and_gaps(events, t0, t1)
    return 100.0 * (1.0 - busy / ((t1 - t0) / 1e9))


def histogram_mean(ctx, name: str):
    """Mean of the program's histogram ``name``: difference of its ``.sum``
    over difference of its ``.count`` between the loop's start and end (its
    quantiles span warm-up and are not used)."""
    start, end = ctx["counters"]["start"], ctx["counters"]["end"]
    n = end.get(f"{name}.count", 0) - start.get(f"{name}.count", 0)
    if n <= 0:
        return None
    return (end[f"{name}.sum"] - start.get(f"{name}.sum", 0.0)) / n
