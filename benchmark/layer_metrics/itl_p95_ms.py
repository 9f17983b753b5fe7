"""95th percentile of the gaps between consecutive tokens of a request,
stamped by the benchmark's loop after each ``sched.step()`` (all gaps of the
untraced measured loop). A tail a user feels, but not an end-to-end metric:
in a closed loop at full lanes it flips between "one prefill before the
step" and "two", and spread by 7-25 % from run to run (chip runs, PR 24).
The median of the same gaps is the end-to-end ``serve_itl_p50_ms``."""


def read(ctx):
    return ctx["loop"].get("itl_p95_ms")
