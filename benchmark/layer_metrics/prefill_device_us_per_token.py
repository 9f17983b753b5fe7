"""Device time of a prefill by padded token, which does not move with the
loop's mix of prompt lengths: growth of the sum of the program's histogram
``cgx.serve.device.prefill_s`` over growth of its counter
``cgx.serve.device.prefill_tokens`` (the padded lengths of the same
intervals), in microseconds. Over the untraced measured loop. A program
without the account reads nothing."""

SECONDS = "cgx.serve.device.prefill_s.sum"
TOKENS = "cgx.serve.device.prefill_tokens"


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    tokens = end.get(TOKENS, 0.0) - start.get(TOKENS, 0.0)
    if tokens <= 0 or SECONDS not in end:
        return None
    return 1e6 * (end[SECONDS] - start.get(SECONDS, 0.0)) / tokens
