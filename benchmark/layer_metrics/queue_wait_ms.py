"""Mean wait of a request between ``submit`` and the start of its prefill
(program's histogram ``cgx.serve.queue_wait_s``, on ``submitted_at``'s clock):
the part of a first token's wait spent behind other requests' prefills and
the decode ticks between them.
Over the untraced measured loop."""

from benchmark import readers


def read(ctx):
    mean = readers.histogram_mean(ctx, "cgx.serve.queue_wait_s")
    return None if mean is None else mean * 1e3
