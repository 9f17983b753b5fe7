"""Device time per step that the first chip's op line spends in collective
ops (all-to-all, which the chip's trace spells ``all_to_all``, all-gather,
all-reduce, ...; for asynchronous pairs that is the ``-start`` issue and the
``-done`` wait), over the traced window."""

from benchmark import readers, trace_reduce


def read(ctx):
    seconds = readers.seconds_per(ctx, trace_reduce.is_collective,
                                  "traced_steps")
    return None if seconds is None else seconds * 1e3
