"""Device time of one prompt's ``prefill_pages`` by the program's own account,
no profiler: mean of the histogram ``cgx.serve.device.prefill_s``, the sound
intervals that end in the read of a first token and hold that one prefill
(a lane write or a release may ride). A mean over the loop's own mix of
padded lengths; a prefill whose read the host reaches late is in no sample.
Its yardstick is ``jit_prefill_pages`` on a kept trace's ``XLA Modules``
line. Over the untraced measured loop. A program without the account reads
nothing."""

from benchmark import readers


def read(ctx):
    if not ctx.get("counters"):
        return None
    mean = readers.histogram_mean(ctx, "cgx.serve.device.prefill_s")
    return None if mean is None else mean * 1e3
