"""Share of the rows the scheduler's ``commit`` program quantized that were
a full tail somebody wanted promoted, over the untraced measured loop: the
program's counter ``cgx.serve.commit.lanes`` (tails promoted) over
``cgx.serve.commit.rows`` (rows quantized a stream: the program's width a
call). The rest were padded slots, written to the pool's scratch row. A
program without the counters (one that quantizes every lane's tail) reads
nothing."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    lanes, rows = "cgx.serve.commit.lanes", "cgx.serve.commit.rows"
    if lanes not in end or rows not in end:
        return None
    quantized = end[rows] - start.get(rows, 0.0)
    if quantized <= 0:
        return None
    return 100.0 * (end[lanes] - start.get(lanes, 0.0)) / quantized
