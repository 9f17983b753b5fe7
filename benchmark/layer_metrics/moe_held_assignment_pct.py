"""Share of the decode steps' expert assignments that fell on an expert this
chip holds, over the untraced measured loop: the program's counter
``cgx.serve.moe.held_assignments`` over ``cgx.serve.moe.assignments`` (every
assignment the router made, over all the published experts). A chip that
holds a quarter of a layer's experts gets 25 % under an even routing; what
lies above is this chip's experts' share of a four-chip layer's work. Nothing
for a program that counts no held assignments (one that holds every
expert)."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.moe.held_assignments"
    if name not in end:
        return None
    made = (end.get("cgx.serve.moe.assignments", 0.0)
            - start.get("cgx.serve.moe.assignments", 0.0))
    if made <= 0:
        return None
    return 100.0 * (end[name] - start.get(name, 0.0)) / made
