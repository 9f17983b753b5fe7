"""The busiest expert's load over the mean load, in the decode steps of the
untraced measured loop: the program's counter ``cgx.serve.moe.load_max``
(each step's largest count of tokens on one expert of one layer) over
``cgx.serve.moe.assignments`` spread evenly over the expert layers' experts.
1 is a perfectly even routing; the stragglers of an expert-parallel layout
wait for this."""


def read(ctx):
    counters, cfg = ctx.get("counters"), ctx["config"]
    if not counters or "n_routed_experts" not in cfg:
        return None
    start, end = counters["start"], counters["end"]

    def delta(name):
        return end.get(name, 0.0) - start.get(name, 0.0)

    assignments = delta("cgx.serve.moe.assignments")
    if assignments <= 0:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    mean = assignments / (layers * cfg["n_routed_experts"])
    return delta("cgx.serve.moe.load_max") / mean
