"""Share of a decode step's experts (expert layers x routed experts) that
got at least one token, over the untraced measured loop: the program's
counter ``cgx.serve.moe.experts_touched`` over ``cgx.serve.decode_steps``.
What a read of the touched experts' weights alone could save."""


def read(ctx):
    counters, cfg = ctx.get("counters"), ctx["config"]
    if not counters or "n_routed_experts" not in cfg:
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.moe.experts_touched"
    steps = (end.get("cgx.serve.decode_steps", 0)
             - start.get("cgx.serve.decode_steps", 0))
    if name not in end or steps <= 0:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    touched = end[name] - start.get(name, 0.0)
    return 100.0 * touched / (steps * layers * cfg["n_routed_experts"])
