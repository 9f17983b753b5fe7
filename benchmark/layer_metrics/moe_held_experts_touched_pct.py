"""Share of a decode step's held experts (expert layers x experts held on
this chip) that got at least one token, over the untraced measured loop: the
program's counter ``cgx.serve.moe.experts_touched`` (which counts held
experts where the layer holds a share) over ``cgx.serve.decode_steps``. The
share of the held experts' weights a decode step has to read. Nothing for a
program that counts no held assignments."""


def read(ctx):
    counters, cfg = ctx.get("counters"), ctx["config"]
    if not counters or "num_experts_published" not in cfg:
        return None
    start, end = counters["start"], counters["end"]
    name = "cgx.serve.moe.experts_touched"
    steps = (end.get("cgx.serve.decode_steps", 0)
             - start.get("cgx.serve.decode_steps", 0))
    if ("cgx.serve.moe.held_assignments" not in end or name not in end
            or steps <= 0):
        return None
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    layers = sum(1 for i in kept if i >= cfg["first_k_dense_replace"])
    touched = end[name] - start.get(name, 0.0)
    return 100.0 * touched / (steps * layers * cfg["num_experts"])
