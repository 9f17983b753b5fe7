"""Closed-loop serving of a hybrid KDA / latent-attention decoder with
routed experts of which the chip holds a share: ``serve_closed``'s loop,
warm-up and clock (loaded by name, not copied), around the program's
``HybridLatentMoEServer`` behind the same ``ContinuousBatchScheduler``.

What differs from ``serve_closed``: the weights (``weights_ling_hybrid``,
bfloat16 as the configuration states), the server (latent pages on the
latent-attention layers, a per-lane recurrent state, a matrix a head, on the
KDA layers, in the type the configuration's ``precision`` states for it), the
reference (``reference_ling_hybrid``, one sampled request at a time, the
recurrence a position at a time, the held experts looped), the token ids
(drawn from the held slice of the vocabulary, which is the configuration's
``vocab_size``), and one more number in ``correct``: the tokens the expert
layers dropped over the whole run (the program's counter
``cgx.serve.moe.dropped``), which has to be 0. The model is imported here, at
the top: a tree without it fails before anything is built.
"""

from __future__ import annotations

import gc

import numpy as np

from torch_cgx_tpu.models.ling_hybrid import LingHybridConfig
from torch_cgx_tpu.serving.hybrid import HybridLatentMoEServer

from benchmark import reference_ling_hybrid, spec, traffic as traffic_mod
from benchmark import weights_ling_hybrid

closed = spec.load_module("drivers", "serve_closed")


def check(ctx, params, finished, dropped: float) -> None:
    """Served tokens against the plain reference, after the window, and
    the expert layers' dropped tokens."""
    cfg, mix, limits = ctx.config, ctx.traffic, ctx.config["limits"]
    ctx.compare("moe_dropped", float(dropped), limits["moe_dropped"])
    done = [f for f in finished if f["ok"]]
    if not done:
        ctx.compare("requests finished in the window", 0, 1, at_most=False)
        return
    rng = np.random.default_rng(ctx.seed)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["output"]))
    others = [i for i in rng.permutation(len(done)) if i != longest]
    sample = [longest] + [int(i) for i in others[: mix["check_requests"] - 1]]
    gaps, agree = reference_ling_hybrid.served_token_gaps(
        params, cfg, [done[i]["prompt"] for i in sample],
        [done[i]["output"] for i in sample],
        pad_multiple=cfg["serve"]["page_tokens"],
        longest=max(g["hi"] for g in mix["prompt_groups"])
        + mix["output"]["hi"] - 1,
        most_outputs=mix["output"]["hi"],
        q_block=cfg["serve"]["q_block"],
        expert_block=cfg["reference"]["expert_block"],
    )
    flat = np.concatenate(gaps)
    ctx.log(f"reference: {len(sample)} requests, {flat.size} served tokens, "
            f"{100 * agree:.1f}% are the reference's own choice; gap below "
            f"the reference's best: max {flat.max():.5f}, mean "
            f"{flat.mean():.6f}, p99 {np.percentile(flat, 99):.5f}")
    ctx.compare("served_gap_max", float(flat.max()), limits["served_gap_max"])
    ctx.compare("served_gap_mean", float(flat.mean()),
                limits["served_gap_mean"])


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from torch_cgx_tpu.serving import (
        ContinuousBatchScheduler, Request, ServeConfig,
    )
    from torch_cgx_tpu.utils.logging import metrics

    from benchmark import trace_reduce

    cfg, sv, mix = ctx.config, ctx.config["serve"], ctx.traffic
    precision = cfg["precision"]
    if precision["kda_state"] != precision["conv_state"]:
        raise SystemExit("benchmark: the server keeps both recurrent states "
                         "in one type; the configuration states two")
    with ctx.phase("weights"):
        params = weights_ling_hybrid.make_params(cfg, ctx.seed)
        jax.block_until_ready(params)
    with ctx.phase("server"):
        model_cfg = LingHybridConfig.from_hf(
            cfg, dtype=jnp.dtype(precision["activations"]),
            chunk=sv["kda_chunk"], q_block=sv["q_block"],
        )
        serve_cfg = ServeConfig(
            page_tokens=sv["page_tokens"], max_batch=sv["max_batch"],
            max_pages=sv["max_pages"], max_seq=sv["max_seq"],
            ship_depth=sv["ship_depth"],
        )
        server = HybridLatentMoEServer(model_cfg, params, serve_cfg,
                                       state_dtype=precision["kda_state"])
        sched = ContinuousBatchScheduler(server)
        bits = sorted({s.bits for layer in sched._prog.streams
                       for _, s in layer})
        ctx.log(f"cache streams {list(sched._prog.names)} on layers "
                f"{list(model_cfg.attention_layers)}, page bits as resolved "
                f"by the program: {bits}; state streams "
                f"{list(sched._prog.state_names)} in "
                f"{server.state_dtype.name}, "
                f"{metrics.get('cgx.serve.state.bytes') / 1e9:.3f} GB held; "
                f"experts {model_cfg.first_expert} to "
                f"{model_cfg.first_expert + model_cfg.n_held} of "
                f"{model_cfg.n_experts} held on "
                f"{len(model_cfg.expert_layers)} layers")
        if bits != [precision["kv_page_bits"]]:
            raise SystemExit(
                f"benchmark: the program resolved page bits {bits}, the "
                f"configuration states {precision['kv_page_bits']}"
            )
    # Host spans around the scheduler's two halves, in every run (see
    # ``serve_closed``: a Pallas kernel's compile-cache key holds the call
    # stack it was traced under).
    for name, label in (("_admit", "bench.admit/prefill"),
                        ("_decode", "bench.decode")):
        inner = getattr(sched, name)

        def spanned(inner=inner, label=label):
            with jax.profiler.TraceAnnotation(label):
                return inner()

        setattr(sched, name, spanned)
    mix_traffic = traffic_mod.ServeTraffic(mix, ctx.seed, cfg["vocab_size"])
    loop = closed.ClosedLoop(sched, mix_traffic, Request, metrics,
                             jax.profiler.TraceAnnotation)
    dropped0 = metrics.get("cgx.serve.moe.dropped")
    with ctx.phase("warm-up"):
        closed.warm_up(ctx, sched, mix_traffic, Request, sv["page_tokens"],
                       sv["max_seq"], sv["max_batch"])
    with ctx.phase("ramp"):
        loop.start_clients()
        while any(slot[1] == 0 for slot in loop.inflight.values()):
            loop.tick()  # until every client has its first token
        loop.run_for(mix["ramp_s"])

    found = {"loop": {}}
    errors0 = metrics.get("cgx.serve.request_errors")
    finished = []
    ctx.open_window()
    if ctx.trace:
        trace_s = min(mix["trace_s"], ctx.seconds / 2)
        loop.reset()
        steps0 = metrics.get("cgx.serve.decode_steps")
        ctx.start_trace()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            loop.run_for(trace_s)
        jax.profiler.stop_trace()
        found["loop"]["traced_decode_steps"] = (
            metrics.get("cgx.serve.decode_steps") - steps0)
        finished += loop.finished
        seconds = ctx.seconds - trace_s
    else:
        seconds = ctx.seconds
    loop.reset()
    counters_start = metrics.snapshot("cgx.serve.")
    elapsed = loop.run_for(seconds)
    ctx.close_window()
    found["counters"] = {"start": counters_start,
                         "end": metrics.snapshot("cgx.serve.")}
    finished += loop.finished
    found["loop"].update(occupancy_sum=loop.occupancy_sum,
                         occupancy_n=loop.occupancy_n,
                         itl_p95_ms=closed.percentile(loop.gaps, 95) * 1e3
                         if loop.gaps else None)
    ctx.read_memory_peak()

    errors = int(metrics.get("cgx.serve.request_errors") - errors0)
    short = sum(1 for f in loop.finished if not f["ok"])
    found["attempted"] = len(loop.finished)
    found["failed"] = errors + short
    dropped = metrics.get("cgx.serve.moe.dropped") - dropped0
    ttfts = [t * 1e3 for t in loop.ttfts]  # requests sent in the window
    ctx.log(f"window {elapsed:.3f} s: {len(loop.finished)} requests "
            f"completed, {loop.tokens} tokens, {len(loop.gaps)} gaps, "
            f"{loop.occupancy_n} decode steps; request errors {errors}, "
            f"short answers {short}; expert assignments "
            f"{metrics.get('cgx.serve.moe.assignments'):.0f}, on held "
            f"experts {metrics.get('cgx.serve.moe.held_assignments'):.0f}, "
            f"dropped {dropped:.0f}; lanes' states written "
            f"{metrics.get('cgx.serve.state.lane_writes'):.0f}")
    if not ctx.trace:
        if len(ttfts) < 2 or not loop.gaps:
            raise SystemExit("benchmark: the window finished too few "
                             "requests to report a tail")
        ctx.log(f"ttft ms: n {len(ttfts)}, median "
                f"{closed.percentile(ttfts, 50):.2f}, p90 "
                f"{closed.percentile(ttfts, 90):.2f}; gap ms: n "
                f"{len(loop.gaps)}, median "
                f"{closed.percentile(loop.gaps, 50) * 1e3:.2f}, p95 "
                f"{closed.percentile(loop.gaps, 95) * 1e3:.2f}")
        found["end_to_end"] = {
            "serve_tokens_per_s": loop.tokens / elapsed,
            "serve_ttft_p90_ms": closed.percentile(ttfts, 90),
            "serve_itl_p50_ms": closed.percentile(loop.gaps, 50) * 1e3,
        }

    # The program's state goes before the reference runs, so that the peak
    # above stays the program's.
    del loop, sched, server
    gc.collect()
    with ctx.phase("reference", excluded=True):
        check(ctx, params, finished, dropped)
    if ctx.trace:
        ctx.read_trace(found)
    return found
