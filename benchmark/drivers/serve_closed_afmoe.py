"""Closed-loop serving of a sandwich-normed window/full grouped-query decoder
with a held share of routed experts: ``serve_closed``'s loop, warm-up and
clock and ``serve_closed_window``'s sample (both loaded by name, not copied),
around the program's ``AfmoeServer`` behind the same
``ContinuousBatchScheduler``.

What differs from ``serve_closed_window``: the weights (``weights_afmoe``:
the chip's share of each layer's experts under a router of the published
width, a slice of the vocabulary), the server (the ``afmoe`` block over the
same ``k`` and ``v`` streams, rings and global table), the reference
(``reference_afmoe``, given the same share and slice), the token ids (drawn
from the held slice, which is the configuration's ``vocab_size``), and one
more count of the traced window, the held experts its decode steps touched
(``experts_matmul_roofline``'s bytes). The model is imported here, at the
top: a tree without it fails before anything is built.
"""

from __future__ import annotations

import gc

import numpy as np

from torch_cgx_tpu.models.afmoe import AfmoeConfig
from torch_cgx_tpu.serving.window import AfmoeServer

from benchmark import reference_afmoe, spec, traffic as traffic_mod
from benchmark import weights_afmoe

closed = spec.load_module("drivers", "serve_closed")
sample = spec.load_module("drivers", "serve_closed_window").sample


def check(ctx, params, finished, dropped: float) -> None:
    """Served tokens against the plain reference, after the window, and
    the expert layers' dropped tokens."""
    cfg, mix, limits = ctx.config, ctx.traffic, ctx.config["limits"]
    ctx.compare("moe_dropped", float(dropped), limits["moe_dropped"])
    done = [f for f in finished if f["ok"]]
    if not done:
        ctx.compare("requests finished in the window", 0, 1, at_most=False)
        return
    picked = sample(done, mix, ctx.seed)
    gaps, agree = reference_afmoe.served_token_gaps(
        params, cfg, [done[i]["prompt"] for i in picked],
        [done[i]["output"] for i in picked],
        pad_multiple=cfg["serve"]["page_tokens"],
        lengths=[g["hi"] + mix["output"]["hi"] - 1
                 for g in mix["prompt_groups"]],
        most_outputs=mix["output"]["hi"], **cfg["reference"],
    )
    flat = np.concatenate(gaps)
    ctx.log(f"reference: {len(picked)} requests (prompts of "
            f"{sorted(len(done[i]['prompt']) for i in picked)}), {flat.size} "
            f"served tokens, {100 * agree:.1f}% are the reference's own "
            f"choice; gap below the reference's best: max {flat.max():.5f}, "
            f"mean {flat.mean():.6f}, p99 {np.percentile(flat, 99):.5f}")
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
    with ctx.phase("weights"):
        params = weights_afmoe.make_params(cfg, ctx.seed)
        jax.block_until_ready(params)
    with ctx.phase("server"):
        model_cfg = AfmoeConfig.from_hf(
            cfg, dtype=jnp.dtype(cfg["precision"]["activations"]),
            q_block=sv["q_block"],
        )
        serve_cfg = ServeConfig(
            page_tokens=sv["page_tokens"], max_batch=sv["max_batch"],
            max_pages=sv["max_pages"], max_seq=sv["max_seq"],
            ship_depth=sv["ship_depth"],
        )
        server = AfmoeServer(model_cfg, params, serve_cfg)
        sched = ContinuousBatchScheduler(server)
        prog = sched._prog
        bits = sorted({s.bits for layer in prog.streams for _, s in layer})
        rows = {bool(w): sched._state["pools"][layer]["k"][0].shape[0]
                for layer, w in enumerate(prog.windows)}
        held = {name: metrics.get(f"cgx.serve.kv.pool_bytes.{name}") / 1e9
                for name in ("window", "global", "uniform")}
        ctx.log(f"cache streams {list(prog.names)}, page bits as resolved "
                f"by the program: {bits}; window layers "
                f"{[i for i, w in enumerate(prog.windows) if w]} keep a ring "
                f"of {prog.ring} pages a lane: their pools hold "
                f"{rows.get(True)} pages, {held['window']:.3f} GB (a uniform "
                f"table would hold {held['uniform']:.3f} GB); the global "
                f"layers' pools hold {rows.get(False)} pages, "
                f"{held['global']:.3f} GB; experts {model_cfg.first_expert} "
                f"to {model_cfg.first_expert + model_cfg.n_held} of "
                f"{model_cfg.n_experts} held on "
                f"{model_cfg.dense.count(False)} layers")
        if bits != [cfg["precision"]["kv_page_bits"]]:
            raise SystemExit(
                f"benchmark: the program resolved page bits {bits}, the "
                f"configuration states {cfg['precision']['kv_page_bits']}"
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
        traced0 = metrics.snapshot("cgx.serve.")
        ctx.start_trace()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            loop.run_for(trace_s)
        jax.profiler.stop_trace()
        traced1 = metrics.snapshot("cgx.serve.")
        found["loop"]["traced_decode_steps"] = (
            traced1.get("cgx.serve.decode_steps", 0.0)
            - traced0.get("cgx.serve.decode_steps", 0.0))
        # The traced steps' live window pages (the window read's roofline).
        name = "cgx.serve.kv.live_pages.window"
        found["loop"]["traced_live_window_pages"] = (
            traced1.get(name, 0.0) - traced0.get(name, 0.0))
        # The traced steps' touched held experts (the products' roofline).
        name = "cgx.serve.moe.experts_touched"
        found["loop"]["traced_experts_touched"] = (
            traced1.get(name, 0.0) - traced0.get(name, 0.0))
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
            f"dropped "
            f"{dropped:.0f}; window pages committed "
            f"{metrics.get('cgx.serve.window.pages_committed'):.0f}, over a "
            f"page that slid out "
            f"{metrics.get('cgx.serve.window.pages_recycled'):.0f}; peak "
            f"memory {ctx.memory_peak_bytes / 1e9:.2f} GB")
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
        }

    # The program's state goes before the reference runs, so that the peak
    # above stays the program's.
    del loop, sched, server, prog
    gc.collect()
    with ctx.phase("reference", excluded=True):
        check(ctx, params, finished, dropped)
    if ctx.trace:
        ctx.read_trace(found)
    return found
