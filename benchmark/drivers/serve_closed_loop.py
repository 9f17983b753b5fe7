"""Closed-loop serving of a looped decoder (its layers run several passes a
token, every pass with a cache of its own) through the program's
``LoopServer``: ``serve_closed``'s loop, warm-up and clock and
``serve_closed_window``'s sample (both loaded by name, not copied) behind the
same ``ContinuousBatchScheduler``, laid out as ``serve_closed_latent`` is.

What differs from one such model to the next is taken by name from the
configuration's ``harness`` block: ``weights`` and ``reference``, the modules
under ``benchmark/`` that draw the parameters from the seed and compute the
plain forward (one sampled request at a time, a request at its own group's
padded length), and ``traced_counters``, the step counters
``cgx.serve.<name>`` whose sum over the traced steps a per-layer reader
wants, by the key it reads (the passes the lanes took and their exit mass a
pass). The model and its adapter are imported here, at the top: a tree
without them fails before anything is built.

What a cold run compiles is compiled side by side, from shapes alone, while
the weights are drawn (:func:`compile_ahead`): the decode step (one scan
over the passes around the 48 layers), the prefill program of every padded
length of the mix, the commit, and the reference's pieces.
"""

from __future__ import annotations

import gc
import importlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from torch_cgx_tpu.models.ouro import OuroConfig
from torch_cgx_tpu.serving.loop import LoopServer

from benchmark import spec, traffic as traffic_mod

closed = spec.load_module("drivers", "serve_closed")
sample = spec.load_module("drivers", "serve_closed_window").sample


def reference_lengths(mix) -> list:
    """The longest sequence of each prompt group: what the reference pads a
    sampled request to."""
    return [g["hi"] + mix["output"]["hi"] - 1 for g in mix["prompt_groups"]]


def compile_ahead(pool, cfg, mix, model_cfg, serve_cfg, make_params,
                  reference) -> list:
    """Jobs of ``pool`` that lower and compile, from shapes alone, what the
    run will call: the decode step, the commit and ``prefill_pages`` at
    every padded length of the mix, with the operands the scheduler will
    hand them (programs of the namespace it will find in its cache, so its
    own first calls take the executables as they are), and the reference's
    pieces at the mix's two sequence lengths. XLA's compile holds no lock of
    Python's. A Pallas kernel's cache key holds the Python frames it was
    traced under unless locations carry none, which is why :func:`run` sets
    ``jax_traceback_in_locations_limit`` to 0 for the whole process."""
    import jax

    from torch_cgx_tpu.serving import programs, scheduler

    prog = scheduler._decode_program(LoopServer(model_cfg, None, serve_cfg))
    tree = jax.eval_shape(make_params)
    state = jax.eval_shape(lambda: programs.fresh_state(prog, serve_cfg))
    pages = serve_cfg.page_tokens

    def prefill(s):
        prog.prefill_pages.lower(
            tree, state["pools"], np.zeros((1, s), np.int32),
            np.arange(s, dtype=np.int32)[None], np.int32(s - 1),
            np.full((s // pages,), serve_cfg.max_pages, np.int32),
            np.int32(0),
        ).compile()

    def commit():
        lanes = np.zeros((serve_cfg.commit_lanes,), np.int32)
        prog.commit.lower(state, lanes, lanes).compile()

    jobs = [pool.submit(lambda: prog.decode_step.lower(tree, state).compile()),
            pool.submit(commit)]
    jobs += [pool.submit(prefill, s)
             for s in traffic_mod.padded_lengths(mix, pages)]
    jobs += [pool.submit(
        reference.compile_ahead, tree, cfg, [length], pad_multiple=pages,
        most_outputs=mix["output"]["hi"], **cfg["reference"])
        for length in reference_lengths(mix)]
    return jobs


def check(ctx, reference, params, finished) -> None:
    """Served tokens against the plain reference, after the window."""
    cfg, mix, limits = ctx.config, ctx.traffic, ctx.config["limits"]
    done = [f for f in finished if f["ok"]]
    if not done:
        ctx.compare("requests finished in the window", 0, 1, at_most=False)
        return
    picked = sample(done, mix, ctx.seed)
    gaps, agree = reference.served_token_gaps(
        params, cfg, [done[i]["prompt"] for i in picked],
        [done[i]["output"] for i in picked],
        pad_multiple=cfg["serve"]["page_tokens"],
        lengths=reference_lengths(mix),
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

    # No Python frames in the locations a kernel is lowered with, so that
    # ``compile_ahead``'s programs are the scheduler's, key for key.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    cfg, sv, mix = ctx.config, ctx.config["serve"], ctx.traffic
    harness = cfg["harness"]
    model_cfg = OuroConfig.from_hf(
        cfg, dtype=jnp.dtype(cfg["precision"]["activations"]),
        q_block=sv["q_block"],
    )
    weights, reference = (
        importlib.import_module("benchmark." + harness[name])
        for name in ("weights", "reference"))
    serve_cfg = ServeConfig(
        page_tokens=sv["page_tokens"], max_batch=sv["max_batch"],
        max_pages=sv["max_pages"], max_seq=sv["max_seq"],
        ship_depth=sv["ship_depth"],
    )
    start, before = time.perf_counter(), dict(ctx.cache_events)
    pool = ThreadPoolExecutor(16)
    ahead = compile_ahead(pool, cfg, mix, model_cfg, serve_cfg,
                          lambda: weights.make_params(cfg, ctx.seed),
                          reference)
    with ctx.phase("weights"):
        params = weights.make_params(cfg, ctx.seed)
        jax.block_until_ready(params)
    with ctx.phase("server"):
        server = LoopServer(model_cfg, params, serve_cfg)
        sched = ContinuousBatchScheduler(server)
        prog = sched._prog
        bits = sorted({s.bits for layer in prog.streams for _, s in layer})
        held = {name: metrics.get(f"cgx.serve.kv.{name}") / 1e9
                for name in ("pool_bytes.global", "tail_bytes")}
        ctx.log(f"cache streams {list(prog.names)} on {server.n_layer} "
                f"layers x {prog.passes} passes, page bits as resolved by "
                f"the program: {bits}; a stream's pool holds "
                f"{sched._state['pools'][0]['k'][0].shape[0]} rows; pools "
                f"{held['pool_bytes.global']:.3f} GB, tails "
                f"{held['tail_bytes']:.3f} GB; step counters "
                f"{list(server.step_counters)}")
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
    with ctx.phase("warm-up"):
        for job in ahead:
            job.result()
        pool.shutdown()
        ctx.log(f"compiled ahead: {len(ahead)} jobs (the decode step, the "
                f"commit, the prefills, the reference's lengths) done "
                f"{time.perf_counter() - start:.1f} s after they were "
                f"started; compile cache {before} -> {ctx.cache_events}")
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
        # The step counters a per-layer reader wants summed over the traced
        # steps (the gates' exit mass a pass).
        for key, name in harness["traced_counters"].items():
            name = "cgx.serve." + name
            found["loop"][key] = (traced1.get(name, 0.0)
                                  - traced0.get(name, 0.0))
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
    ttfts = [t * 1e3 for t in loop.ttfts]  # requests sent in the window
    ctx.log(f"window {elapsed:.3f} s: {len(loop.finished)} requests "
            f"completed, {loop.tokens} tokens, {len(loop.gaps)} gaps, "
            f"{loop.occupancy_n} decode steps; request errors {errors}, "
            f"short answers {short}; peak memory "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")
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
        check(ctx, reference, params, finished)
    if ctx.trace:
        ctx.read_trace(found)
    return found
