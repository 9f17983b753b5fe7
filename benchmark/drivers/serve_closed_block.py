"""Closed-loop serving of a decoder that generates by diffusion over blocks (a
decode step runs a block of positions a lane and yields 0 tokens or a whole
block) through the program's ``BlockDiffusionServer``: ``serve_closed``'s
loop, warm-up and clock and ``serve_closed_window``'s sample (both loaded by
name, not copied) behind the same ``ContinuousBatchScheduler``, laid out as
``serve_closed_loop`` is.

What differs from one such model to the next is taken by name from the
configuration's ``harness`` block: ``weights`` and ``reference``, the modules
under ``benchmark/`` that draw the parameters from the seed and replay a
request's denoising steps, and ``traced_counters``, the step counters
``cgx.serve.<name>`` whose sum over the traced steps a per-layer reader
wants, by the key it reads. The model and its adapter are imported here, at
the top: a tree without them fails before anything is built.

``correct`` for a step that yields a block. The loop keeps every finished
request's tokens and each token's unmask step (the denoising step of its
block at which the server unmasked it). After the window, over a seeded
sample of the finished requests (the longest and some of the long prompts'
group among them) and in each a seeded sample of ``check_blocks`` whole
blocks (the first, which opens with the prompt's remainder, and the last
among them), the reference replays every denoising step as the server saw it
and two pairs of gaps are held to the cell's limits: ``served_gap``, by
which a served token's reference logit lies below the reference's best at
its position at the step it was unmasked, and ``unmask_gap``, by which the
reference's log-confidence at the position the server unmasked lies below
the reference's most confident masked position at that step. At least
``checked_positions`` positions a run, and no token dropped by the experts.

What a cold run compiles is compiled side by side, from shapes alone, while
the weights are drawn (:func:`compile_ahead`).
"""

from __future__ import annotations

import gc
import importlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from torch_cgx_tpu.models.sdar_moe import SdarMoeConfig
from torch_cgx_tpu.serving.block import BlockDiffusionServer

from benchmark import spec, traffic as traffic_mod

closed = spec.load_module("drivers", "serve_closed")
sample = spec.load_module("drivers", "serve_closed_window").sample


class BlockLoop(closed.ClosedLoop):
    """``serve_closed``'s loop; a finished request also keeps each of its
    tokens' unmask step. (The loop files a request as finished and at once
    sends the client's next: the request it replaces is the one just
    filed.)"""

    def _submit(self, client: int, first: bool) -> None:
        last = self.inflight.get(client)
        if last is not None and last[0].done and self.finished:
            self.finished[-1]["unmask_step"] = list(last[0].unmask_step)
        super()._submit(client, first)


def reference_lengths(mix, page_tokens: int) -> list:
    """The longest sequence of each prompt group, in whole pages: what the
    reference pads a sampled request's final tokens to."""
    return [-(-(g["hi"] + mix["output"]["hi"]) // page_tokens) * page_tokens
            for g in mix["prompt_groups"]]


def replayed_steps(cfg, mix) -> int:
    """Rows the reference's replay of one request is padded to: every
    denoising step of the sampled blocks."""
    return mix["check_blocks"] * cfg["denoising_steps"]


def compile_ahead(pool, cfg, mix, model_cfg, serve_cfg, make_params,
                  reference) -> list:
    """Jobs of ``pool`` that lower and compile, from shapes alone, what the
    run will call (``serve_closed_loop.compile_ahead`` says why it works):
    the block step, the commit, ``prefill_pages`` at every padded length of
    the mix, and the reference's pieces at the mix's sequence lengths."""
    import jax

    from torch_cgx_tpu.serving import programs, scheduler

    prog = scheduler._decode_program(
        BlockDiffusionServer(model_cfg, None, serve_cfg))
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
    jobs += [pool.submit(reference.compile_ahead, tree, cfg, length,
                         replayed_steps(cfg, mix), **cfg["reference"])
             for length in reference_lengths(mix, pages)]
    return jobs


def sampled_blocks(whole: int, want: int, rng) -> list:
    """``want`` of a request's ``whole`` blocks, the first and the last
    among them, in order."""
    inner = [int(b) for b in rng.permutation(np.arange(1, max(whole - 1, 1)))]
    ends = sorted({0, whole - 1})
    return sorted(ends + inner[: max(want - len(ends), 0)])


def check(ctx, reference, params, finished, dropped: float) -> None:
    """The served runs' denoising steps against the plain reference's
    replay, after the window, and the expert layers' dropped tokens."""
    import jax

    cfg, mix, limits = ctx.config, ctx.traffic, ctx.config["limits"]
    ctx.compare("moe_dropped", float(dropped), limits["moe_dropped"])
    done = [f for f in finished if f["ok"] and "unmask_step" in f]
    if not done:
        ctx.compare("requests finished in the window", 0, 1, at_most=False)
        return
    picked = sample(done, mix, ctx.seed)
    rng = np.random.default_rng(ctx.seed + 1)
    lengths = sorted(reference_lengths(mix, cfg["serve"]["page_tokens"]))
    served, unmask, agree = [], [], 0
    with jax.default_matmul_precision("highest"):
        for i in picked:
            req = done[i]
            whole = reference.whole_blocks(req["prompt"], req["output"], cfg)
            total = len(req["prompt"]) + len(req["output"])
            s, u, a = reference.replay_gaps(
                params, cfg, req["prompt"], req["output"], req["unmask_step"],
                sampled_blocks(whole, mix["check_blocks"], rng),
                length=next(n for n in lengths if n >= total),
                rows_to=replayed_steps(cfg, mix), **cfg["reference"])
            served.append(s)
            unmask.append(u)
            agree += a
    served, unmask = np.concatenate(served), np.concatenate(unmask)
    ctx.log(f"reference: {len(picked)} requests (prompts of "
            f"{sorted(len(done[i]['prompt']) for i in picked)}), "
            f"{served.size} checked positions, {100 * agree / served.size:.1f}"
            f"% of the served tokens are the reference's own choice; served "
            f"gap max {served.max():.5f} mean {served.mean():.6f} p99 "
            f"{np.percentile(served, 99):.5f}; unmask gap max "
            f"{unmask.max():.5f} mean {unmask.mean():.6f} p99 "
            f"{np.percentile(unmask, 99):.5f}, "
            f"{100 * np.mean(unmask == 0):.1f}% are 0")
    ctx.compare("checked_positions", int(served.size),
                limits["checked_positions"], at_most=False)
    for name, values in (("served_gap", served), ("unmask_gap", unmask)):
        ctx.compare(f"{name}_max", float(values.max()),
                    limits[f"{name}_max"])
        ctx.compare(f"{name}_mean", float(values.mean()),
                    limits[f"{name}_mean"])


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
    model_cfg = SdarMoeConfig.from_hf(
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
        server = BlockDiffusionServer(model_cfg, params, serve_cfg)
        sched = ContinuousBatchScheduler(server)
        prog = sched._prog
        bits = sorted({s.bits for layer in prog.streams for _, s in layer})
        ctx.log(f"cache streams {list(prog.names)} on {server.n_layer} "
                f"layers, a block of {prog.block} positions a lane a step, "
                f"{model_cfg.denoise_steps} denoising steps, threshold "
                f"{model_cfg.unmask_threshold}; page bits as resolved by the "
                f"program: {bits}; step counters "
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
    # Ids from the whole vocabulary but the mask id: the draw's few
    # occurrences of it become id 0.
    mix_traffic._tokens[mix_traffic._tokens == cfg["mask_token_id"]] = 0
    loop = BlockLoop(sched, mix_traffic, Request, metrics,
                     jax.profiler.TraceAnnotation)
    with ctx.phase("warm-up"):
        for job in ahead:
            job.result()
        pool.shutdown()
        ctx.log(f"compiled ahead: {len(ahead)} jobs (the block step, the "
                f"commit, the prefills, the reference's lengths) done "
                f"{time.perf_counter() - start:.1f} s after they were "
                f"started; compile cache {before} -> {ctx.cache_events}")
        closed.warm_up(ctx, sched, mix_traffic, Request, sv["page_tokens"],
                       sv["max_seq"], sv["max_batch"])
    with ctx.phase("ramp"):
        loop.start_clients()
        while any(slot[1] == 0 for slot in loop.inflight.values()):
            loop.tick()  # until every client has its first block
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
        # The traced steps, and the step counters a per-layer reader wants
        # summed over them (the experts they touched).
        for key, name in {"traced_decode_steps": "decode_steps",
                          **harness["traced_counters"]}.items():
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

    dropped = metrics.get("cgx.serve.moe.dropped")
    # The program's state goes before the reference runs, so that the peak
    # above stays the program's.
    del loop, sched, server, prog
    gc.collect()
    with ctx.phase("reference", excluded=True):
        check(ctx, reference, params, finished, dropped)
    if ctx.trace:
        ctx.read_trace(found)
    return found
