"""Closed-loop serving: ``clients`` callers, each sends its next request when
the last one completes (evaluation harnesses, agent workers, batch jobs).

Drives the program's ``GPT2Server`` + ``ContinuousBatchScheduler`` with
local prefill, from one thread: ``sched.step()``, then the benchmark's own
bookkeeping reads ``len(req.output)`` of every request in flight, stamps
the new tokens, and hands a finished client its next request.

* set-up: weights from the seed on the device, the server and its pools,
  one warm-up request that fills a tail (the commit program), then the
  clients' first requests, which between them use every prompt length of the
  mix (each length has its own eager slice programs, each padded length its
  own prefill program; lengths the clients do not cover get a warm-up
  request of their own), then ``ramp_s`` more seconds of the mix, so that
  the lanes are full and out of step when the window opens;
* window: the loop, for ``--seconds``;
* check, after the window: a seeded sample of the requests the window
  finished, the longest among them, through the plain reference
  (:func:`benchmark.reference.served_token_gaps`).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import reference, traffic as traffic_mod, weights


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    return float(s[min(len(s) - 1, max(0, int(np.ceil(q / 100 * len(s))) - 1))])


class ClosedLoop:
    """The load generator and the benchmark's own clock on every token."""

    def __init__(self, sched, mix_traffic, request_cls, metrics, annotate):
        self.sched = sched
        self.traffic = mix_traffic
        self.Request = request_cls
        self.metrics = metrics
        self.annotate = annotate
        self.inflight = {}  # client -> [request, tokens seen, last stamp, asked]
        self.reset()

    def reset(self) -> None:
        self.since = time.monotonic()  # requests sent from now on have a TTFT
        self.gaps = []
        self.ttfts = []
        self.finished = []  # dicts of completed requests
        self.tokens = 0
        self.occupancy_sum = 0.0
        self.occupancy_n = 0
        self._decode_steps = self.metrics.get("cgx.serve.decode_steps")

    def _submit(self, client: int, first: bool) -> None:
        rid, prompt, out = self.traffic.next_request(client, first)
        req = self.Request(id=rid, tokens=prompt, max_new_tokens=out)
        self.sched.submit(req)
        self.inflight[client] = [req, 0, None, out]

    def start_clients(self) -> None:
        for c in range(self.traffic.clients):
            self._submit(c, first=True)

    def tick(self) -> None:
        with self.annotate("bench.step"):
            progressed = self.sched.step()
        now = time.monotonic()
        with self.annotate("bench.bookkeeping"):
            steps = self.metrics.get("cgx.serve.decode_steps")
            if steps != self._decode_steps:
                self._decode_steps = steps
                self.occupancy_sum += self.metrics.get(
                    "cgx.serve.batch_occupancy")
                self.occupancy_n += 1
            for client, slot in list(self.inflight.items()):
                req, seen, last, asked = slot
                n = len(req.output)
                if n < seen:  # evicted and requeued by the scheduler
                    seen, last = 0, None
                for j in range(seen, n):
                    stamp = req.first_token_at if j == 0 else now
                    if j == 0 and req.submitted_at >= self.since:
                        self.ttfts.append(stamp - req.submitted_at)
                    if last is not None:
                        self.gaps.append(stamp - last)
                    last = stamp
                    self.tokens += 1
                slot[1], slot[2] = n, last
                if req.done:
                    self.finished.append({
                        "prompt": req.tokens, "output": list(req.output),
                        "asked": asked, "ok": n == asked,
                    })
                    self._submit(client, first=False)
        if not progressed:
            time.sleep(0.0005)

    def run_for(self, seconds: float) -> float:
        """The loop for ``seconds``; returns the seconds it really took."""
        start = time.monotonic()
        while True:
            self.tick()
            now = time.monotonic()
            if now - start >= seconds:
                return now - start


def warm_up(ctx, sched, mix_traffic, request_cls, page_tokens: int,
            max_seq: int, max_batch: int) -> None:
    """The shapes that filling the lanes will not use."""
    every = traffic_mod.prompt_lengths(ctx.traffic)
    wanted = [(p, 2) for p in every if p not in mix_traffic.first_lengths]
    tail = max(every, key=lambda p: p % page_tokens)
    fill = page_tokens - tail % page_tokens + 2
    if tail + fill <= max_seq:
        wanted.append((tail, fill))  # a tail fills: the commit program
    for lo in range(0, len(wanted), max_batch):
        for i, (plen, out) in enumerate(wanted[lo: lo + max_batch]):
            sched.submit(request_cls(
                id=f"warm-{lo + i}", tokens=mix_traffic.warmup_prompt(plen),
                max_new_tokens=out,
            ))
        if not sched.run(deadline_s=280.0):
            raise SystemExit("benchmark: warm-up requests did not complete")


def check(ctx, params, finished) -> None:
    """Served tokens against the plain reference, after the window."""
    mix, limits = ctx.traffic, ctx.config["limits"]
    done = [f for f in finished if f["ok"]]
    if not done:
        ctx.compare("requests finished in the window", 0, 1, at_most=False)
        return
    rng = np.random.default_rng(ctx.seed)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["output"]))
    others = [i for i in rng.permutation(len(done)) if i != longest]
    sample = [longest] + [int(i) for i in others[: mix["check_requests"] - 1]]
    gaps, agree = reference.served_token_gaps(
        params, ctx.config, [done[i]["prompt"] for i in sample],
        [done[i]["output"] for i in sample],
        block_rows=mix["check_block_rows"],
        longest=max(g["hi"] for g in mix["prompt_groups"])
        + mix["output"]["hi"] - 1,
        most_outputs=mix["output"]["hi"],
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

    from torch_cgx_tpu.models.gpt2 import GPT2Config
    from torch_cgx_tpu.serving import (
        ContinuousBatchScheduler, GPT2Server, Request, ServeConfig,
    )
    from torch_cgx_tpu.utils.logging import metrics

    from benchmark import trace_reduce

    cfg, sv, mix = ctx.config, ctx.config["serve"], ctx.traffic
    with ctx.phase("weights"):
        params = weights.make_params(cfg, ctx.seed)
        jax.block_until_ready(params)
    with ctx.phase("server"):
        model_cfg = GPT2Config(
            vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_model=cfg["n_embd"],
            max_seq=cfg["n_positions"],
        )
        serve_cfg = ServeConfig(
            page_tokens=sv["page_tokens"], max_batch=sv["max_batch"],
            max_pages=sv["max_pages"], max_seq=sv["max_seq"],
            ship_depth=sv["ship_depth"],
        )
        server = GPT2Server(model_cfg, params, serve_cfg)
        sched = ContinuousBatchScheduler(server)
        bits = sorted({s.bits for s in sched._prog.specs})
        ctx.log(f"kv page bits as resolved by the program: {bits}")
        if bits != [cfg["precision"]["kv_page_bits"]]:
            raise SystemExit(
                f"benchmark: the program resolved kv page bits {bits}, the "
                f"configuration states {cfg['precision']['kv_page_bits']}"
            )
    # Host spans around the scheduler's two halves. In every run, traced or
    # not: a Pallas kernel's compile-cache key holds the Python call stack
    # it was traced under, so a wrapper in traced runs only would compile
    # the decode, commit and page-quantize programs a second time.
    for name, label in (("_admit", "bench.admit/prefill"),
                        ("_decode", "bench.decode")):
        inner = getattr(sched, name)

        def spanned(inner=inner, label=label):
            with jax.profiler.TraceAnnotation(label):
                return inner()

        setattr(sched, name, spanned)
    mix_traffic = traffic_mod.ServeTraffic(mix, ctx.seed, cfg["vocab_size"])
    loop = ClosedLoop(sched, mix_traffic, Request, metrics,
                      jax.profiler.TraceAnnotation)
    with ctx.phase("warm-up"):
        warm_up(ctx, sched, mix_traffic, Request, sv["page_tokens"],
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
                         itl_p95_ms=percentile(loop.gaps, 95) * 1e3
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
            f"short answers {short}")
    if not ctx.trace:
        if len(ttfts) < 2 or not loop.gaps:
            raise SystemExit("benchmark: the window finished too few "
                             "requests to report a tail")
        ctx.log(f"ttft ms: n {len(ttfts)}, median {percentile(ttfts, 50):.2f}"
                f", p90 {percentile(ttfts, 90):.2f}; gap ms: n "
                f"{len(loop.gaps)}, median "
                f"{percentile(loop.gaps, 50) * 1e3:.2f}, p95 "
                f"{percentile(loop.gaps, 95) * 1e3:.2f}")
        found["end_to_end"] = {
            "serve_tokens_per_s": loop.tokens / elapsed,
            "serve_ttft_p90_ms": percentile(ttfts, 90),
            "serve_itl_p50_ms": percentile(loop.gaps, 50) * 1e3,
        }

    # The program's state goes before the reference runs, so that the peak
    # above stays the program's.
    del loop, sched, server
    gc.collect()
    with ctx.phase("reference", excluded=True):
        check(ctx, params, finished)
    if ctx.trace:
        ctx.read_trace(found)
    return found
