"""GPT-2 serving with a paged quantized KV cache — the serving-plane
composition example (ISSUE 15; docs/SERVING.md).

Disaggregated prefill/decode with continuous batching:

* a prefill worker thread computes each request's KV, cuts it into
  fixed-size pages, quantizes them under the ``kv_page`` wire edge
  (``CGX_KV_BITS`` / ``--bits``) and ships them over publish-after-write
  counter streams;
* the decode scheduler polls those streams without ever blocking,
  admits requests into a fixed lane batch as their pages land, gathers
  each lane's pages with the dequantize fused into the attention read,
  and greedy-decodes one token per lane per step;
* the optional SLO controller (``--ttft-slo-ms`` / ``--tps-slo``)
  re-solves the KV bit budget from the live metric stream.

Runs hermetically on CPU (synthetic prompts, randomly initialized tiny
GPT-2), and on a real chip with the same flags. Per-request outputs plus
tokens/s and TTFT print at the end — the same numbers ``bench.py
--serve`` commits as gated trajectories.

    python examples/serve_gpt2.py --requests 6 --gen 16 --bits 8
    python examples/serve_gpt2.py --local            # no transport hop
    python examples/serve_gpt2.py --kill-prefill 2   # failover demo
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(
        description="GPT-2 continuous-batching serving with quantized "
                    "paged KV"
    )
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--prompt", type=int, default=48,
                   help="synthetic prompt length (tokens)")
    p.add_argument("--gen", type=int, default=16,
                   help="tokens to generate per request")
    p.add_argument("--batch", type=int, default=4, help="decode lanes")
    p.add_argument("--page-tokens", type=int, default=16)
    p.add_argument("--bits", type=int, default=None,
                   help="KV page width (default: CGX_KV_BITS; 0 = raw "
                        "f16 shipping)")
    p.add_argument("--local", action="store_true",
                   help="colocated mode: no transport hop, the "
                        "scheduler prefills in-process")
    p.add_argument("--kill-prefill", type=int, default=None,
                   metavar="N",
                   help="kill the prefill worker after N requests — "
                        "the remaining streams stall and decode fails "
                        "over to local prefill (the recovery demo)")
    p.add_argument("--throttle-mbps", type=float, default=0.0,
                   help="model a bandwidth-bound prefill→decode wire "
                        "(0 = unthrottled)")
    p.add_argument("--ttft-slo-ms", type=float, default=0.0,
                   help="engage the SLO controller at this TTFT target")
    p.add_argument("--tps-slo", type=float, default=0.0,
                   help="engage the SLO controller at this tokens/s "
                        "target")
    p.add_argument("--model", choices=("tiny", "small"), default="tiny")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU platform (CI/laptop runs)")
    p.add_argument("--json", action="store_true",
                   help="print one JSON summary line (harness mode)")
    return p.parse_args()


class DictStore:
    """In-process c10d-Store look-alike for the single-host demo (a real
    deployment passes the group's TCP/File store here)."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self._d[k] = bytes(v)

    def get(self, k):
        with self._lock:
            if k not in self._d:
                raise KeyError(k)
            return self._d[k]

    def add(self, k, v):
        with self._lock:
            cur = int(self._d.get(k, b"0")) + int(v)
            self._d[k] = str(cur).encode()
            return cur

    def delete_key(self, k):
        with self._lock:
            self._d.pop(k, None)


def main():
    args = parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.bits is not None:
        os.environ["CGX_KV_BITS"] = str(args.bits)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
    from torch_cgx_tpu.serving import (
        ContinuousBatchScheduler, GPT2Server, KvPageReceiver, Request,
        ServeConfig, ServeSloController,
    )
    from torch_cgx_tpu.serving.prefill import PrefillWorker
    from torch_cgx_tpu.utils import entry
    from torch_cgx_tpu.utils.logging import metrics

    entry.setup_compile_cache()
    device = entry.require_accelerator(cpu_requested=args.cpu)
    print(f"device: {device}", file=sys.stderr)
    cfg = (
        GPT2Config.tiny() if args.model == "tiny" else GPT2Config.small()
    )
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    max_seq = args.prompt + args.gen + args.page_tokens
    serve_cfg = ServeConfig(
        page_tokens=args.page_tokens,
        max_batch=args.batch,
        max_pages=max(
            64, args.requests * (max_seq // args.page_tokens + 1)
        ),
        max_seq=max_seq,
        ship_depth=4,
    )
    server = GPT2Server(cfg, params, serve_cfg)

    rng = np.random.default_rng(0)
    requests = [
        Request(
            id=f"req{i}",
            tokens=[int(t) for t in
                    rng.integers(0, cfg.vocab_size, args.prompt)],
            max_new_tokens=args.gen,
        )
        for i in range(args.requests)
    ]

    store = DictStore()
    receiver = None if args.local else KvPageReceiver(store)
    sched = ContinuousBatchScheduler(server, receiver=receiver)
    slo = ServeSloController(
        ttft_slo_ms=args.ttft_slo_ms or None,
        tps_slo=args.tps_slo or None,
        every=20,
    )

    worker_thread = None
    worker = None
    t0 = time.perf_counter()
    if args.local:
        for r in requests:
            sched.submit(r)
    else:
        worker = PrefillWorker(
            server, store,
            throttle_gbps=(args.throttle_mbps / 1e3
                           if args.throttle_mbps else None),
        )
        for r in requests:
            sched.submit(r, remote=True)

        def run_prefill():
            for i, r in enumerate(requests):
                if (args.kill_prefill is not None
                        and i >= args.kill_prefill):
                    print(
                        f"[prefill] worker dying after {i} request(s) — "
                        "watch decode fail over, not wedge",
                        file=sys.stderr,
                    )
                    return  # simulated mid-stream death
                worker.serve(r.id, r.tokens)

        worker_thread = threading.Thread(target=run_prefill, daemon=True)
        worker_thread.start()

    deadline = time.monotonic() + 600.0
    while sched.outstanding() and time.monotonic() < deadline:
        if not sched.step():
            time.sleep(0.002)
        slo.step()
    wall = time.perf_counter() - t0
    if worker_thread is not None:
        worker_thread.join(timeout=30)
    if worker is not None:
        worker.stop()
    if sched.outstanding():
        print("ERROR: serving run left requests outstanding",
              file=sys.stderr)
        return 1

    tokens = sum(len(r.output) for r in requests)
    ttft = metrics.histogram_stats("cgx.serve.ttft_ms") or {}
    summary = {
        "requests": len(requests),
        "tokens": tokens,
        "device": device,
        "tokens_per_s": round(tokens / wall, 3),
        "ttft_p50_ms": round(ttft.get("p50", 0.0), 3),
        "ttft_p90_ms": round(ttft.get("p90", 0.0), 3),
        "kv_bits": int(os.environ.get("CGX_KV_BITS", "8") or 0),
        "prefill_failovers": int(
            metrics.get("cgx.serve.prefill_failovers")
        ),
        "pages_allocated": int(metrics.get("cgx.serve.pages_allocated")),
        "kv_bytes_wire": metrics.get("cgx.serve.kv_bytes_wire"),
        "slo_bits_budget": (
            slo.budget if slo.engaged else None
        ),
    }
    if args.json:
        print(json.dumps(summary))
        return 0
    for r in requests:
        head = " ".join(str(t) for t in r.output[:8])
        print(f"{r.id}: {len(r.output)} tokens [{head}"
              + (" ...]" if len(r.output) > 8 else "]"))
    print(json.dumps(summary, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
