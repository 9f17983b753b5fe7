"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the library's default TPU path once, at GPT-2 124M width, through the
entry points a user calls, and checks what comes out by the repo's own means:

* **train** — ``make_train_step`` + ``replicate`` + ``shard_batch`` on a
  ``flat_mesh()`` over every visible chip: adam, 4 bits / bucket 512,
  8 x 512 tokens per chip, one compile step and six more; then the
  fabric-off step (plain ``psum``) and the same per-device gradients through
  ``gradient_sync`` with the fabric on and off.
* **serve** — ``GPT2Server`` + ``ContinuousBatchScheduler`` in the example's
  disaggregated shape (``PrefillWorker`` thread -> ``KvPageReceiver``):
  eight requests of 192 prompt tokens and 32 new tokens at the default
  8-bit KV pages.

Every ``CGX_*`` variable is dropped from the children's environment, so
``auto`` decides everywhere; on one chip the train phase sets
``CGX_DEBUG_FORCE_CODEC=1`` (a one-device sync axis otherwise returns the
gradients untouched and no codec kernel would be in the step).

One process owns a chip: this parent never imports ``jax``; each phase is a
child process, run one after the other. The run fails — non-zero exit, no
result line — if any check fails, any phase raises, or the platform is not
``tpu`` (an inherited ``JAX_PLATFORMS=cpu`` included). Wall-clock figures it
prints are set-up evidence, not a benchmark. The last two lines of standard
output are the summary (every phase's checks, counters and smoke timings,
ending ``"claim": null``) and then the result, one JSON object with exactly
these keys::

    [chip_smoke] summary {"phases": {...}, "claim": null}
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

``--rehearse-cpu [--devices N]`` runs the same code at a tiny size on the
CPU backend (the sandbox has no chip). It says ``cpu`` in its output, skips
the Mosaic check, keeps any ``CGX_*`` the caller set, and is not what the
driver runs.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PHASE_TIMEOUT_S = {"train": 780, "serve": 360}  # the whole run: < 1200 s
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Parent: no jax here.
# ---------------------------------------------------------------------------


def parent(argv) -> int:
    rehearse = "--rehearse-cpu" in argv
    if not os.path.isdir(os.path.join(REPO, "torch_cgx_tpu")):
        print("chip_smoke: torch_cgx_tpu/ is not beside this script — "
              "nothing to run", file=sys.stderr)
        return 2
    env = dict(os.environ)
    if rehearse:
        n = argv[argv.index("--devices") + 1] if "--devices" in argv else "1"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(n)}"
        )
        log("REHEARSAL on the cpu backend at a tiny size — not the chip run")
    else:
        dropped = sorted(k for k in env if k.startswith("CGX_"))
        for k in dropped:
            del env[k]
        if dropped:
            log(f"dropped from the children's environment: {dropped}")
    phases = {}
    device = None
    for phase in ("train", "serve"):
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
        if rehearse:
            cmd.append("--rehearse-cpu")
        log(f"phase {phase}: starting child")
        t0 = time.monotonic()
        proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        )
        try:
            out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S[phase])
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            print(f"chip_smoke: phase {phase} exceeded "
                  f"{PHASE_TIMEOUT_S[phase]} s and was killed",
                  file=sys.stderr)
            return 1
        result = None
        for line in out.splitlines():
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                print(line, flush=True)
        if proc.returncode != 0 or result is None or not result.get("ok"):
            print(f"chip_smoke: phase {phase} FAILED (exit code "
                  f"{proc.returncode})", file=sys.stderr)
            return 1
        device = result["device"]
        result["wall_s"] = round(time.monotonic() - t0, 1)
        phases[phase] = result
        log(f"phase {phase}: ok in {result['wall_s']} s")
    log("summary " + json.dumps({"phases": phases, "claim": None}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Children: each owns the chip for its lifetime.
# ---------------------------------------------------------------------------


class Checks:
    def __init__(self):
        self.rows = []

    def add(self, name: str, ok: bool, value=None) -> None:
        self.rows.append({"check": name, "ok": bool(ok), "value": value})
        log(f"  check {'PASS' if ok else 'FAIL'}  {name}: {value}")

    @property
    def ok(self) -> bool:
        return all(r["ok"] for r in self.rows)


def _open_child(rehearse: bool):
    """Common child set-up: compile cache, device gate, cache counters."""
    sys.path.insert(0, REPO)
    import collections

    import jax
    from jax import monitoring

    from torch_cgx_tpu.utils import entry

    cache_dir = entry.setup_compile_cache()
    cache_events = collections.Counter()

    def on_event(event, **_):
        if "/compilation_cache/" in event:
            cache_events[event.rsplit("/", 1)[1]] += 1

    monitoring.register_event_listener(on_event)
    device = entry.device_summary()
    log(f"  device: {device}  jax {jax.__version__}  "
        f"compile cache: {cache_dir}")
    if device["platform"] != "tpu" and not rehearse:
        raise SystemExit(
            f"chip_smoke: platform is {device['platform']!r}, not tpu "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    return device, cache_dir, cache_events


def _counters(*prefixes: str) -> dict:
    from torch_cgx_tpu.utils.logging import metrics

    return {
        k: v for k, v in sorted(metrics.snapshot().items())
        if k.startswith(prefixes)
    }


def _finish(result: dict, checks: Checks) -> int:
    result["checks"] = checks.rows
    result["ok"] = checks.ok
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0 if checks.ok else 1


def phase_train(rehearse: bool) -> int:
    device, cache_dir, cache_events = _open_child(rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
    from torch_cgx_tpu.parallel import (
        flat_mesh, gradient_sync, make_train_step, replicate, shard_batch,
    )
    from torch_cgx_tpu.parallel import planner, topology
    from torch_cgx_tpu.utils.compat import shard_map

    fabric_on = {
        "CGX_COMPRESSION_QUANTIZATION_BITS": "4",
        "CGX_COMPRESSION_BUCKET_SIZE": "512",
    }
    n_dev = device["count"]
    if n_dev == 1:
        fabric_on["CGX_DEBUG_FORCE_CODEC"] = "1"

    def set_fabric(on: bool) -> None:
        for k, v in fabric_on.items():
            if on:
                os.environ[k] = v
            else:
                os.environ.pop(k, None)

    if rehearse:
        cfg, per_chip, seq, steps = GPT2Config.tiny(max_seq=64), 2, 64, 3
    else:
        cfg = GPT2Config(n_layer=12, n_head=12, d_model=768,
                         vocab_size=50257, max_seq=512)
        per_chip, seq, steps = 8, 512, 6
    mesh = flat_mesh()
    set_fabric(True)
    decision = topology.route(mesh, ("dp",))
    log(f"  route: {decision.route} ({decision.reason}); "
        f"planner model: {planner.cost_model().source}")

    model = GPT2(cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(per_chip * n_dev, seq)
    ).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:1]))[
        "params"
    ]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    opt = optax.adam(1e-4)
    batch = shard_batch(jnp.asarray(tokens), mesh)

    def loss_fn(p, b):
        return lm_loss(model.apply({"params": p}, b), b)

    def fresh():
        # Deep copy: donation would otherwise alias the source buffers.
        p = replicate(jax.tree.map(jnp.array, params), mesh)
        return p, replicate(opt.init(params), mesh)

    checks = Checks()

    # -- fabric on: lower (what was staged), compile step, six more --------
    step_on = make_train_step(loss_fn, opt, mesh)
    p_on, s_on = fresh()
    t0 = time.perf_counter()
    text = step_on.lower(p_on, s_on, batch, jnp.int32(0)).as_text()
    lower_s = time.perf_counter() - t0
    staged = {
        "mosaic_custom_calls": text.count("tpu_custom_call"),
        "all_to_all": text.count("all_to_all"),
        "all_gather": text.count("all_gather"),
        "all_reduce": text.count("all_reduce"),
        "kernels": {
            name: text.count(name) for name in (
                "cgx_quantize_flat", "cgx_dequantize_flat",
                "cgx_quantize_chunks", "cgx_dequantize_chunks",
                "cgx_sra_epilogue", "cgx_reduce_rows",
                "cgx_matmul_quantize",
            ) if name in text
        },
    }
    del text
    t0 = time.perf_counter()
    p_on, s_on, loss = step_on(p_on, s_on, batch, jnp.int32(0))
    loss_on0 = float(loss)
    first_call_s = time.perf_counter() - t0
    step_s, losses = [], [loss_on0]
    for i in range(1, steps + 1):
        t0 = time.perf_counter()
        p_on, s_on, loss = step_on(p_on, s_on, batch, jnp.int32(i))
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
    step_med = sorted(step_s)[len(step_s) // 2]
    engaged = _counters(
        "cgx.xla.", "cgx.sched.", "cgx.plan.", "cgx.codec.producer_",
        "cgx.codec.lowering.", "cgx.trace.allreduce.",
    )
    log(f"  staged in the lowered step: {staged}")
    log(f"  engaged (trace-time counters): {engaged}")
    log(f"  smoke timing, not a benchmark: lower {lower_s:.1f} s, first "
        f"call {first_call_s:.1f} s (compile ~{first_call_s - step_med:.1f}"
        f" s), step median {step_med * 1e3:.1f} ms over {steps}; compile "
        f"cache events {dict(cache_events)}")
    checks.add("train steps run (1 compile + more)", len(losses) >= steps + 1,
               len(losses))
    checks.add("fabric-on loss finite at every step",
               bool(np.isfinite(losses).all()),
               [round(x, 4) for x in losses])
    if rehearse:
        log("  Mosaic check skipped: cpu rehearsal")
    else:
        checks.add("Mosaic custom call in the lowered step (no interpret "
                   "mode)", staged["mosaic_custom_calls"] > 0,
                   staged["mosaic_custom_calls"])
    if n_dev > 1:
        checks.add("collectives in the lowered step",
                   staged["all_to_all"] > 0 and staged["all_gather"] > 0,
                   {k: staged[k] for k in
                    ("all_to_all", "all_gather", "all_reduce")})
        if not rehearse:
            checks.add("route is staged", decision.route == "staged",
                       decision.route)
        same = True
        for leaf in jax.tree.leaves(p_on):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            same &= all(
                s.tobytes() == shards[0].tobytes() for s in shards[1:]
            )
        checks.add(f"updated params bit-identical on all {n_dev} devices",
                   same, same)
    mem = [
        {k: (d.memory_stats() or {}).get(k) for k in
         ("bytes_in_use", "peak_bytes_in_use")}
        for d in jax.devices()
    ]
    if not rehearse:
        floor = 4 * n_params  # each replica holds at least the f32 params
        checks.add("work resident on every device (peak bytes >= params)",
                   all((m["peak_bytes_in_use"] or 0) >= floor for m in mem),
                   mem)
    del p_on, s_on

    # -- fabric off: plain psum -------------------------------------------
    set_fabric(False)
    step_off = make_train_step(loss_fn, opt, mesh)
    p_off, s_off = fresh()
    t0 = time.perf_counter()
    p_off, s_off, loss = step_off(p_off, s_off, batch, jnp.int32(0))
    loss_off0 = float(loss)
    off_first_call_s = time.perf_counter() - t0
    del p_off, s_off
    checks.add("fabric-off loss finite", bool(np.isfinite(loss_off0)),
               round(loss_off0, 4))
    rel = abs(loss_on0 - loss_off0) / abs(loss_off0)
    checks.add("step-0 loss on vs off within bf16 tolerance (1e-2)",
               rel < 1e-2, f"{loss_on0:.5f} vs {loss_off0:.5f}")

    # -- the same gradients through gradient_sync, fabric on and off ------
    p_ref = replicate(params, mesh)
    per_dev = jax.jit(shard_map(
        lambda p, b: jax.tree.map(
            lambda g: g[None], jax.grad(loss_fn)(p, b)
        ),
        mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp"),
        check_vma=False,
    ))(p_ref, batch)

    def synced():
        return jax.jit(shard_map(
            lambda g: gradient_sync(
                jax.tree.map(lambda x: x[0], g), mesh=mesh
            ),
            mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=False,
        ))(per_dev)

    set_fabric(True)
    red_on = synced()
    set_fabric(False)
    red_off = synced()

    @jax.jit
    def compare(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        num = sum(jnp.sum((x - y) ** 2) for x, y in zip(la, lb))
        den = sum(jnp.sum(y ** 2) for y in lb)
        differ = sum(jnp.sum(x != y) for x, y in zip(la, lb))
        return jnp.sqrt(num / den), differ

    rel_l2, differ = compare(red_on, red_off)
    rel_l2, differ = float(rel_l2), int(differ)
    checks.add("gradient_sync on vs off: relative L2 < 0.2 (4-bit "
               "envelope)", rel_l2 < 0.2, round(rel_l2, 5))
    checks.add("gradient_sync on is not bit-equal to off (the codec ran)",
               differ > 0, f"{differ} of {n_params} values differ")

    return _finish({
        "device": device,
        "model": f"GPT-2 {n_params / 1e6:.1f}M "
                 f"(L{cfg.n_layer} H{cfg.n_head} d{cfg.d_model} "
                 f"vocab {cfg.vocab_size})",
        "batch": f"{per_chip} x {seq} tokens per chip, {n_dev} chip(s)",
        "fabric_on_env": fabric_on,
        "route": decision.route,
        "planner_model": planner.cost_model().source,
        "staged": staged,
        "engaged": engaged,
        "memory_stats": mem,
        "smoke_timing_not_benchmark": {
            "lower_s": round(lower_s, 2),
            "first_call_s": round(first_call_s, 2),
            "compile_s_approx": round(first_call_s - step_med, 2),
            "step_ms_median": round(step_med * 1e3, 2),
            "step_ms_all": [round(x * 1e3, 2) for x in step_s],
            "fabric_off_first_call_s": round(off_first_call_s, 2),
        },
        "compile_cache": {"dir": cache_dir, "events": dict(cache_events)},
    }, checks)


def phase_serve(rehearse: bool) -> int:
    device, cache_dir, cache_events = _open_child(rehearse)
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from examples.serve_gpt2 import DictStore
    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
    from torch_cgx_tpu.parallel import planner
    from torch_cgx_tpu.runtime import native
    from torch_cgx_tpu.serving import (
        ContinuousBatchScheduler, GPT2Server, KvPageReceiver, Request,
        ServeConfig,
    )
    from torch_cgx_tpu.serving.prefill import PrefillWorker
    from torch_cgx_tpu.utils.logging import metrics

    if rehearse:
        cfg, n_req, prompt_len, gen = GPT2Config.tiny(), 4, 48, 8
    else:
        cfg, n_req, prompt_len, gen = GPT2Config.small(), 8, 192, 32
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    serve_cfg = ServeConfig.from_env(cfg)
    server = GPT2Server(cfg, params, serve_cfg)
    host_codec = native.status()
    page_values = serve_cfg.page_tokens * cfg.d_model
    log(f"  {serve_cfg} (page size and ship depth solved by the planner "
        f"from its {planner.cost_model().source!r} model); kv bits "
        f"{cgx_config.kv_bits()}; a page is {page_values} values = "
        f"{page_values // 512} buckets of 512 (the codec kernels cover "
        f"whole 32-bucket chunks)")
    log(f"  host codec: {host_codec}")
    log(f"  devices used: 1 of {device['count']} — GPT2Server places "
        f"nothing, so everything lives on device 0")

    rng = np.random.default_rng(0)

    def request(name: str, new_tokens: int) -> Request:
        return Request(
            id=name, max_new_tokens=new_tokens,
            tokens=[int(t) for t in
                    rng.integers(0, cfg.vocab_size, prompt_len)],
        )

    store = DictStore()
    sched = ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    checks = Checks()

    # A server compiles before it takes traffic (the failover clock of a
    # remote stream starts at submit and is shorter than a cold compile):
    # one LOCAL request through the scheduler builds the local prefill, lane
    # write and decode programs, and one stream shipped BEFORE its submit
    # builds the two only the disaggregated path runs, the worker's forward
    # and the scheduler's ingest.
    worker = PrefillWorker(server, store)
    t0 = time.perf_counter()
    sched.submit(request("warmup", 2))
    shipped = request("warmup-shipped", 2)
    worker.serve(shipped.id, shipped.tokens)
    sched.submit(shipped, remote=True)
    warm_ok = sched.run(deadline_s=300.0)
    warmup_s = time.perf_counter() - t0
    checks.add("warm-up requests (one local, one shipped: compile the "
               "programs)", warm_ok, f"{warmup_s:.1f} s")

    requests = [request(f"req{i}", gen) for i in range(n_req)]
    t0 = time.perf_counter()
    for r in requests:
        sched.submit(r, remote=True)
    prefill_errors = []

    def run_prefill():
        try:
            for r in requests:
                worker.serve(r.id, r.tokens)
        except BaseException as e:  # surfaced as a failed check below
            prefill_errors.append(repr(e))
            raise

    thread = threading.Thread(target=run_prefill, daemon=True)
    thread.start()
    deadline = time.monotonic() + 240.0
    while sched.outstanding() and time.monotonic() < deadline:
        if not sched.step():
            time.sleep(0.002)
    serve_s = time.perf_counter() - t0
    thread.join(timeout=30)
    worker.stop()

    counters = {
        name: int(metrics.get(f"cgx.serve.{name}")) for name in (
            "prefill_failovers", "ingest_errors", "request_errors",
            "local_prefills", "prefills_shipped", "pages_ingested",
            "pages_committed", "decode_steps", "tokens_generated",
        )
    }
    checks.add("prefill worker thread finished without error",
               not prefill_errors and not thread.is_alive(), prefill_errors)
    checks.add(f"{n_req} / {n_req} requests complete",
               not sched.outstanding() and all(r.done for r in requests),
               sum(r.done for r in requests))
    checks.add(f"every request returned {gen} tokens",
               all(len(r.output) == gen for r in requests),
               [len(r.output) for r in requests])
    checks.add("cgx.serve.prefill_failovers == 0",
               counters["prefill_failovers"] == 0,
               counters["prefill_failovers"])
    checks.add("cgx.serve.ingest_errors == 0",
               counters["ingest_errors"] == 0, counters["ingest_errors"])
    checks.add("only the local warm-up prefilled locally",
               counters["local_prefills"] == 1
               and counters["prefills_shipped"] == n_req + 1,
               {k: counters[k] for k in
                ("local_prefills", "prefills_shipped")})

    # Prefill logits at the last prompt position vs a plain GPT2.apply.
    prompt = np.asarray(requests[0].tokens, np.int32)
    pad = -len(prompt) % serve_cfg.page_tokens
    padded = np.pad(prompt, (0, pad))
    # Params are an argument, as in the server's own programs: closed over,
    # they would be ~500 MB of constants inside the executable.
    logits = jax.jit(
        lambda p, t, pos, last: GPT2Server(cfg, p, serve_cfg).prefill_forward(
            t, pos, last
        )[0]
    )(server.p, padded[None], np.arange(len(padded), dtype=np.int32)[None],
      np.int32(len(prompt) - 1))
    ref = model.apply(params, jnp.asarray(prompt[None]), train=False)[:, -1]
    logits, ref = np.asarray(logits, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(logits - ref)) / np.max(np.abs(ref)))
    checks.add("prefill logits finite, shape (1, vocab)",
               logits.shape == (1, cfg.vocab_size)
               and bool(np.isfinite(logits).all()), logits.shape)
    checks.add("prefill logits agree with GPT2.apply (max |diff| / max "
               "|ref| < 3e-2, bf16)", err < 3e-2, round(err, 5))

    lowering = _counters("cgx.codec.lowering.")
    log(f"  codec lowerings (trace-time, per call site): {lowering}")
    log(f"  smoke timing, not a benchmark: warm-up {warmup_s:.1f} s, "
        f"{n_req} requests in {serve_s:.2f} s; compile cache events "
        f"{dict(cache_events)}")
    return _finish({
        "device": device,
        "devices_used": 1,
        "model": f"GPT-2 L{cfg.n_layer} H{cfg.n_head} d{cfg.d_model} "
                 f"vocab {cfg.vocab_size}",
        "traffic": f"{n_req} requests, {prompt_len} prompt + {gen} new "
                   f"tokens, disaggregated prefill",
        "serve_config": {
            "page_tokens": serve_cfg.page_tokens,
            "max_batch": serve_cfg.max_batch,
            "max_pages": serve_cfg.max_pages,
            "max_seq": serve_cfg.max_seq,
            "ship_depth": serve_cfg.ship_depth,
            "planner_model": planner.cost_model().source,
        },
        "kv_bits": cgx_config.kv_bits(),
        "page_buckets": page_values // 512,
        "host_codec": host_codec,
        "counters": counters,
        "codec_lowering": lowering,
        "smoke_timing_not_benchmark": {
            "warmup_s": round(warmup_s, 2),
            "serve_s": round(serve_s, 2),
        },
        "compile_cache": {"dir": cache_dir, "events": dict(cache_events)},
    }, checks)


def main(argv) -> int:
    if "--phase" in argv:
        phase = argv[argv.index("--phase") + 1]
        run = {"train": phase_train, "serve": phase_serve}[phase]
        return run("--rehearse-cpu" in argv)
    return parent(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
