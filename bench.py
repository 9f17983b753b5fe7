"""Benchmark harness — prints ONE JSON line.

Adaptive to available hardware:

* single device (the driver's real-TPU run): fused Pallas codec throughput
  (quantize and dequantize timed separately, plus ``pct_hbm_roofline``
  against the chip's HBM bandwidth) and a north-star proxy — a jitted
  GPT-2 train step with the codec round trip on its gradients vs the plain
  step, bounding the achievable compressed-DP speedup (BASELINE.md).
  ``vs_baseline`` = XLA-codec round-trip time / Pallas round-trip time.
* multi-device: quantized 4-bit SRA allreduce of a 64 MB fp32 gradient
  buffer vs XLA's native fp32 ``psum``; ``vs_baseline`` = fp32-psum time /
  quantized time (>1 = faster than fp32).

Timing methodology: per-dispatch overhead can exceed most ops measured
here, so every single-device number uses a *slope* method: run K operand
sets through ``lax.scan`` inside one jit and report (t_K - t_1)/(K - 1)
(per-call wall clock measures dispatch latency, not the op).

A lint pre-flight (tools/lint.py) aborts the bench if any undefined name is
present — a broken hot path must fail loudly here, not measure garbage
(VERDICT r2 #2).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from torch_cgx_tpu.utils.compat import shard_map

BITS = 4
BUCKET = 512

# HBM bandwidth per chip generation (GB/s) — jax-ml.github.io/scaling-book.
HBM_GBPS = {
    "TPU v3": 900.0,
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}


BENCH_LOG = Path(__file__).resolve().parent / "BENCH_LOG.jsonl"


def log_jsonl(record: dict) -> None:
    """Append a structured perf record to BENCH_LOG.jsonl so
    round-over-round performance is diffable as data, not prose."""
    rec = dict(record)
    rec.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%S"))
    # Counter context rides along with every perf row: which paths ran,
    # how many elements traveled compressed vs raw, any faults — the BENCH
    # trajectory is then diffable against the registry, not just wall
    # clock. Never let the snapshot break (or bloat) the record itself.
    try:
        from torch_cgx_tpu.utils.logging import metrics as _metrics

        snap = _metrics.snapshot()
        if snap and "metrics" not in rec:
            rec["metrics"] = snap
    except Exception:
        pass
    # Memory trajectory (ISSUE 18): the ledger's peak-bytes high-water
    # rides on every record when CGX_MEMLEDGER is on, so bench_gate can
    # fail a memory regression exactly like a throughput regression
    # (the <metric>:peak_mb trajectory). None/off = no key, no gate.
    try:
        from torch_cgx_tpu.observability import memledger as _memledger

        pk = _memledger.peak_mb()
        if pk is not None and pk > 0 and "peak_mb" not in rec:
            rec["peak_mb"] = pk
    except Exception:
        pass
    # NOT setdefault: its default argument evaluates eagerly, which would
    # probe jax.devices() even when the caller pre-filled the keys.
    try:
        if "chip" not in rec:
            rec["chip"] = jax.devices()[0].device_kind
        if "backend" not in rec:
            rec["backend"] = jax.default_backend()
    except Exception:
        pass  # never let logging break (or hang) the measurement itself
    try:
        with open(BENCH_LOG, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass


def _preflight_lint() -> None:
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "tools" / "lint.py")],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        print(json.dumps({
            "metric": "lint_failure",
            "value": 0,
            "unit": "findings",
            "vs_baseline": 0,
            "detail": {"findings": proc.stdout.strip().splitlines()[:20]},
        }))
        sys.exit(1)


def _chip() -> tuple[str, float]:
    kind = jax.devices()[0].device_kind
    bw = next((v for k, v in HBM_GBPS.items() if k in kind), 0.0)
    return kind, bw


def scan_time(fn, stack, iters: int = 6) -> float:
    """Marginal per-execution seconds: slope between a K-length and a
    1-length scan over stacked operand sets (dispatch overhead cancels)."""

    def runner(s):
        def body(c, x):
            out = fn(x)
            leaf = jax.tree.leaves(out)[0]
            return c + leaf.ravel()[0].astype(jnp.float32), 0

        return lax.scan(body, jnp.float32(0), s)[0]

    jr = jax.jit(runner)

    def timed(s):
        np.asarray(jr(s))  # warm + sync
        t0 = time.perf_counter()
        for _ in range(iters):
            o = jr(s)
        np.asarray(o)
        return (time.perf_counter() - t0) / iters

    k = jax.tree.leaves(stack)[0].shape[0]
    t_k = timed(stack)
    t_1 = timed(jax.tree.map(lambda a: a[:1], stack))
    return max((t_k - t_1) / (k - 1), 1e-9)


def bench_codec(on_tpu: bool) -> dict:
    from torch_cgx_tpu.ops import codec, codec_pallas

    # 512 MB on real hardware so the op dwarfs noise; small in interpret
    # mode (CPU fallback) where the Pallas path runs in pure Python.
    n = 128 * 1024 * 1024 if on_tpu else 1024 * 1024
    k = 4 if on_tpu else 2
    # Generate operands on-device: a device-side PRNG draw moves no bytes
    # (2 GB of host-generated data would be a slow host-to-device copy).
    stack = jax.jit(
        lambda key: jax.random.normal(key, (k, 1, n), jnp.float32)
    )(jax.random.PRNGKey(1))
    stack.block_until_ready()

    def q_pallas(x):
        q = codec_pallas.quantize_batch(
            x, BITS, BUCKET, stochastic=False, interpret=not on_tpu
        )
        return (q.packed, q.meta)

    def q_xla(x):
        q = jax.vmap(lambda r: codec.quantize(r, BITS, BUCKET))(x)
        return (q.packed, q.meta)

    # genuinely distinct payloads per scan slot
    qts = [
        codec_pallas.quantize_batch(
            stack[i], BITS, BUCKET, interpret=not on_tpu
        )
        for i in range(k)
    ]
    q_stack = jax.tree.map(
        lambda *xs: jnp.stack(xs) if isinstance(xs[0], jax.Array) else xs[0],
        *qts,
    )

    def d_pallas(q):
        return codec_pallas.dequantize_batch(
            q, out_dtype=jnp.float32, interpret=not on_tpu
        )

    def d_xla(q):
        return jax.vmap(
            lambda qq: codec.dequantize(qq, out_dtype=jnp.float32)
        )(q)

    tpq = scan_time(q_pallas, stack)
    tpd = scan_time(d_pallas, q_stack)
    txq = scan_time(q_xla, stack)
    txd = scan_time(d_xla, q_stack)

    gbytes = n * 4 / 1e9
    nb = n // BUCKET
    # Actual HBM traffic: quantize reads 4n, writes n*bits/8 payload +
    # 8*nb meta; dequantize is the mirror image.
    moved = (n * 4 + n * BITS / 8 + nb * 8) / 1e9
    chip, hbm = _chip()
    tp, tx = tpq + tpd, txq + txd

    def pct(t):
        return round(moved / t / hbm * 100, 1) if hbm else None

    return {
        "metric": f"pallas_codec_{BITS}bit_{n * 4 // 2**20}MB_roundtrip",
        "value": round(gbytes / tp, 3),
        "unit": "GB/s",
        "vs_baseline": round(tx / tp, 3),
        "detail": {
            "quantize_GBps": round(gbytes / tpq, 1),
            "dequantize_GBps": round(gbytes / tpd, 1),
            "quantize_pct_hbm_roofline": pct(tpq),
            "dequantize_pct_hbm_roofline": pct(tpd),
            "t_pallas_quantize_ms": round(tpq * 1e3, 3),
            "t_pallas_dequantize_ms": round(tpd * 1e3, 3),
            "t_xla_quantize_ms": round(txq * 1e3, 3),
            "t_xla_dequantize_ms": round(txd * 1e3, 3),
            "chip": chip,
            "hbm_GBps": hbm,
            "timing": "scan-slope (dispatch overhead cancelled)",
        },
    }


def bench_sra_epilogue(on_tpu: bool, ws: int = 8) -> dict:
    """Staged vs fused SRA epilogue: the dequantize-accumulate-requantize
    of the ws peer payloads a rank runs between the all_to_all and the
    all_gather (the second codec round trip of PERF_NOTES.md's round-5
    analysis). The staged form materializes the decoded (ws, chunk) f32
    rows in HBM and re-reads them through an XLA select/sum and a separate
    quantize kernel; the fused Pallas kernel does all of it in one HBM
    pass. Both produce bit-identical wire bytes (asserted before timing)."""
    from torch_cgx_tpu.ops import codec_pallas, dispatch

    total = 128 * 1024 * 1024 if on_tpu else 256 * 1024
    chunk = total // ws
    k = 4 if on_tpu else 2
    own = jnp.int32(ws // 2)
    stack = jax.jit(
        lambda key: jax.random.normal(key, (k, ws, chunk), jnp.float32)
    )(jax.random.PRNGKey(2))
    stack.block_until_ready()
    qts = [
        codec_pallas.quantize_batch(stack[i], BITS, BUCKET, interpret=not on_tpu)
        for i in range(k)
    ]
    q_stack = jax.tree.map(
        lambda *xs: jnp.stack(xs) if isinstance(xs[0], jax.Array) else xs[0],
        *qts,
    )

    def staged(args):
        q, xs = args
        vals = codec_pallas.dequantize_batch(
            q, out_dtype=jnp.float32, interpret=not on_tpu
        )
        mask = (jnp.arange(ws) == own)[:, None]
        red = dispatch.ordered_rowsum(
            jnp.where(mask, xs.astype(jnp.float32), vals)
        )
        q2 = codec_pallas.quantize_batch(
            red[None], BITS, BUCKET, interpret=not on_tpu
        )
        return (q2.packed, q2.meta)

    def fused(args):
        q, xs = args
        q2 = codec_pallas.sra_epilogue_batch(
            q, raw_row=xs[ws // 2], own_idx=own, interpret=not on_tpu
        )
        return (q2.packed, q2.meta)

    # Wire-identity pre-flight: a fused epilogue that changes bytes must
    # fail loudly here, never be timed (the qbench byte-check discipline).
    ws_s, ms_s = jax.jit(staged)((qts[0], stack[0]))
    ws_f, ms_f = jax.jit(fused)((qts[0], stack[0]))
    assert bool(jnp.array_equal(ws_s, ws_f)) and bool(
        jnp.array_equal(ms_s, ms_f)
    ), "fused SRA epilogue wire bytes diverge from the staged path"

    t_staged = scan_time(staged, (q_stack, stack))
    t_fused = scan_time(fused, (q_stack, stack))
    gbytes = total * 4 / 1e9
    return {
        "metric": (
            f"sra_epilogue_fused_vs_staged_{BITS}bit_"
            f"{total * 4 // 2**20}MB_x{ws}"
        ),
        "value": round(gbytes / t_fused, 3),
        "unit": "GB/s",
        "vs_baseline": round(t_staged / t_fused, 3),
        "detail": {
            "t_staged_ms": round(t_staged * 1e3, 3),
            "t_fused_ms": round(t_fused * 1e3, 3),
            "ws": ws,
            "chunk_elems": chunk,
            "wire_identity": "bit-identical (asserted)",
            "timing": "scan-slope (dispatch overhead cancelled)",
        },
    }


def bench_codec_roofline(
    mb: int = 64, ws: int = 4, bits: int = BITS, iters: int = 5
) -> list:
    """ISSUE 11 records: (a) ``quantize_roofline_frac_*`` — the flat
    quantize kernel's achieved HBM-roofline fraction (vs the chip table
    on TPU, vs a measured same-backend read floor on CPU — the ``@cpu``
    trajectory bench_gate quarantines); (b)
    ``producer_fused_vs_staged_*`` — the fused matmul+quantize producer
    kernel vs the staged matmul-then-quantize pair, wire-byte pre-flighted
    (bit-equal where the two matmuls agree, quantization-envelope
    allclose otherwise — the producer-fuse contract). With
    ``CGX_AUTOTUNE=on`` a short tile sweep runs first and persists the
    winners (ops/autotune.py), so the timed rows measure the tuned
    configs a production run would use."""
    from torch_cgx_tpu import config as cfg_mod
    from torch_cgx_tpu.config import CompressionConfig
    from torch_cgx_tpu.ops import autotune, codec_pallas, dispatch
    from torch_cgx_tpu.ops import fused_producer as fp
    from torch_cgx_tpu.parallel import reducers

    on_tpu = jax.default_backend() == "tpu"
    n = (mb * 2**20 // 4) if on_tpu else 2**20
    n -= n % (ws * 32 * BUCKET)
    mb_eff = n * 4 // 2**20
    chip, hbm = _chip()
    cc = CompressionConfig(bits=bits, bucket_size=BUCKET)

    k = 4 if on_tpu else 2
    stack = jax.jit(
        lambda key: jax.random.normal(key, (k, 1, n), jnp.float32)
    )(jax.random.PRNGKey(3))
    stack.block_until_ready()

    def quantize(x):
        q = codec_pallas.quantize_batch(
            x, bits, BUCKET, interpret=not on_tpu
        )
        return (q.packed, q.meta)

    # --- optional autotune sweep (hardware sessions set CGX_AUTOTUNE=on;
    # CI/auto only consults, never measures) -----------------------------
    tuned = None
    if cfg_mod.autotune_mode() == "on":
        n_chunks = n // (32 * BUCKET)

        def measure(cand):
            os.environ["CGX_PALLAS_TILE_CHUNKS"] = str(cand.tc)
            os.environ["CGX_PALLAS_DB"] = "on" if cand.db else "off"
            try:
                return scan_time(quantize, stack, iters=max(2, iters // 2))
            finally:
                os.environ.pop("CGX_PALLAS_TILE_CHUNKS", None)
                os.environ.pop("CGX_PALLAS_DB", None)

        cands = [
            autotune.TunedConfig(tc=tc, db=db)
            for tc in (4, 8, 16)
            for db in (False, True)
            if autotune.snap_to_divisor(tc, n_chunks, 64) == tc
        ]
        tuned = autotune.tune(
            autotune.KIND_FLAT, cands, measure,
            n_chunks=n_chunks, bucket_size=BUCKET, bits=bits,
            input_bytes=n * 4,
        )

    t_q = scan_time(quantize, stack, iters=iters)
    nb = n // BUCKET
    moved = (n * 4 + n * bits / 8 + nb * 8) / 1e9
    if hbm:
        denom, denom_src = hbm, "chip_table"
    else:
        # Same-backend read floor: a max-reduce over the identical
        # operand — the achievable-memory-bandwidth proxy for @cpu rows.
        t_floor = scan_time(
            lambda x: jnp.max(x), stack, iters=iters
        )
        denom = (n * 4 / 1e9) / t_floor
        denom_src = "measured_read_floor"
    frac = (moved / t_q) / denom if denom else 0.0
    from torch_cgx_tpu.utils.logging import metrics as _metrics

    _metrics.set("cgx.codec.roofline_frac", round(frac, 4))
    roofline_rec = {
        "metric": f"quantize_roofline_frac_{bits}bit_{mb_eff}MB",
        "value": round(frac, 4),
        "unit": "frac",
        "vs_baseline": round(moved / t_q, 2),
        "detail": {
            "quantize_GBps_moved": round(moved / t_q, 2),
            "roofline_GBps": round(denom, 2),
            "roofline_source": denom_src,
            "t_quantize_ms": round(t_q * 1e3, 3),
            "chip": chip,
            "autotuned": None if tuned is None else {
                "tc": tuned.tc, "db": tuned.db, "gbps": tuned.gbps,
            },
            "timing": "scan-slope (dispatch overhead cancelled)",
        },
    }

    # --- producer-fused vs staged quantize-after-grad -------------------
    # Shapes: dw = x2^T @ g2 of exactly the wire-aligned size; CPU keeps
    # the interpret-mode kernel small.
    if on_tpu:
        din, o = 1024, max(128, n // 1024 - (n // 1024) % 128)
        din = n // o
    else:
        din, o = 256, 512
    K = 256 if on_tpu else 64
    n_p = din * o
    chunk = n_p // ws
    rng = jax.random.PRNGKey(7)
    x2 = jax.random.normal(rng, (K, din), jnp.float32)
    g2 = jax.random.normal(jax.random.fold_in(rng, 1), (K, o), jnp.float32)
    geo = fp._kernel_geometry(K, din, o, ws, chunk, cc)
    if geo is None:
        return [roofline_rec]
    tm, tk = geo

    def staged(args):
        x2, g2 = args
        dw = (
            jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ()))) / ws
        ).reshape(ws, chunk)
        q = reducers._quantize_rows(dw, cc, None)
        return (q.packed, q.meta)

    def fused(args):
        x2, g2 = args
        q = fp._matmul_quantize_q(
            x2, g2, cc, ws=ws, chunk=chunk, div=ws, tm=tm, tk=tk,
            interpret=not on_tpu,
        )
        return (q.packed, q.meta)

    # Pre-flight: byte-equal when the two matmul lowerings agree on this
    # backend; otherwise the decoded payloads must sit inside the
    # quantization envelope (2 * unit per coordinate).
    ps, ms = jax.jit(staged)((x2, g2))
    pf, mf = jax.jit(fused)((x2, g2))
    bit_equal = bool(jnp.array_equal(ps, pf)) and bool(
        jnp.array_equal(ms, mf)
    )
    if not bit_equal:
        qs = reducers._quantize_rows(
            (jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ()))) / ws
             ).reshape(ws, chunk), cc, None,
        )
        d_s = dispatch.dequantize_batch(qs)
        qf = fp._matmul_quantize_q(
            x2, g2, cc, ws=ws, chunk=chunk, div=ws, tm=tm, tk=tk,
            interpret=not on_tpu,
        )
        d_f = dispatch.dequantize_batch(qf)
        unit = jnp.max(jnp.abs(d_s)) / ((1 << bits) - 1)
        assert bool(jnp.all(jnp.abs(d_s - d_f) <= 2 * unit + 1e-6)), (
            "producer-fused payload outside the quantization envelope"
        )

    k2 = 4 if on_tpu else 2
    xs_stack = (
        jnp.stack([x2 + i for i in range(k2)]),
        jnp.stack([g2 + i for i in range(k2)]),
    )
    t_staged = scan_time(staged, xs_stack, iters=iters)
    t_fused = scan_time(fused, xs_stack, iters=iters)
    producer_rec = {
        "metric": (
            f"producer_fused_vs_staged_{bits}bit_{n_p * 4 // 2**20}MB"
        ),
        "value": round(n_p * 4 / 1e9 / t_fused, 3),
        "unit": "GB/s",
        "vs_baseline": round(t_staged / t_fused, 3),
        "detail": {
            "t_staged_ms": round(t_staged * 1e3, 3),
            "t_fused_ms": round(t_fused * 1e3, 3),
            "din": din, "o": o, "K": K, "ws": ws,
            "tm": tm, "tk": tk,
            "wire_identity": (
                "bit-identical (asserted)" if bit_equal
                else "quantization-envelope (matmul association differs)"
            ),
            # HBM byte accounting (PERF_NOTES "Producer-fused quantize"):
            # staged writes + re-reads the f32 gradient; fused writes
            # only packed+meta.
            "hbm_bytes_staged": int(n_p * 4 * 2 + n_p * bits / 8),
            "hbm_bytes_fused": int(n_p * bits / 8 + (n_p // BUCKET) * 8),
            "timing": "scan-slope (dispatch overhead cancelled)",
        },
    }
    return [roofline_rec, producer_rec]


def bench_train_step(on_tpu: bool) -> dict:
    """North-star proxy on one chip: jitted GPT-2 train step with the codec
    round trip applied to its gradients (the per-rank work of a compressed
    DP sync) vs the plain step. Bounds the achievable multi-chip speedup:
    codec overhead must stay a small fraction of step time for the wire
    savings to win (BASELINE.md north star)."""
    _bench_env = {
        "CGX_DEBUG_FORCE_CODEC": "1",
        "CGX_COMPRESSION_QUANTIZATION_BITS": str(BITS),
        "CGX_COMPRESSION_BUCKET_SIZE": str(BUCKET),
    }
    _saved_env = {k: os.environ.get(k) for k in _bench_env}
    os.environ.update(_bench_env)
    try:
        return _bench_train_step_inner(on_tpu, mesh1=Mesh(
            np.asarray(jax.devices()[:1]), ("dp",)
        ))
    finally:
        for key, prior in _saved_env.items():
            if prior is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prior


def _bench_train_step_inner(on_tpu: bool, mesh1) -> dict:
    import optax

    from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
    from torch_cgx_tpu.parallel import gradient_sync

    cfg = (
        GPT2Config(n_layer=12, n_head=12, d_model=768, vocab_size=50257,
                   max_seq=512)
        if on_tpu
        else GPT2Config.tiny()
    )
    batch, seq = (8, 512) if on_tpu else (2, 64)
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch, seq)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    opt = optax.adam(1e-4)
    opt_state = opt.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    def loss_fn(p):
        return lm_loss(model.apply({"params": p}, tokens), tokens)

    def plain_step(carry):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = opt.update(grads, s, p)
        return (optax.apply_updates(p, updates), s), loss

    def codec_step(carry):
        # The PRODUCTION gradient-sync path on a 1-device mesh with
        # CGX_DEBUG_FORCE_CODEC: allreduce_tree's grouping (large leaves
        # standalone — zero-copy flat views; small leaves fused) + the
        # per-rank codec round trip of SRA. This measures what a real rank
        # pays, including the framework's own glue.
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = shard_map(
            lambda g: gradient_sync(g, mesh=mesh1, average=False),
            mesh=mesh1,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )(grads)
        updates, s = opt.update(grads, s, p)
        return (optax.apply_updates(p, updates), s), loss

    def steps_time(step, k: int, iters: int = 3) -> float:
        def runner(p, s):
            def body(carry, _):
                carry, loss = step(carry)
                return carry, loss

            (_, _), losses = lax.scan(body, (p, s), None, length=k)
            return losses[-1]

        jr = jax.jit(runner)

        def timed():
            np.asarray(jr(params, opt_state))
            t0 = time.perf_counter()
            for _ in range(iters):
                o = jr(params, opt_state)
            np.asarray(o)
            return (time.perf_counter() - t0) / iters

        return timed()

    k = 6 if on_tpu else 3
    t_plain = (steps_time(plain_step, k) - steps_time(plain_step, 1)) / (k - 1)
    t_codec = (steps_time(codec_step, k) - steps_time(codec_step, 1)) / (k - 1)
    overhead = (t_codec - t_plain) / t_plain * 100
    return {
        "model": "gpt2-small" if on_tpu else "gpt2-tiny",
        "params_M": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "step_plain_ms": round(t_plain * 1e3, 2),
        "step_with_codec_ms": round(t_codec * 1e3, 2),
        "codec_overhead_pct": round(overhead, 1),
        "grad_bytes_MB": round(n_params * 4 / 2**20, 1),
    }


def bench_allreduce(devices) -> dict:
    from torch_cgx_tpu.config import CompressionConfig
    from torch_cgx_tpu.parallel.reducers import quantized_allreduce

    n_elems = 16 * 1024 * 1024  # 64 MB fp32
    mesh = Mesh(np.asarray(devices), ("dp",))
    ws = len(devices)
    cc = CompressionConfig(bits=BITS, bucket_size=BUCKET)
    x = jax.device_put(
        jnp.arange(n_elems, dtype=jnp.float32) / n_elems,
        NamedSharding(mesh, P()),
    )

    def q_allreduce(x):
        return quantized_allreduce(x, "dp", ws, cc, "SRA")

    def f32_allreduce(x):
        return jax.lax.psum(x, "dp")

    shard = dict(mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    q = jax.jit(shard_map(q_allreduce, **shard))
    f = jax.jit(shard_map(f32_allreduce, **shard))

    def fetch(out):
        for leaf in jax.tree.leaves(out):
            np.asarray(jax.device_get(leaf.ravel()[:1]))

    def t(fn, *args):
        for _ in range(3):
            fetch(fn(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        fetch(out)
        return (time.perf_counter() - t0) / 10

    tq, tf = t(q, x), t(f, x)
    gbytes = n_elems * 4 / 1e9
    return {
        "metric": f"sra_allreduce_{BITS}bit_64MB_x{ws}",
        "value": round(gbytes / tq, 3),
        "unit": "GB/s",
        "vs_baseline": round(tf / tq, 3),
        "detail": {
            "t_quantized_ms": round(tq * 1e3, 3),
            "t_fp32_psum_ms": round(tf * 1e3, 3),
            "devices": ws,
        },
    }


# ---------------------------------------------------------------------------
# In-XLA single-program allreduce vs the host bridge (ISSUE 8): the same
# payload through (a) one staged XLA program on a ws-device mesh
# (parallel/xla_allreduce.py — quantize -> all_to_all -> fused epilogue ->
# all_gather, zero host hops) and (b) the production torch bridge
# (ProcessGroupCGX over shm/store — ws real OS processes). Both children run
# in fresh subprocesses so the parent's backend state never leaks; on a box
# without ws real accelerators the staged child runs on a forced CPU
# multi-device platform and the record keys into the `@cpu` trajectory
# (bench_gate separates placeholder from chip truth).
# ---------------------------------------------------------------------------


def _xla_payload(n: int, ws: int) -> np.ndarray:
    base = (np.arange(n, dtype=np.float32) / n) - 0.5
    return np.stack([(r + 1) * base for r in range(ws)])


def _xla_staged_child(mb: int, ws: int, iters: int) -> None:
    """Child: time the staged single-program allreduce; one JSON line."""
    from torch_cgx_tpu.config import CompressionConfig
    from torch_cgx_tpu.parallel import xla_allreduce

    n = mb * 2**20 // 4
    cc = CompressionConfig(bits=BITS, bucket_size=BUCKET)
    mesh = Mesh(np.asarray(jax.devices()[:ws]), ("dp",))
    per = _xla_payload(n, ws)
    out = xla_allreduce.staged_allreduce(per, mesh=mesh, cc=cc)  # build+warm
    head = np.asarray(out)[0, :16].tolist()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = xla_allreduce.staged_allreduce(per, mesh=mesh, cc=cc)
        np.asarray(jax.device_get(out[0, :1]))  # sync
    dt = (time.perf_counter() - t0) / iters
    print(json.dumps({
        "t_staged_ms": dt * 1e3,
        "head": head,
        "backend": jax.default_backend(),
        "chip": jax.devices()[0].device_kind,
        "program_cache": xla_allreduce.program_cache_stats(),
    }))


def _xla_bridge_rank(rank: int, ws: int, initfile: str, mb: int,
                     iters: int, q) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import torch
    import torch.distributed as dist

    import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"

    n = mb * 2**20 // 4
    base = torch.arange(n, dtype=torch.float32) / n - 0.5
    t = (rank + 1) * base
    dist.init_process_group(
        "cgx", init_method=f"file://{initfile}", rank=rank, world_size=ws
    )
    try:
        res = t.clone()
        dist.all_reduce(res)  # warm (arena growth) + correctness capture
        dist.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            dist.all_reduce(t)
        dist.barrier()
        dt = (time.perf_counter() - t0) / iters
        if rank == 0:
            q.put({"t_bridge_ms": dt * 1e3, "head": res[:16].tolist()})
    finally:
        dist.destroy_process_group()


def _xla_bridge_child(mb: int, ws: int, iters: int) -> None:
    """Child: time the production bridge allreduce (ws real processes
    over the shm/store plane); one JSON line."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    with tempfile.TemporaryDirectory() as d:
        initfile = os.path.join(d, "init")
        procs = [
            ctx.Process(
                target=_xla_bridge_rank, args=(r, ws, initfile, mb, iters, q)
            )
            for r in range(ws)
        ]
        for p in procs:
            p.start()
        try:
            rec = q.get(timeout=600)
        finally:
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
    print(json.dumps(rec))


def _run_json_child(args: list, env: dict, timeout: float = 900.0) -> dict:
    proc = subprocess.run(
        args, env=env, capture_output=True, text=True, timeout=timeout,
    )
    tail = (proc.stdout.strip().splitlines() or [""])[-1]
    if proc.returncode != 0 or not tail.startswith("{"):
        raise RuntimeError(
            f"child {args[2:]} failed rc={proc.returncode}: "
            f"{proc.stderr.strip()[-800:]}"
        )
    return json.loads(tail)


def bench_xla_allreduce(mb: int = 8, ws: int = 4, iters: int = 5) -> dict:
    """Staged single-program allreduce vs the production bridge on the
    same ``mb``-MB fp32 payload at ``ws`` ranks (the ISSUE 8 acceptance
    record). Staged child uses real accelerators when >= ws exist, else a
    forced CPU multi-device platform (record then keys ``@cpu``)."""
    base_env = {
        **os.environ,
        "CGX_XLA_ALLREDUCE": "on",
        "CGX_COMPRESSION_QUANTIZATION_BITS": str(BITS),
        "CGX_COMPRESSION_BUCKET_SIZE": str(BUCKET),
    }
    env_staged = dict(base_env)
    # Probe in a throwaway subprocess: initializing the TPU client here
    # would hold the chips the staged child must itself acquire (libtpu
    # refuses a second claimant in the same process tree).
    use_real = False
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import json, jax; print(json.dumps("
             "[jax.default_backend(), len(jax.devices())]))"],
            env=dict(base_env), capture_output=True, text=True, timeout=180,
        )
        backend, n_dev = json.loads(
            (probe.stdout.strip().splitlines() or ["[]"])[-1]
        )
        use_real = backend != "cpu" and n_dev >= ws
    except Exception:
        pass
    if not use_real:
        env_staged["JAX_PLATFORMS"] = "cpu"
        env_staged["XLA_FLAGS"] = (
            env_staged.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={ws}"
        )
    me = str(Path(__file__).resolve())
    staged = _run_json_child(
        [sys.executable, me, "--xla-allreduce-staged-child",
         str(mb), str(ws), str(iters)], env_staged,
    )
    env_bridge = dict(base_env)
    env_bridge["JAX_PLATFORMS"] = "cpu"
    bridge = _run_json_child(
        [sys.executable, me, "--xla-allreduce-bridge-child",
         str(mb), str(ws), str(iters)], env_bridge,
    )
    t_s, t_b = staged["t_staged_ms"], bridge["t_bridge_ms"]
    head_diff = max(
        abs(a - b) for a, b in zip(staged["head"], bridge["head"])
    )
    gbytes = mb * 2**20 / 1e9  # fp32 payload bytes per rank
    return {
        "metric": f"xla_allreduce_vs_bridge_{BITS}bit_{mb}MB_x{ws}",
        "value": round(gbytes / (t_s / 1e3), 3),
        "unit": "GB/s",
        "vs_baseline": round(t_b / t_s, 3),
        "chip": staged.get("chip", "unknown"),
        "backend": staged.get("backend", "unknown"),
        "detail": {
            "t_staged_ms": round(t_s, 3),
            "t_bridge_ms": round(t_b, 3),
            "ws": ws,
            "payload_MB": mb,
            "iters": iters,
            "results_head_max_abs_diff": head_diff,
            "staged_backend": staged.get("backend"),
            "bridge": "ProcessGroupCGX shm/store, ws real processes",
            "program_cache": staged.get("program_cache"),
        },
    }


# ---------------------------------------------------------------------------
# Compiled-schedule pipeline vs the monolithic path (ISSUE 9): the same
# payload through the production bridge twice — CGX_SCHEDULE=on (chunked
# encode/put/take/epilogue with the double-buffered in-flight window) vs
# unset (monolithic phase barriers) — with a bit-equality pre-flight on the
# full reduced tensor and the cgx_trace overlap_frac attribution of both
# runs attached (the pipelined run must report overlap > 0 where the
# monolithic run reports ~0). Host-plane measurement (the bridge always
# runs on host CPU), tagged backend "host" like shm_bench.
# ---------------------------------------------------------------------------


def _sched_bridge_rank(rank, ws, initfile, mb, iters, chunks, mode, mdir, q):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = str(BITS)
    os.environ["CGX_COMPRESSION_BUCKET_SIZE"] = str(BUCKET)
    os.environ["CGX_METRICS_DIR"] = mdir
    if mode == "plan":
        # Planner mode (bench.py --planner): the step planner owns the
        # depth decision through the ENV-ONLY bridge plane — CGX_PLANNER
        # plus (for the calibrated run) the CGX_PLANNER_MODEL file the
        # parent wrote. The rank process deliberately does NOT import
        # the parallel package: it exercises exactly the pure-bridge
        # path (backend._plan_bridge_chunks, the dependency-light
        # mirror), and stays import-symmetric with the static ranks so
        # the A/B measures the decision, not the process footprint.
        os.environ["CGX_PLANNER"] = "on"
        os.environ.pop("CGX_SCHEDULE", None)
        os.environ.pop("CGX_SCHED_CHUNKS", None)
    else:
        os.environ["CGX_SCHED_CHUNKS"] = str(chunks)
        os.environ["CGX_SCHEDULE"] = "on" if mode == "pipe" else "off"
    import zlib

    import torch
    import torch.distributed as dist

    import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"
    from torch_cgx_tpu.observability import timeline
    from torch_cgx_tpu.utils.logging import metrics as _m

    n = mb * 2**20 // 4
    base = torch.arange(n, dtype=torch.float32) / n - 0.5
    t = (rank + 1) * base
    dist.init_process_group(
        "cgx", init_method=f"file://{initfile}", rank=rank, world_size=ws
    )
    try:
        res = t.clone()
        dist.all_reduce(res)  # warm (arena growth) + bit-equality capture
        dist.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            work = t.clone()
            dist.all_reduce(work)
        dist.barrier()
        dt = (time.perf_counter() - t0) / iters
        timeline.flush()
        if rank == 0:
            wall = _m.get("cgx.sched.wall_s")
            rec = {
                "t_ms": dt * 1e3,
                "crc": zlib.crc32(res.numpy().tobytes()),
                "live_overlap": (
                    _m.get("cgx.sched.overlap_s") / wall if wall else 0.0
                ),
            }
            if mode == "plan":
                # the depth the mirror actually ran (gauge set per call)
                rec["chunks"] = int(_m.get("cgx.plan.bridge_chunks") or 1)
            q.put(rec)
    finally:
        dist.destroy_process_group()


def _sched_bridge_child(mb: int, ws: int, iters: int, chunks: int,
                        mode: str) -> None:
    """Child: one bridge run (ws real processes) in the given mode; prints
    one JSON line with timing, the full-result crc32 and the cgx_trace
    per-rank overlap_frac attribution of the run's own metrics dir."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    with tempfile.TemporaryDirectory() as d:
        initfile = os.path.join(d, "init")
        mdir = os.path.join(d, "metrics")
        os.makedirs(mdir, exist_ok=True)
        procs = [
            ctx.Process(
                target=_sched_bridge_rank,
                args=(r, ws, initfile, mb, iters, chunks, mode, mdir, q),
            )
            for r in range(ws)
        ]
        for p in procs:
            p.start()
        try:
            rec = q.get(timeout=600)
        finally:
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
        # Attribution over the run's own span files (tools/cgx_trace.py):
        # the committed record carries the overlap measurement, not just
        # wall clock — bench_gate's overlap floor gates on it.
        sys.path.insert(0, str(Path(__file__).parent / "tools"))
        import cgx_trace

        per_rank = cgx_trace.load_spans(mdir)
        att = cgx_trace.attribution(per_rank) if per_rank else {"per_rank": {}}
        fracs = [
            c.get("overlap_frac", 0.0) for c in att["per_rank"].values()
        ]
        rec["overlap_frac"] = (
            round(sum(fracs) / len(fracs), 4) if fracs else 0.0
        )
        # Mean per-rank measured stage seconds (ISSUE 17): the parent
        # turns these into per-component prediction ratios
        # (bench_gate's <metric>:pred_ratio:<component> trajectories).
        if att["per_rank"]:
            n_ranks = len(att["per_rank"])
            rec["measured_components"] = {
                k: round(
                    sum(c.get(k, 0.0) for c in att["per_rank"].values())
                    / n_ranks, 6,
                )
                for k in ("quantize", "wire", "wait", "other")
            }
        if mode == "plan":
            # span-calibrated cost model of THIS run (rates + overlap):
            # computed post-measurement in the child, never in a rank —
            # the parent fits the per-chunk overhead across runs and
            # persists the result for the calibrated planner run.
            from torch_cgx_tpu.parallel import planner as _planner

            rec["model"] = _planner.CostModel.from_spans(mdir).as_dict()
    print(json.dumps(rec))


def bench_schedule(mb: int = 32, ws: int = 4, iters: int = 4,
                   chunks: int = 8) -> dict:
    """Pipelined vs monolithic bridge allreduce on the same ``mb``-MB fp32
    payload (the ISSUE 9 acceptance record): bit-equality pre-flight on
    the full result, then wall-clock + overlap_frac of both runs. The
    payload is chosen bucket-aligned (mb*2^20/4 divisible by ws*512) so
    the deterministic pipelined run is bit-equal by the schedule
    compiler's contract."""
    n = mb * 2**20 // 4
    if (-(-n // ws)) % BUCKET:
        raise ValueError(
            f"--mb {mb} at ws {ws} is not bucket-aligned (ceil(n/ws) must "
            f"divide by {BUCKET}) — the bit-equality pre-flight needs an "
            "aligned payload"
        )
    me = str(Path(__file__).resolve())
    env = {**os.environ}
    env.pop("CGX_SCHEDULE", None)
    mono = _run_json_child(
        [sys.executable, me, "--schedule-bridge-child",
         str(mb), str(ws), str(iters), str(chunks), "mono"], env,
    )
    pipe = _run_json_child(
        [sys.executable, me, "--schedule-bridge-child",
         str(mb), str(ws), str(iters), str(chunks), "pipe"], env,
    )
    if mono["crc"] != pipe["crc"]:
        raise AssertionError(
            "schedule bench: pipelined result diverges from monolithic "
            f"(crc {pipe['crc']:#x} vs {mono['crc']:#x}) — the bit-"
            "equality contract of parallel/schedule.py is broken"
        )
    t_m, t_p = mono["t_ms"], pipe["t_ms"]
    gbytes = mb * 2**20 / 1e9
    return {
        "metric": (
            f"sched_pipelined_vs_monolithic_{BITS}bit_{mb}MB_x{ws}"
        ),
        "value": round(gbytes / (t_p / 1e3), 3),
        "unit": "GB/s",
        "vs_baseline": round(t_m / t_p, 3),
        # Top-level so bench_gate's overlap floor gates it (the pipelined
        # run's cgx_trace attribution; the monolithic run's is in detail
        # for the ~0 contrast).
        "overlap_frac": pipe["overlap_frac"],
        # Host-plane measurement: the bridge always runs on host CPU, on
        # any box — a genuine trajectory (shm_bench's convention), not a
        # CPU placeholder for a chip number.
        "backend": "host",
        "chip": "host",
        "detail": {
            "t_pipelined_ms": round(t_p, 3),
            "t_monolithic_ms": round(t_m, 3),
            "ws": ws,
            "payload_MB": mb,
            "iters": iters,
            "sched_chunks": chunks,
            "results": "bit-equal (crc32 of full tensor asserted)",
            "overlap_frac_monolithic": mono["overlap_frac"],
            "overlap_frac_pipelined": pipe["overlap_frac"],
            "live_overlap_pipelined": round(pipe.get("live_overlap", 0.0), 4),
            "bridge": "ProcessGroupCGX shm/store, ws real processes",
        },
    }


def _planner_pred_components(
    fitted, n: int, ws: int, iters: int, measured,
) -> dict:
    """{component: predicted/measured ratio} for the calibrated model's
    per-stage raw-work predictions vs the run's span attribution —
    empty when the child attached no measurement (spanless run)."""
    if not isinstance(measured, dict):
        return {}
    per_slice = fitted.predict_slice_components(
        n, ws, BITS, BUCKET, chunks=1, route="bridge"
    )
    out = {}
    for comp in ("quantize", "wire"):
        m = float(measured.get(comp, 0.0))
        p = per_slice.get(comp, 0.0) * iters
        if m > 1e-9 and p > 0:
            out[comp] = round(p / m, 4)
    return out


def bench_planner(mb: int = 32, ws: int = 4, iters: int = 4) -> dict:
    """Planner-vs-static record (the ISSUE 12 acceptance row): the full
    closed loop on the production bridge —

    1. **static baseline**: ``CGX_SCHEDULE=on`` at the default
       ``CGX_SCHED_CHUNKS`` (the configuration a hand-tuned job runs);
    2. **calibration run**: ``CGX_PLANNER=on`` under the built-in
       default model (the mirror's depth), leaving span telemetry;
    3. the parent builds the span-calibrated ``CostModel`` and fits the
       per-chunk overhead from the TWO measured (depth, time) points —
       the rates say how the exposed stage amortizes, the two
       measurements pin what each extra chunk really costs on this box;
    4. **planner run**: the calibrated model persisted to a
       ``CGX_PLANNER_MODEL`` file every rank loads (the group-consistent
       channel) — the planner's OWN depth decision, measured.

    Static and planner configs take the min of two child runs each (the
    least-contended estimate — see ``_best_of``). Bit-equality
    pre-flight across all runs (the deterministic schedule contract:
    any depth, same bytes), ``overlap_frac`` attached, and
    predicted-vs-measured carried for ``bench_gate``'s prediction floor
    (``pred_ratio`` trajectory + ``CGX_GATE_PRED_SLACK`` hard check).
    ``vs_baseline`` >= 1.0 = the planner's calibrated decision beats
    (or ties) the static configuration."""
    import dataclasses
    import tempfile

    from torch_cgx_tpu.config import DEFAULT_SCHED_CHUNKS
    from torch_cgx_tpu.parallel import planner as planner_mod

    n = mb * 2**20 // 4
    if (-(-n // ws)) % BUCKET:
        raise ValueError(
            f"--mb {mb} at ws {ws} is not bucket-aligned (ceil(n/ws) must "
            f"divide by {BUCKET}) — the bit-equality pre-flight needs an "
            "aligned payload"
        )
    me = str(Path(__file__).resolve())
    env = {**os.environ}
    for k in ("CGX_SCHEDULE", "CGX_SCHED_CHUNKS", "CGX_PLANNER",
              "CGX_PLANNER_MODEL"):
        env.pop(k, None)

    def _best_of(n_runs, extra_env, *args):
        """min-t_ms of repeated child runs — the least-contended
        estimate; a shared box's load spikes inflate individual runs by
        ±25%, and a single-sample A/B would measure the scheduler, not
        the schedule."""
        recs = [
            _run_json_child(
                [sys.executable, me, "--schedule-bridge-child", *args],
                {**env, **extra_env},
            )
            for _ in range(n_runs)
        ]
        return min(recs, key=lambda r: r["t_ms"])

    static = _best_of(
        2, {}, str(mb), str(ws), str(iters), str(DEFAULT_SCHED_CHUNKS),
        "pipe",
    )
    cal = _run_json_child(
        [sys.executable, me, "--schedule-bridge-child",
         str(mb), str(ws), str(iters), "0", "plan"], env,
    )
    # Two-point overhead fit: t(c) = B + E/c + c*O with E (the exposed
    # non-bottleneck stage) from the calibrated rates; the static and
    # calibration runs measured t at two depths, so O falls out of the
    # difference (B cancels). Guarded to stay positive.
    model = planner_mod.CostModel.from_dict(cal["model"])
    rates_only = dataclasses.replace(model, chunk_overhead_s=0.0)
    exposed = rates_only.predict_slice(
        n, ws, BITS, BUCKET, chunks=1, route="bridge"
    ) - rates_only.predict_slice(
        n, ws, BITS, BUCKET, chunks=10**9, route="bridge"
    )
    c_s, t_s = DEFAULT_SCHED_CHUNKS, static["t_ms"] / 1e3
    c_c, t_c = max(1, int(cal["chunks"])), cal["t_ms"] / 1e3
    if c_c != c_s:
        overhead = ((t_c - t_s) - exposed * (1 / c_c - 1 / c_s)) / (c_c - c_s)
    else:
        overhead = model.chunk_overhead_s
    overhead = max(overhead, 1e-6)
    fitted = dataclasses.replace(
        model, chunk_overhead_s=overhead, source=model.source + "+2pt"
    )
    with tempfile.TemporaryDirectory() as d:
        mpath = os.path.join(d, "cost_model.json")
        fitted.save(mpath)
        plan = _best_of(
            2, {"CGX_PLANNER_MODEL": mpath},
            str(mb), str(ws), str(iters), "0", "plan",
        )
    crcs = {static["crc"], cal["crc"], plan["crc"]}
    if len(crcs) != 1:
        raise AssertionError(
            "planner bench: results diverge across runs "
            f"(crcs {sorted(crcs)}) — the planner must only pick knobs, "
            "never change bytes"
        )
    t_p = plan["t_ms"]
    depth = max(1, int(plan["chunks"]))
    # The model's own prediction for the depth it chose, anchored at the
    # measured calibration point (B from t_c at depth c_c).
    predicted_ms = (
        t_c + exposed * (1 / depth - 1 / c_c) + (depth - c_c) * overhead
    ) * 1e3
    gbytes = mb * 2**20 / 1e9
    return {
        "metric": f"planner_vs_static_{BITS}bit_{mb}MB_x{ws}",
        "value": round(gbytes / (t_p / 1e3), 3),
        "unit": "GB/s",
        # >= 1.0 = the planner's calibrated decision beats the static
        # configuration — the acceptance bar.
        "vs_baseline": round(static["t_ms"] / t_p, 3),
        "overlap_frac": plan["overlap_frac"],
        # bench_gate's prediction floor: the trajectory key
        # planner_vs_static_*:pred_ratio plus the hard slack pair.
        "predicted_step_ms": round(predicted_ms, 3),
        "measured_step_ms": round(t_p, 3),
        "pred_ratio": round(predicted_ms / t_p, 4) if t_p else 0.0,
        # Per-component prediction accuracy (ISSUE 17): raw per-stage
        # work (chunks=1 — span durations measure work, not exposure)
        # against the planner run's measured span attribution. Gated as
        # <metric>:pred_ratio:<component> trajectories by bench_gate.
        "pred_components": _planner_pred_components(
            fitted, n, ws, iters, plan.get("measured_components")
        ),
        # Host-plane measurement (the bridge always runs on host CPU) —
        # a genuine trajectory, like bench_schedule/shm_bench.
        "backend": "host",
        "chip": "host",
        "detail": {
            "t_planned_ms": round(t_p, 3),
            "t_static_ms": round(static["t_ms"], 3),
            "t_calibration_ms": round(cal["t_ms"], 3),
            "planner_chunks": depth,
            "static_chunks": DEFAULT_SCHED_CHUNKS,
            "calibration_chunks": c_c,
            "fitted_overhead_ms": round(overhead * 1e3, 3),
            "cost_model": fitted.source,
            "ws": ws,
            "payload_MB": mb,
            "iters": iters,
            "results": "bit-equal (crc32 of full tensor asserted, 3 runs)",
            "overlap_frac_static": static["overlap_frac"],
            "overlap_frac_planned": plan["overlap_frac"],
            "bridge": "ProcessGroupCGX shm/store, ws real processes",
        },
    }


# ---------------------------------------------------------------------------
# bench.py --async-dcn (ISSUE 13): asynchronous cross-slice plane vs the
# synchronous two-level path under an injected slow DCN edge. 2 fake
# slices (CGX_SHM_HOST_ID) x ws/2 ranks; the slow edge is a
# `slow_rank:<10x step>@rank=<sliceB leader>@edge=dcn` fault — on the
# sync path it sits on the critical path (every rank stalls behind the
# cross exchange), on the async path the same fault fires inside the
# dedicated sender thread and the step never feels it. The committed
# record carries the speedup, a convergence-proxy loss delta (distance
# to the global optimum of a deterministic quadratic), and the round-0
# delta crc of two repeated async runs (bit-reproducible under the
# fixed seed). Host-plane measurement, tagged backend "host".
# ---------------------------------------------------------------------------


def _async_dcn_rank(rank, ws, initfile, mb, iters, h, mode, delay_ms, q):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = str(BITS)
    os.environ["CGX_COMPRESSION_BUCKET_SIZE"] = str(BUCKET)
    half = ws // 2
    # two fake slices on one real box; the byte plane stays off so the
    # intra stage rides the store deterministically on any CI box
    os.environ["CGX_SHM_HOST_ID"] = f"slice{rank // half}"
    os.environ["CGX_SHM"] = "0"
    if delay_ms > 0:
        os.environ["CGX_FAULTS"] = (
            f"slow_rank:{delay_ms}ms@rank={half}@edge=dcn"
        )
    if mode == "async":
        os.environ["CGX_ASYNC"] = "on"
        os.environ["CGX_ASYNC_H"] = str(h)
        # speed bench, not a staleness trial: the slow edge may lag many
        # rounds and must not trip the bound mid-measurement
        os.environ["CGX_ASYNC_MAX_LAG"] = str(1 << 20)
    import datetime

    import torch
    import torch.distributed as dist

    from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX

    n = mb * 2**20 // 4
    store = dist.FileStore(initfile, ws)
    pg = ProcessGroupCGX(store, rank, ws, datetime.timedelta(seconds=120))
    plane = None
    if mode == "async":
        from torch_cgx_tpu.parallel import async_plane as ap

        def mem():
            si, ns_, leaders, lg, gen = pg.async_slice_info()
            return ap.Membership(
                slice_idx=si, n_slices=ns_, leaders=tuple(leaders),
                global_ranks=tuple(lg), generation=gen,
            )

        si0, _n2, leaders0, _lg0, _g0 = pg.async_slice_info()
        # transport_fn/intra_fn: re-resolved per generation (the sender
        # is rebuilt after a reconfigure); leaders fold + publish, the
        # slice's other ranks apply the leader's exact fold bytes.
        plane = ap.AsyncPlane(
            membership_fn=mem,
            transport_fn=pg.async_sender,
            intra_fn=pg.async_intra,
            is_leader=(rank == leaders0[si0]),
        )
    # deterministic quadratic: per-rank target t_r, loss 0.5||p - t_r||^2,
    # global optimum mean(t_r); params start identical on every rank
    rng = np.random.default_rng(7)
    targets = rng.standard_normal((ws, n)).astype(np.float32)
    p = np.zeros(n, np.float32)
    denom = ws if mode == "sync" else half  # async: intra-slice mean
    lr = 0.5
    t0 = time.perf_counter()
    for step in range(iters):
        if step == 1:
            t0 = time.perf_counter()  # exclude the warm step
        g = p - targets[rank]
        t = torch.from_numpy(g.copy())
        pg.allreduce([t]).wait()
        p = p - lr * (t.numpy() / denom)
        if plane is not None:
            p = plane.maybe_outer_step(step, p)
    dt = (time.perf_counter() - t0) / max(1, iters - 1)
    if rank == 0:
        opt = targets.mean(axis=0)
        rec = {
            "t_ms": dt * 1e3,
            "opt_dist": float(
                np.linalg.norm(p - opt) / max(np.linalg.norm(opt), 1e-9)
            ),
        }
        if plane is not None and plane.first_delta_crc is not None:
            rec["delta_crc"] = int(plane.first_delta_crc)
        q.put(rec)
    pg.shutdown()


def _async_dcn_child(mb: int, ws: int, iters: int, h: int, mode: str,
                     delay_ms: int) -> None:
    """Child: one 2-slice bridge run (ws real processes) in the given
    mode; prints one JSON line with timing + the convergence proxy."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    with tempfile.TemporaryDirectory() as d:
        initfile = os.path.join(d, "init")
        procs = [
            ctx.Process(
                target=_async_dcn_rank,
                args=(r, ws, initfile, mb, iters, h, mode, delay_ms, q),
            )
            for r in range(ws)
        ]
        for p in procs:
            p.start()
        try:
            rec = q.get(timeout=600)
        finally:
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
    print(json.dumps(rec))


def bench_async_dcn(mb: int = 8, ws: int = 4, iters: int = 6,
                    h: int = 2) -> dict:
    """Async-vs-sync cross-slice record (the ISSUE 13 acceptance row):

    1. unfaulted sync run → the base step time the fault scales from;
    2. sync run with a 10x ``slow_rank@edge=dcn`` fault on slice B's
       leader — the synchronous two-level path stalls every step;
    3. async run (``CGX_ASYNC=on``) under the SAME fault — the cross
       stage leaves the critical path, deltas ship every ``h`` steps
       through the sender thread;
    4. a repeat of (3): the round-0 delta crc must match byte-for-byte
       (deterministic codec under the fixed seed).

    ``vs_baseline`` = faulted-sync / faulted-async step time (the
    acceptance floor is 1.5x); the convergence proxy (distance to the
    quadratic's global optimum after the same number of steps) rides in
    ``detail`` as ``loss_delta``."""
    if ws % 2 or ws < 4:
        raise ValueError(f"--ws {ws} must be even and >= 4 (2 slices)")
    me = str(Path(__file__).resolve())
    env = {**os.environ}
    for k in ("CGX_ASYNC", "CGX_ASYNC_H", "CGX_FAULTS", "CGX_SHM_HOST_ID"):
        env.pop(k, None)

    def run(mode: str, delay_ms: int) -> dict:
        return _run_json_child(
            [sys.executable, me, "--async-dcn-child", str(mb), str(ws),
             str(iters), str(h), mode, str(delay_ms)], env,
        )

    base = run("sync", 0)
    delay_ms = max(50, int(round(10 * base["t_ms"])))
    sync_f = run("sync", delay_ms)
    async_f = run("async", delay_ms)
    async_r = run("async", delay_ms)
    crc_a, crc_r = async_f.get("delta_crc"), async_r.get("delta_crc")
    if crc_a is None or crc_r is None:
        # A missing crc means NO outer round ever fired — the async arm
        # did zero cross-slice work and the "speedup" would really
        # measure skipping reconciliation entirely. Fail loudly instead
        # of committing a vacuous record.
        raise AssertionError(
            f"async-dcn bench: no outer round fired in the async run "
            f"(h={h} vs iters={iters}?) — raise --iters or lower --h"
        )
    if crc_a != crc_r:
        raise AssertionError(
            "async-dcn bench: round-0 delta crc differs across repeated "
            f"runs ({crc_a:#x} vs {crc_r:#x}) — the deterministic-delta "
            "contract of parallel/async_plane.py is broken"
        )
    t_sync, t_async = sync_f["t_ms"], async_f["t_ms"]
    gbytes = mb * 2**20 / 1e9
    return {
        "metric": f"async_vs_sync_xslice_{BITS}bit_{mb}MB_x{ws}",
        "value": round(gbytes / (t_async / 1e3), 3),
        "unit": "GB/s",
        "vs_baseline": round(t_sync / t_async, 3),
        # Host-plane measurement (the bridge always runs on host CPU) —
        # a genuine trajectory, like bench_schedule/shm_bench.
        "backend": "host",
        "chip": "host",
        "detail": {
            "t_sync_faulted_ms": round(t_sync, 3),
            "t_async_faulted_ms": round(t_async, 3),
            "t_sync_clean_ms": round(base["t_ms"], 3),
            "slow_edge_ms": delay_ms,
            "ws": ws,
            "slices": 2,
            "payload_MB": mb,
            "iters": iters,
            "async_h": h,
            "opt_dist_sync": sync_f["opt_dist"],
            "opt_dist_async": async_f["opt_dist"],
            "loss_delta": round(
                async_f["opt_dist"] - sync_f["opt_dist"], 6
            ),
            "delta_crc": async_f.get("delta_crc"),
            "deltas": "bit-reproducible (round-0 wire crc equal across "
                      "2 runs under the fixed seed)",
            "bridge": "ProcessGroupCGX store bridge, ws real processes, "
                      "2 fake slices via CGX_SHM_HOST_ID",
        },
    }


# ---------------------------------------------------------------------------
# Unified wire plane (ISSUE 10): each routed edge's collective raw vs
# compressed on the same payload — ring-attention/pipeline ppermute hops and
# the MoE dispatch all_to_all through wire.dispatch, with a bit-equality
# pre-flight on the unconfigured edge (it must lower to the plain lax
# collective) and a quantization-envelope allclose on the compressed one.
# Runs on real chips when >= ws exist, else a forced CPU multi-device
# platform (records then key into the `@cpu` trajectories).
# ---------------------------------------------------------------------------


def _wire_child(mb: int, ws: int, bits: int, iters: int) -> None:
    """Child: per-edge raw-vs-compressed timings; one JSON line."""
    import re as _re

    from torch_cgx_tpu.config import CompressionConfig
    from torch_cgx_tpu.wire import EdgeConfig
    from torch_cgx_tpu.wire import dispatch as wire_dispatch
    from torch_cgx_tpu.wire import edges as wire_edges

    n = mb * 2**20 // 4  # per-device fp32 elements
    mesh = Mesh(np.asarray(jax.devices()[:ws]), ("d",))
    perm = [(i, (i + 1) % ws) for i in range(ws)]
    cc = CompressionConfig(bits=bits, bucket_size=BUCKET)
    rng = np.random.default_rng(0)

    def timed(fn, x):
        def sync(o):
            np.asarray(jax.device_get(jax.tree.leaves(o)[0].ravel()[:1]))

        for _ in range(2):
            sync(fn(x))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        sync(out)
        return (time.perf_counter() - t0) / iters

    out = {
        "backend": jax.default_backend(),
        "chip": jax.devices()[0].device_kind,
        "edges": {},
    }

    def measure(kind, name, edge_fn, plain_fn, payload, specs):
        shard = dict(mesh=mesh, in_specs=specs, out_specs=specs,
                     check_vma=False)
        f_raw = jax.jit(shard_map(edge_fn, **shard))
        f_plain = jax.jit(shard_map(plain_fn, **shard))
        r_raw, r_plain = np.asarray(f_raw(payload)), np.asarray(f_plain(payload))
        if not (r_raw == r_plain).all():
            raise AssertionError(
                f"wire bench pre-flight: unconfigured {kind} edge is not "
                "bit-equal to the plain collective"
            )
        wire_edges.set_edge_config(
            kind, "^" + _re.escape(name) + "$", EdgeConfig(cc=cc)
        )
        f_comp = jax.jit(shard_map(edge_fn, **shard))  # fresh trace
        r_comp = np.asarray(f_comp(payload))
        envelope = 2.0 * float(np.abs(payload).max()) / (2**bits - 1)
        if not np.allclose(r_comp, r_raw, atol=envelope):
            raise AssertionError(
                f"wire bench pre-flight: {kind} compressed result outside "
                f"the {bits}-bit envelope "
                f"(max diff {np.abs(r_comp - r_raw).max():.3g} > {envelope:.3g})"
            )
        out["edges"][kind] = {
            "t_raw_ms": timed(f_raw, payload) * 1e3,
            "t_compressed_ms": timed(f_comp, payload) * 1e3,
            "max_abs_diff": float(np.abs(r_comp - r_raw).max()),
            "envelope": envelope,
        }

    per = _xla_payload(n, ws)  # (ws, n), one row per device
    for kind, name in (("ring_kv", "bench.kv"), ("pp_act", "bench.act")):
        measure(
            kind, name,
            lambda xs, k=kind, nm=name: wire_dispatch.wire_ppermute(
                xs, "d", perm, kind=k, name=nm
            ),
            lambda xs: lax.ppermute(xs, "d", perm),
            per, P("d"),
        )
    # MoE dispatch buffer (E, C, D), E % ws == 0, replicated input: the
    # all_to_all splits the expert dim locally like the EP helpers do.
    e_dim, cap = ws * 4, 64
    d_model = max(32, n // (e_dim * cap))
    buf = rng.normal(size=(e_dim, cap, d_model)).astype(np.float32)
    measure(
        "moe_a2a", "bench.a2a",
        lambda t: wire_dispatch.wire_all_to_all(
            t, "d", split_axis=0, concat_axis=1, kind="moe_a2a",
            name="bench.a2a",
        ),
        lambda t: lax.all_to_all(
            t, "d", split_axis=0, concat_axis=1, tiled=True
        ),
        buf, P(),
    )
    print(json.dumps(out))


def bench_wire(mb: int = 8, ws: int = 4, bits: int = 4,
               iters: int = 5) -> list:
    """Per-edge compressed-vs-raw records for the unified wire plane (the
    ISSUE 10 acceptance bench): one BENCH_LOG row per edge kind, each
    carrying the pre-flight evidence (unconfigured edge bit-equal to the
    plain collective; compressed within the quantization envelope)."""
    env = {
        **os.environ,
        "CGX_WIRE": "on",
        "CGX_COMPRESSION_BUCKET_SIZE": str(BUCKET),
    }
    use_real = False
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import json, jax; print(json.dumps("
             "[jax.default_backend(), len(jax.devices())]))"],
            env=dict(env), capture_output=True, text=True, timeout=180,
        )
        backend, n_dev = json.loads(
            (probe.stdout.strip().splitlines() or ["[]"])[-1]
        )
        use_real = backend != "cpu" and n_dev >= ws
    except Exception:
        pass
    if not use_real:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={ws}"
        )
    me = str(Path(__file__).resolve())
    child = _run_json_child(
        [sys.executable, me, "--wire-child",
         str(mb), str(ws), str(bits), str(iters)], env,
    )
    gbytes = mb * 2**20 / 1e9
    results = []
    for kind, d in child["edges"].items():
        t_r, t_c = d["t_raw_ms"], d["t_compressed_ms"]
        results.append({
            "metric": f"wire_{kind}_compressed_vs_raw_{bits}bit_{mb}MB_x{ws}",
            "value": round(gbytes / (t_c / 1e3), 3),
            "unit": "GB/s",
            "vs_baseline": round(t_r / t_c, 3),
            "chip": child.get("chip", "unknown"),
            "backend": child.get("backend", "unknown"),
            "detail": {
                "t_raw_ms": round(t_r, 3),
                "t_compressed_ms": round(t_c, 3),
                "ws": ws,
                "payload_MB": mb,
                "bits": bits,
                "iters": iters,
                "preflight": (
                    "raw edge bit-equal to plain collective; compressed "
                    f"max|diff| {d['max_abs_diff']:.3g} within envelope "
                    f"{d['envelope']:.3g}"
                ),
            },
        })
    return results


def _maybe_gate(results: list) -> tuple:
    """CGX_BENCH_GATE=1: run tools/bench_gate.py on the fresh records
    against the committed trajectory BEFORE they are logged — a regressed
    run exits nonzero, and the offending rows land in BENCH_LOG flagged
    ``unresolved`` (the gate's normalizer skips such rows), so a cliff
    neither passes silently nor ratchets its own baseline median down.
    Returns ``(exit code, regressed metric names)`` — only the named
    metrics are flagged, so a healthy family measured in the same run
    keeps feeding its own baseline history."""
    if os.environ.get("CGX_BENCH_GATE", "0") != "1":
        return 0, set()
    proc = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "tools" / "bench_gate.py"),
         "--candidate", "-", "--json"],
        input="".join(
            json.dumps({"tool": "bench", **r}) + "\n" for r in results
        ),
        capture_output=True, text=True,
    )
    sys.stderr.write(proc.stdout + proc.stderr)
    regressed = set()
    try:
        verdict = json.loads(proc.stdout)
        regressed = {r["metric"] for r in verdict.get("regressions", [])}
    except (ValueError, TypeError, AttributeError):
        pass
    return proc.returncode, regressed


def _gate_and_log(results: list) -> int:
    """The shared bench epilogue: gate BEFORE logging — the candidate must
    not be part of the history it is judged against, and a regressed row
    must not poison future baseline medians (it is logged, but flagged out
    of the gate's view). Only rc == 1 is a regression VERDICT; any other
    nonzero is a gate infrastructure error (missing log, bad args) — the
    measurement is healthy, so log it clean and don't fail the bench.
    Returns the exit code the caller should propagate."""
    rc, regressed = _maybe_gate(results)
    if rc not in (0, 1):
        print(f"bench: bench_gate errored (exit {rc}); measurement "
              "logged ungated", file=sys.stderr)
        rc = 0
    for r in results:
        rec = {"tool": "bench", **r}
        # Flag only the metrics the gate named (a JSON-parse failure with
        # rc==1 degrades to flagging everything — never let a regressed
        # row slip into the baselines clean).
        if rc == 1 and (not regressed or r.get("metric") in regressed):
            rec["unresolved"] = (
                "bench_gate: regression vs the committed trajectory "
                "(see gate output); excluded from future baselines"
            )
        log_jsonl(rec)
    return rc


# ---------------------------------------------------------------------------
# Serving plane (ISSUE 15): quantized vs raw-f16 KV shipping under a
# bandwidth-modeled prefill→decode wire, measured as continuous-batching
# tokens/s and TTFT. The child is CPU-pinned (the decode program runs on
# the test backend — rows key into the `@cpu` trajectories); the wire
# model is the sender thread's byte-proportional throttle, so wire-byte
# savings translate to admission latency exactly as on a real
# bandwidth-bound interconnect (the --async-dcn injected-delay
# methodology, applied to serving).
# ---------------------------------------------------------------------------


def _serve_child(
    bits: int, requests: int, prompt: int, gen: int, batch: int,
    throttle_mbps: float,
) -> None:
    """Child: one serving run at CGX_KV_BITS=`bits`; one JSON line."""
    import tempfile
    import threading
    import zlib

    # Span telemetry for the run (ISSUE 17): the critical-path engine
    # decomposes the measured TTFT post-hoc from these — set before any
    # serving object records a span.
    mdir = tempfile.mkdtemp(prefix="cgx-serve-bench-")
    os.environ["CGX_METRICS_DIR"] = mdir

    from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
    from torch_cgx_tpu.serving.prefill import PrefillWorker
    from torch_cgx_tpu.serving.scheduler import (
        ContinuousBatchScheduler, GPT2Server, Request, ServeConfig,
    )
    from torch_cgx_tpu.serving.transport import KvPageReceiver
    from torch_cgx_tpu.utils.logging import metrics

    class _DictStore:
        """Minimal c10d-Store look-alike (the test-suite FakeStore)."""

        def __init__(self):
            import threading as _t

            self._d, self._l = {}, _t.Lock()

        def set(self, k, v):
            with self._l:
                self._d[k] = bytes(v)

        def get(self, k):
            with self._l:
                if k not in self._d:
                    raise KeyError(k)
                return self._d[k]

        def add(self, k, v):
            with self._l:
                cur = int(self._d.get(k, b"0")) + int(v)
                self._d[k] = str(cur).encode()
                return cur

        def delete_key(self, k):
            with self._l:
                self._d.pop(k, None)

    from torch_cgx_tpu import config as cfg_mod

    # The serving stack resolves the width from CGX_KV_BITS; the argv
    # copy exists only for the process list — they must agree or the
    # row would label a width it never measured.
    assert bits == cfg_mod.kv_bits(), (bits, cfg_mod.kv_bits())
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    page_tokens = 16
    sv = ServeConfig(
        page_tokens=page_tokens, max_batch=batch,
        max_pages=max(64, requests * ((prompt + gen) // page_tokens + 2)),
        max_seq=prompt + gen + page_tokens, ship_depth=4,
    )
    server = GPT2Server(cfg, params, sv)
    store = _DictStore()
    recv = KvPageReceiver(store)
    sched = ContinuousBatchScheduler(server, receiver=recv)
    worker = PrefillWorker(
        server, store, throttle_gbps=throttle_mbps / 1e3
    )
    rng = np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(0, cfg.vocab_size, prompt)]
        for _ in range(requests)
    ]
    # Warm-up: compile prefill/decode/commit programs outside the timed
    # window (a cold jit would otherwise stall the first streams into
    # the failover rung and measure the compiler, not the wire).
    warm = Request(id="warm", tokens=list(prompts[0]),
                   max_new_tokens=page_tokens + 2)
    sched.submit(warm)
    assert sched.run(deadline_s=600), "serve bench warm-up wedged"
    metrics.reset()
    reqs = [
        Request(id=f"r{i}", tokens=list(p), max_new_tokens=gen)
        for i, p in enumerate(prompts)
    ]
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r, remote=True)
    t = threading.Thread(
        target=lambda: [worker.serve(r.id, r.tokens) for r in reqs]
    )
    t.start()
    ok = sched.run(deadline_s=600)
    wall = time.perf_counter() - t0
    t.join(timeout=30)
    worker.stop()
    assert ok, "serve bench run left outstanding requests"
    failovers = metrics.get("cgx.serve.prefill_failovers")
    assert failovers == 0, (
        f"serve bench: {failovers} prefill failover(s) fired — the "
        "measurement would mix local-prefill admissions into the wire "
        "contrast; raise CGX_SERVE_PREFILL_TIMEOUT_MS"
    )
    tokens = sum(len(r.output) for r in reqs)
    ttft = metrics.histogram_stats("cgx.serve.ttft_ms") or {}
    crc = zlib.crc32(
        b"".join(
            np.asarray(r.output, np.int32).tobytes() for r in reqs
        )
    )
    # Post-hoc TTFT decomposition over the run's own span files: mean
    # per-request admission/prefill/ship/decode ms (the warm-up request
    # is excluded — its spans predate the timed window), plus the total
    # kv.ship wall time the pred-ratio contrast below needs.
    from torch_cgx_tpu.observability import critpath as critpath_mod
    from torch_cgx_tpu.observability import timeline as timeline_mod

    timeline_mod.flush()
    timed_ids = {r.id for r in reqs}
    ttft_components = {}
    ship_wall_s = 0.0
    try:
        rep = critpath_mod.analyze(mdir, use_cache=False)
        sums: dict = {}
        n_req = 0
        for rid, rr in rep["requests"].items():
            if rid not in timed_ids or rr["ttft_s"] is None:
                continue
            n_req += 1
            for k, v in rr["components"].items():
                sums[k] = sums.get(k, 0.0) + v
        if n_req:
            ttft_components = {
                k: round(v / n_req * 1e3, 3) for k, v in sorted(sums.items())
            }
        for tr in critpath_mod.load_tracks(mdir).values():
            for ev in tr["events"]:
                if ev.get("name") == "kv.ship" and ev.get("req") in timed_ids:
                    ship_wall_s += float(ev.get("dur_s", 0.0))
    except Exception:
        pass  # a breakdown failure must not kill the bench row
    print(json.dumps({
        "tok_s": tokens / wall,
        "wall_s": wall,
        "tokens": tokens,
        "ttft_p50_ms": ttft.get("p50", 0.0),
        "ttft_mean_ms": ttft.get("mean", 0.0),
        "ttft_components": ttft_components,
        "ship_wall_s": round(ship_wall_s, 6),
        "tokens_crc": crc,
        "kv_bytes_wire": metrics.get("cgx.serve.kv_bytes_wire"),
        "backend": jax.default_backend(),
        "chip": jax.devices()[0].device_kind,
    }))


def _serve_pred_components(rec: dict, throttle_mbps: float) -> dict:
    """{"ship": predicted/measured} for a serve child record: the
    modeled link makes the ship prediction exact arithmetic
    (bytes / rate), so the ratio gates transport efficiency itself."""
    ship_wall = float(rec.get("ship_wall_s") or 0.0)
    wire_bytes = float(rec.get("kv_bytes_wire") or 0.0)
    if ship_wall <= 1e-9 or wire_bytes <= 0 or throttle_mbps <= 0:
        return {}
    predicted_s = wire_bytes / (throttle_mbps / 1e3 * 1e9)
    return {"ship": round(predicted_s / ship_wall, 4)}


def bench_serve(
    requests: int = 10, prompt: int = 96, gen: int = 24, batch: int = 8,
    bits: int = 8, throttle_mbps: float = 0.5,
) -> list:
    """Quantized-vs-raw KV shipping records (the ISSUE 15 acceptance
    rows): the same request stream served twice under a
    ``throttle_mbps``-modeled prefill→decode wire — once with raw-f16 KV
    pages (``CGX_KV_BITS=0``, the baseline) and once quantized at
    ``bits``. ``vs_baseline`` on the tokens/s row is quantized/f16
    (acceptance floor 1.3x at 8 bits); the TTFT row gates through the
    inverse-latency trajectory. Greedy outputs must be token-identical
    between the arms (crc over every generated token) — the wire saves
    bytes, never answers."""
    me = str(Path(__file__).resolve())
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CGX_KV_BITS", "CGX_KV_PAGE_TOKENS", "CGX_WIRE"):
        env.pop(k, None)
    env["CGX_SERVE_PREFILL_TIMEOUT_MS"] = "60000"

    def run(kv_bits: int) -> dict:
        child_env = dict(env, CGX_KV_BITS=str(kv_bits))
        return _run_json_child(
            [sys.executable, me, "--serve-child", str(kv_bits),
             str(requests), str(prompt), str(gen), str(batch),
             str(throttle_mbps)], child_env,
        )

    f16 = run(0)
    quant = run(bits)
    if quant["tokens_crc"] != f16["tokens_crc"]:
        raise AssertionError(
            f"serve bench: greedy outputs differ between {bits}-bit and "
            f"f16 KV (crc {quant['tokens_crc']:#x} vs "
            f"{f16['tokens_crc']:#x}) — the quantized-KV bit envelope "
            "flipped an argmax on the bench model"
        )
    shared_detail = {
        "requests": requests,
        "prompt_tokens": prompt,
        "gen_tokens": gen,
        "max_batch": batch,
        "kv_bits": bits,
        "wire_model_MBps": throttle_mbps,
        "t_f16_wall_s": round(f16["wall_s"], 3),
        "t_quant_wall_s": round(quant["wall_s"], 3),
        "kv_bytes_wire_f16": f16["kv_bytes_wire"],
        "kv_bytes_wire_quant": quant["kv_bytes_wire"],
        "greedy_token_identity": True,
        "transport": "store counter streams (publish-after-write), "
                     "sender throttled to the modeled wire rate",
        "backend": f16["backend"],
        "chip": f16["chip"],
    }
    tag = f"{bits}bit_p{prompt}_g{gen}_b{batch}"
    return [
        {
            "metric": f"serve_tokens_per_s_{tag}",
            "value": round(quant["tok_s"], 3),
            "unit": "tok/s",
            "vs_baseline": round(quant["tok_s"] / f16["tok_s"], 3),
            "backend": f16["backend"],
            "chip": f16["chip"],
            "detail": dict(shared_detail,
                           tok_s_f16=round(f16["tok_s"], 3)),
        },
        {
            "metric": f"serve_ttft_ms_{tag}",
            "value": round(quant["ttft_p50_ms"], 3),
            "unit": "ms",
            "ttft_ms": round(quant["ttft_p50_ms"], 3),
            "vs_baseline": round(
                f16["ttft_p50_ms"] / quant["ttft_p50_ms"], 3
            ) if quant["ttft_p50_ms"] else 0.0,
            # Critical-path TTFT decomposition of the quantized arm
            # (mean ms per request) + the wire-model prediction ratio
            # for the ship stage: the modeled link rate is exact by
            # construction, so predicted ship time is bytes/rate — the
            # trajectory catches a transport regression that inflates
            # ship wall time beyond what the bytes explain.
            "ttft_components": quant.get("ttft_components") or {},
            "pred_components": _serve_pred_components(
                quant, throttle_mbps
            ),
            "backend": f16["backend"],
            "chip": f16["chip"],
            "detail": dict(
                shared_detail,
                ttft_p50_ms_f16=round(f16["ttft_p50_ms"], 3),
                ttft_components_f16=f16.get("ttft_components") or {},
            ),
        },
    ]


# ---------------------------------------------------------------------------
# Elastic rejoin (ISSUE 16): announce-to-step-loop latency of a
# checkpoint-free rank join. ws survivor processes run a live bridge
# step loop under the elastic coordinator; one joiner process announces,
# receives the snapshot pages over the counter-stream wire, and re-enters
# the step loop at the bumped generation. The committed number is the
# joiner's full join() wall clock — no checkpoint file is ever written or
# read. Lower is better: bench_gate trajects the inverse (joins/s) via
# the top-level ``rejoin_latency_ms`` field.
# ---------------------------------------------------------------------------

_REJOIN_TAIL = 4  # post-join steps everyone runs together before exiting
_REJOIN_MAX_STEPS = 400
_REJOIN_STEP_S = 0.05
_REJOIN_GRAD_N = 4096  # tiny allreduce: steps pace on the sleep, not bytes


def _rejoin_env(donors: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_ELASTIC"] = "1"
    os.environ["CGX_JOIN_DONORS"] = str(donors)


def _rejoin_step_fn():
    import torch

    def step_fn(group, state, idx):
        g = np.full(_REJOIN_GRAD_N, 1e-3 * (idx + 1), np.float32)
        t = torch.from_numpy(g)
        group.allreduce([t]).wait()
        time.sleep(_REJOIN_STEP_S)
        return state

    return step_fn


def _rejoin_rank(rank, ws, initfile, mb, donors, q):
    import traceback

    try:
        _rejoin_env(donors)
        import datetime

        import torch.distributed as dist

        from torch_cgx_tpu.robustness import elastic as el
        from torch_cgx_tpu.robustness.supervisor import RecoverySupervisor
        from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX

        n = mb * 2**20 // 4
        store = dist.FileStore(initfile, ws + 1)
        pg = ProcessGroupCGX(
            store, rank, ws, datetime.timedelta(seconds=120)
        )
        sup = RecoverySupervisor(store, pg)
        el.ElasticCoordinator(store, sup)
        rng = np.random.default_rng(11)
        state = rng.standard_normal(n).astype(np.float32)
        fn = _rejoin_step_fn()
        step, end = 0, None
        while True:
            state = sup.run_steps(state, 1, fn, start_step=step)
            step += 1
            if end is None and sup.generation >= 1:
                # The grow fired at the entry of the step just run, so
                # the join step is step-1; the joiner replays from there
                # and everyone stops at the same index.
                end = (step - 1) + _REJOIN_TAIL
            if end is not None and step >= end:
                break
            if step >= _REJOIN_MAX_STEPS:
                raise RuntimeError(
                    f"rank {rank}: joiner never admitted within "
                    f"{_REJOIN_MAX_STEPS} steps"
                )
        pg.shutdown()
        q.put((rank, None, None))
    except Exception:
        q.put((rank, traceback.format_exc(), None))


def _rejoin_joiner(ws, initfile, mb, donors, q):
    import traceback

    try:
        _rejoin_env(donors)
        from torch_cgx_tpu.robustness import elastic as el
        from torch_cgx_tpu.robustness.supervisor import RecoverySupervisor
        from torch_cgx_tpu.utils.logging import metrics as m

        import torch.distributed as dist

        n = mb * 2**20 // 4
        store = dist.FileStore(initfile, ws + 1)
        t0 = time.perf_counter()
        res = el.join(store, np.zeros(n, np.float32), global_rank=ws)
        join_ms = (time.perf_counter() - t0) * 1e3
        sup = RecoverySupervisor(store, res.group)
        el.ElasticCoordinator(store, sup, consumed=res.decision.intents_n)
        sup.run_steps(res.state, _REJOIN_TAIL, _rejoin_step_fn(),
                      start_step=res.step)
        res.group.shutdown()
        q.put(("joiner", None, {
            "join_ms": join_ms,
            "step": res.step,
            "generation": res.generation,
            "members": res.members,
            "pages": m.get("cgx.elastic.pages_received"),
        }))
    except Exception:
        q.put(("joiner", traceback.format_exc(), None))


def _rejoin_child(mb: int, ws: int, donors: int) -> None:
    """Child: one live-bridge join round (ws survivors + 1 joiner, all
    real processes); prints one JSON line with the join latency."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    with tempfile.TemporaryDirectory() as d:
        initfile = os.path.join(d, "init")
        procs = [
            ctx.Process(target=_rejoin_rank,
                        args=(r, ws, initfile, mb, donors, q))
            for r in range(ws)
        ]
        for p in procs:
            p.start()
        time.sleep(0.5)  # survivors enter the step loop first
        jp = ctx.Process(target=_rejoin_joiner,
                         args=(ws, initfile, mb, donors, q))
        jp.start()
        procs.append(jp)
        try:
            rec, errs = None, []
            for _ in range(ws + 1):
                tag, err, payload = q.get(timeout=300)
                if err:
                    errs.append(f"{tag}: {err}")
                if payload is not None:
                    rec = payload
        finally:
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
    if errs or rec is None:
        raise RuntimeError("rejoin bench failed:\n" + "\n".join(errs))
    print(json.dumps(rec))


def bench_rejoin(mb: int = 8, ws: int = 2, donors: int = 1,
                 iters: int = 3) -> dict:
    """Elastic rejoin record (the ISSUE 16 acceptance row): median over
    `iters` fresh join rounds of the joiner's announce-to-step-loop wall
    clock. The joiner holds zero state at start — everything it resumes
    with arrived as snapshot pages over the store wire; the run writes
    no checkpoint file."""
    me = str(Path(__file__).resolve())
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CGX_FAULTS", "CGX_ELASTIC", "CGX_JOIN_DONORS",
              "CGX_SHM_HOST_ID"):
        env.pop(k, None)
    runs = [
        _run_json_child(
            [sys.executable, me, "--rejoin-child",
             str(mb), str(ws), str(donors)], env,
        )
        for _ in range(iters)
    ]
    lat = sorted(r["join_ms"] for r in runs)
    med = lat[len(lat) // 2]
    rep = min(runs, key=lambda r: abs(r["join_ms"] - med))
    return {
        "metric": f"elastic_rejoin_{mb}MB_ws{ws}",
        "value": round(med, 3),
        "unit": "ms",
        "rejoin_latency_ms": round(med, 3),
        "backend": "host",
        "chip": "host",
        "detail": {
            "ws_before": ws,
            "ws_after": ws + 1,
            "donors": donors,
            "payload_MB": mb,
            "runs_ms": [round(x, 3) for x in lat],
            "join_step": rep["step"],
            "generation": rep["generation"],
            "members": rep["members"],
            "snapshot_pages": rep["pages"],
            "checkpoint_files": 0,
            "bridge": "ProcessGroupCGX store bridge, ws+1 real "
                      "processes; join() timed announce -> admitted -> "
                      "pages received -> step-loop re-entry",
        },
    }


# ---------------------------------------------------------------------------
# Socket transport vs store fallback (ISSUE 20): the same bridge
# allreduce through both cross-process byte planes — CGX_TRANSPORT=socket
# (push-mode frames over supervised TCP links) vs the legacy store path
# (publish + bounded-poll get) — with CGX_SHM=0 in both children so the
# contrast is purely the transport, a crc bit-equality pre-flight (the
# socket plane must be a byte-identical carrier), and a small-message
# latency contrast: the store path pays a poll tick per take, the socket
# plane wakes on frame arrival, so small collectives are expected >= 2x
# faster. A LinkThrottle-modeled slow-link row prices the same payload
# through a constrained link (the serving plane's byte-proportional
# model) against the model's own serialization time.
# ---------------------------------------------------------------------------


def _transport_bridge_rank(rank, ws, initfile, mb, iters, small_iters,
                           mode, q):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_SHM"] = "0"  # isolate the cross-process byte plane
    if mode == "socket":
        os.environ["CGX_TRANSPORT"] = "socket"
    else:
        os.environ.pop("CGX_TRANSPORT", None)
    import zlib

    import torch
    import torch.distributed as dist

    import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"

    n = mb * 2**20 // 4
    base = torch.arange(n, dtype=torch.float32) / n - 0.5
    big = (rank + 1) * base
    small = ((rank + 1) * base[:1024]).clone()
    dist.init_process_group(
        "cgx", init_method=f"file://{initfile}", rank=rank, world_size=ws
    )
    try:
        res = big.clone()
        dist.all_reduce(res)  # warm (arena growth) + crc capture
        dist.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            dist.all_reduce(big)
        dist.barrier()
        t_big = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(small_iters):
            dist.all_reduce(small)
        dist.barrier()
        t_small = (time.perf_counter() - t0) / small_iters
        if rank == 0:
            q.put({
                "t_big_ms": t_big * 1e3,
                "t_small_ms": t_small * 1e3,
                "crc": zlib.crc32(res.numpy().tobytes()),
            })
    finally:
        dist.destroy_process_group()


def _transport_bridge_child(mb, ws, iters, small_iters, mode):
    """Child: time the bridge allreduce over one transport mode (ws real
    processes); one JSON line."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    with tempfile.TemporaryDirectory() as d:
        initfile = os.path.join(d, "init")
        procs = [
            ctx.Process(
                target=_transport_bridge_rank,
                args=(r, ws, initfile, mb, iters, small_iters, mode, q),
            )
            for r in range(ws)
        ]
        for p in procs:
            p.start()
        try:
            rec = q.get(timeout=600)
        finally:
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
    print(json.dumps(rec))


def _transport_throttle_row(mb: int = 4, gbps: float = 0.5) -> dict:
    """LinkThrottle-modeled slow-link row: one SocketTransport pair in
    this process, the sender constrained by the serving plane's
    byte-proportional LinkThrottle at ``gbps`` — measured wall clock for
    an ``mb``-MB post+fetch vs the model's own serialization time."""
    import threading as _threading

    from torch_cgx_tpu.serving.transport import LinkThrottle
    from torch_cgx_tpu.torch_backend import transport as _tp

    class _DictStore:
        def __init__(self):
            self._d = {}
            self._lock = _threading.Lock()

        def set(self, k, v):
            with self._lock:
                self._d[k] = bytes(v)

        def get(self, k):
            with self._lock:
                return self._d[k]

        def check(self, keys):
            with self._lock:
                return all(k in self._d for k in keys)

    store = _DictStore()

    def addr(p):
        return f"tpbench/addr/{p}"

    tx = _tp.SocketTransport(
        store, "0", addr, rank=0, io_timeout_s=10.0,
        throttle=LinkThrottle(gbps),
    )
    rx = _tp.SocketTransport(store, "1", addr, rank=1, io_timeout_s=10.0)
    payload = os.urandom(mb * 2**20)
    try:
        tx.post("tpbench/warm", b"x" * 64, to=("1",))
        rx.fetch("tpbench/warm", timeout_s=10.0, peer="0")
        t0 = time.perf_counter()
        tx.post("tpbench/pay", payload, to=("1",))
        got = rx.fetch("tpbench/pay", timeout_s=120.0, peer="0")
        dt = time.perf_counter() - t0
    finally:
        tx.close()
        rx.close()
    if got != payload:
        raise RuntimeError("throttled socket roundtrip corrupted payload")
    modeled_s = len(payload) / (gbps * 1e9)
    return {
        "gbps": gbps,
        "payload_MB": mb,
        "measured_ms": round(dt * 1e3, 3),
        "modeled_ms": round(modeled_s * 1e3, 3),
        "measured_gbps": round(len(payload) / 1e9 / dt, 4),
    }


def bench_transport(mb: int = 4, ws: int = 2, iters: int = 10,
                    small_iters: int = 40) -> dict:
    """Socket-vs-store data-plane record (the ISSUE 20 acceptance row).
    Children are fresh spawned process groups (the transport engages at
    backend construction, so the mode must be in the env before init)."""
    me = str(Path(__file__).resolve())
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CGX_FAULTS", "CGX_TRANSPORT", "CGX_SHM",
              "CGX_SHM_HOST_ID"):
        env.pop(k, None)
    args = [str(mb), str(ws), str(iters), str(small_iters)]
    store = _run_json_child(
        [sys.executable, me, "--transport-bridge-child", *args, "store"],
        env,
    )
    sock = _run_json_child(
        [sys.executable, me, "--transport-bridge-child", *args, "socket"],
        env,
    )
    if store["crc"] != sock["crc"]:
        raise RuntimeError(
            "transport crc pre-flight failed: store crc "
            f"{store['crc']:#010x} != socket crc {sock['crc']:#010x} — "
            "the socket plane must be a byte-identical carrier"
        )
    small_speedup = (
        store["t_small_ms"] / sock["t_small_ms"]
        if sock["t_small_ms"] else 0.0
    )
    big_speedup = (
        store["t_big_ms"] / sock["t_big_ms"] if sock["t_big_ms"] else 0.0
    )
    gbytes = mb * 2**20 / 1e9
    return {
        "metric": f"transport_socket_vs_store_{mb}MB_x{ws}",
        "value": round(gbytes / (sock["t_big_ms"] / 1e3), 3),
        "unit": "GB/s",
        "vs_baseline": round(big_speedup, 3),
        "backend": "host",
        "chip": "host",
        "detail": {
            "ws": ws,
            "payload_MB": mb,
            "iters": iters,
            "small_iters": small_iters,
            "t_big_socket_ms": round(sock["t_big_ms"], 3),
            "t_big_store_ms": round(store["t_big_ms"], 3),
            "t_small_socket_ms": round(sock["t_small_ms"], 3),
            "t_small_store_ms": round(store["t_small_ms"], 3),
            "small_msg_speedup": round(small_speedup, 3),
            "small_msg_expectation": ">=2x — the store take pays a poll "
                                     "tick, the socket fetch wakes on "
                                     "frame arrival",
            "crc_preflight": "bit-identical",
            "slow_link": _transport_throttle_row(mb=min(mb, 4)),
            "bridge": "ProcessGroupCGX, ws real processes, CGX_SHM=0 "
                      "both modes; socket mode adds CGX_TRANSPORT=socket",
        },
    }


def main() -> None:
    from torch_cgx_tpu.utils import entry

    entry.setup_compile_cache()
    argv = sys.argv[1:]
    if argv and argv[0] == "--xla-allreduce-staged-child":
        _xla_staged_child(int(argv[1]), int(argv[2]), int(argv[3]))
        return
    if argv and argv[0] == "--xla-allreduce-bridge-child":
        _xla_bridge_child(int(argv[1]), int(argv[2]), int(argv[3]))
        return
    if argv and argv[0] == "--schedule-bridge-child":
        _sched_bridge_child(
            int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]), argv[5]
        )
        return
    if argv and argv[0] == "--wire-child":
        _wire_child(int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]))
        return
    if argv and argv[0] == "--serve-child":
        _serve_child(
            int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]),
            int(argv[5]), float(argv[6]),
        )
        return
    if argv and argv[0] == "--serve":
        # Serving-plane record: both
        # children are CPU-pinned single-process runs — never touches
        # the accelerator.
        _preflight_lint()
        kw = {}
        for flag, name, cast in (
            ("--requests", "requests", int), ("--prompt", "prompt", int),
            ("--gen", "gen", int), ("--batch", "batch", int),
            ("--bits", "bits", int),
            ("--throttle-mbps", "throttle_mbps", float),
        ):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = cast(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires a {cast.__name__} "
                        f"value, got {val!r}"
                    )
        results = bench_serve(**kw)
        rc = _gate_and_log(results)
        print(json.dumps(results))
        sys.exit(rc)
    if argv and argv[0] == "--transport-bridge-child":
        _transport_bridge_child(
            int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]), argv[5]
        )
        return
    if argv and argv[0] == "--transport":
        # Socket-vs-store transport record: bridge children are fresh
        # CPU-pinned process groups —
        # runs on any box without touching the accelerator.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--iters", "iters"),
                           ("--small-iters", "small_iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_transport(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    if argv and argv[0] == "--rejoin-child":
        _rejoin_child(int(argv[1]), int(argv[2]), int(argv[3]))
        return
    if argv and argv[0] == "--rejoin":
        # Elastic rejoin record:
        # all ranks are fresh CPU-pinned processes on the store bridge —
        # runs on any box without touching the accelerator.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--donors", "donors"), ("--iters", "iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_rejoin(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    if argv and argv[0] == "--async-dcn-child":
        _async_dcn_child(
            int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]),
            argv[5], int(argv[6]),
        )
        return
    if argv and argv[0] == "--async-dcn":
        # Async-vs-sync cross-slice record: bridge children are fresh
        # CPU-pinned process groups —
        # runs on any box without touching the accelerator.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--iters", "iters"), ("--h", "h")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_async_dcn(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    if argv and argv[0] == "--wire":
        # Per-edge wire-plane records:
        # the child is a fresh subprocess (real chips when available, a
        # forced CPU multi-device platform otherwise).
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--bits", "bits"), ("--iters", "iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        results = bench_wire(**kw)
        rc = _gate_and_log(results)
        print(json.dumps(results))
        sys.exit(rc)
    if argv and argv[0] == "--codec-roofline":
        # Codec roofline round-2 records: quantize roofline fraction +
        # producer-fused vs staged,
        # both wire pre-flighted and gated like every trajectory.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--bits", "bits"), ("--iters", "iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        results = bench_codec_roofline(**kw)
        rc = _gate_and_log(results)
        print(json.dumps(results))
        sys.exit(rc)
    if argv and argv[0] == "--schedule":
        # Pipelined-vs-monolithic schedule record: bridge children are
        # fresh CPU-pinned process
        # groups, so it runs on any box without touching the device.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--iters", "iters"), ("--chunks", "chunks")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_schedule(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    if argv and argv[0] == "--planner":
        # Planner-vs-static record:
        # bridge children are fresh CPU-pinned process groups — the
        # planner calibrates from the run's own telemetry, the static
        # child reruns its chosen knobs by hand, and the committed row
        # carries predicted-vs-measured for the bench_gate floor.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--iters", "iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_planner(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    if argv and argv[0] == "--xla-allreduce":
        # Standalone staged-vs-bridge record: children are fresh
        # subprocesses, so the parent's backend
        # never wedges; the record lands in BENCH_LOG like every metric.
        _preflight_lint()
        kw = {}
        for flag, name in (("--mb", "mb"), ("--ws", "ws"),
                           ("--iters", "iters")):
            if flag in argv:
                idx = argv.index(flag) + 1
                val = argv[idx] if idx < len(argv) else ""
                try:
                    kw[name] = int(val)
                except ValueError:
                    sys.exit(
                        f"bench: {flag} requires an integer value, "
                        f"got {val!r}"
                    )
        result = bench_xla_allreduce(**kw)
        rc = _gate_and_log([result])
        print(json.dumps(result))
        sys.exit(rc)
    _preflight_lint()
    devices = jax.devices()
    extra = []
    if len(devices) > 1:
        result = bench_allreduce(devices)
    else:
        on_tpu = jax.default_backend() == "tpu"
        result = bench_codec(on_tpu)
        result["detail"]["train_step"] = bench_train_step(on_tpu)
        # The second codec round trip of the production SRA path, staged
        # vs fused — its own BENCH_LOG record so the fused-path trajectory
        # is gate-able independently of the raw kernel numbers.
        extra.append(bench_sra_epilogue(on_tpu))
    rc = _gate_and_log([result] + extra)
    print(json.dumps(result))
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
