"""Quantize-kernel experiment harness (single chip).

Measures one variant per invocation (keeps device-service load small and
output incremental):

    python tools/qbench.py current        # public quantize_batch fast path
    python tools/qbench.py current --tc 32
    python tools/qbench.py butterfly      # log-tree OR pack experiment
    python tools/qbench.py mul            # reciprocal-multiply encode
    python tools/qbench.py nometa         # payload-only store (bound)
    python tools/qbench.py read           # HBM read floor (max-reduce only)
    python tools/qbench.py dequant        # public dequantize_batch

All operands are generated on-device (no host->device copy of benchmark
payloads) and sized to 128 MB by default. Timing is a scan slope (scan_time).
Experimental kernels are byte-checked against the XLA codec oracle on a
small slice before timing — a variant that changes the wire is reported,
not silently timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CB = 32  # chunk buckets (codec.CHUNK_BUCKETS)


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


def make_variant_kernel(name: str, bits: int, b: int, tc: int):
    """Experimental flat-quantize kernels. Same wire contract as
    codec_pallas._quantize_flat_impl (words (C*bits*rb, 128) i32,
    meta (C*32, 2) f32)."""
    rb = b // 128
    maxlvl = np.float32((1 << bits) - 1)

    def meta_of(x4):
        # rb axis first (full-width folds), lane reduction on rb x less data
        # — same order as _quantize_flat_impl.
        bmax = jnp.max(jnp.max(x4, axis=2, keepdims=True), axis=3, keepdims=True)
        bmin = jnp.min(jnp.min(x4, axis=2, keepdims=True), axis=3, keepdims=True)
        unit = (bmax - bmin) * np.float32(1.0 / maxlvl)
        safe = jnp.where(unit > 0, unit, np.float32(1.0))
        return unit, bmin, safe

    def pack_sum(lvl):
        sub = lax.broadcasted_iota(jnp.int32, (tc, CB, rb, 128), 1)
        planes = [jnp.sum(((lvl >> w) & 1) << sub, axis=1) for w in range(bits)]
        return jnp.stack(planes, axis=1).reshape(tc * bits * rb, 128)

    def pack_butterfly(lvl):
        planes = []
        for w in range(bits):
            a = (lvl >> w) & 1  # (tc, 32, rb, 128)
            sh = 16
            while sh >= 1:
                a = a[:, :sh] | (a[:, sh : 2 * sh] << sh)
                sh //= 2
            planes.append(a.reshape(tc, rb, 128))
        return jnp.stack(planes, axis=1).reshape(tc * bits * rb, 128)

    def kernel(x_ref, w_ref, m_ref):
        x4 = x_ref[:].astype(jnp.float32).reshape(tc, CB, rb, 128)
        unit, bmin, safe = meta_of(x4)
        if name == "metalane":
            # Lane-major meta store: per chunk one (128,) row holding
            # [32 units | 32 mins | 64 zeros] — a full-width store instead
            # of the wire's (., 2) narrow pairs (the transpose back to the
            # wire layout outside the kernel costs one tiny XLA pass on
            # n/64 bytes). Measures the remedy for the narrow-store lead,
            # not just its removal (nometa). Payload identical to current.
            lvl = jnp.clip(
                jnp.floor((x4 - bmin) / safe + np.float32(0.5)), 0, maxlvl
            ).astype(jnp.int32)
            w_ref[:] = pack_sum(lvl)
            m_ref[:] = jnp.concatenate(
                [unit.reshape(tc, CB), bmin.reshape(tc, CB),
                 jnp.zeros((tc, 64), jnp.float32)],
                axis=1,
            )  # (tc, 128)
            return
        if name == "read":
            # One word per chunk derived from the reduction — the whole
            # input is read, almost nothing is computed or stored.
            chunk_u = jnp.max(unit, axis=1, keepdims=True)  # (tc,1,1,1)
            w_ref[:] = jnp.broadcast_to(
                chunk_u.astype(jnp.int32),
                (tc, bits, rb, 128),
            ).reshape(tc * bits * rb, 128)
            m_ref[:] = jnp.concatenate(
                [unit.reshape(tc * CB, 1), bmin.reshape(tc * CB, 1)], axis=1
            )
            return
        if name == "mul":
            lvl = jnp.clip(
                jnp.floor((x4 - bmin) * (np.float32(1.0) / safe) + np.float32(0.5)),
                0,
                maxlvl,
            ).astype(jnp.int32)
        else:
            lvl = jnp.clip(
                jnp.floor((x4 - bmin) / safe + np.float32(0.5)), 0, maxlvl
            ).astype(jnp.int32)
        packed = pack_butterfly(lvl) if name == "butterfly" else pack_sum(lvl)
        w_ref[:] = packed
        if name != "nometa":
            m_ref[:] = jnp.concatenate(
                [unit.reshape(tc * CB, 1), bmin.reshape(tc * CB, 1)], axis=1
            )
        else:
            m_ref[:] = jnp.zeros((tc * CB, 2), jnp.float32)

    return kernel


def run_variant_kernel(name, xs, bits, b, tc, interpret: bool = False):
    """``interpret=True`` runs the experiment kernel in Pallas interpret
    mode (CPU) — the suite smoke-checks every variant's shapes and wire
    bytes there, so a shape bug can't survive until a live-chip session
    (the round-5 `read` reshape bug burned a hardware step exactly that
    way)."""
    rows, m = xs.shape
    rb = b // 128
    n_chunks = rows * m // (CB * b)
    kernel = make_variant_kernel(name, bits, b, tc)
    if name == "metalane":
        meta_spec = pl.BlockSpec((tc, 128), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
        meta_shape = jax.ShapeDtypeStruct((n_chunks, 128), jnp.float32)
    else:
        meta_spec = pl.BlockSpec((tc * CB, 2), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
        meta_shape = jax.ShapeDtypeStruct((n_chunks * CB, 2), jnp.float32)
    f = pl.pallas_call(
        kernel,
        grid=(n_chunks // tc,),
        in_specs=[
            pl.BlockSpec((tc * CB * rb, 128), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((tc * bits * rb, 128), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            meta_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks * bits * rb, 128), jnp.int32),
            meta_shape,
        ],
        interpret=interpret,
    )
    return jax.jit(lambda x: f(x.reshape(-1, 128)))


def main():
    from torch_cgx_tpu.utils import entry

    entry.setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("variant", choices=[
        "current", "butterfly", "mul", "nometa", "metalane", "read", "dequant",
        "sra_epilogue",
    ])
    ap.add_argument(
        "--ws", type=int, default=8,
        help="peer rows for the sra_epilogue variant (the SRA world size)",
    )
    ap.add_argument("--tc", type=int, default=0, help="tile chunks override")
    ap.add_argument("--mb", type=int, default=128, help="payload MB (fp32)")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--bucket", type=int, default=512)
    # Default raised 3 -> 8 after the 2026-07-31 session: every k=3
    # production-path run on the busier shared chip was noise-unresolved
    # while the --k 8 runs resolved cleanly.
    ap.add_argument("--k", type=int, default=8, help="scan slots (>= 2)")
    args = ap.parse_args()
    if args.k < 2:
        ap.error("--k must be >= 2 (slope timing needs two scan lengths)")

    if args.tc:
        os.environ["CGX_PALLAS_TILE_CHUNKS"] = str(args.tc)

    from torch_cgx_tpu.ops import codec, codec_pallas

    n = args.mb * 1024 * 1024 // 4
    bits, b = args.bits, args.bucket
    k = args.k
    stack = jax.jit(
        lambda key: jax.random.normal(key, (k, 1, n), jnp.float32)
    )(jax.random.PRNGKey(1))
    stack.block_until_ready()
    gb = n * 4 / 1e9
    tc = args.tc or codec_pallas._pipe_tc(n // (CB * b), b)

    if args.variant == "sra_epilogue":
        # The fused dequant-accumulate-requantize kernel over ws peer rows
        # (the production SRA epilogue on TPU dispatch). Byte-checked
        # against the staged decode/select/sum/quantize oracle on a small
        # slice before timing, like every experimental kernel here.
        from torch_cgx_tpu.ops import dispatch

        ws = args.ws
        chunk = n // ws
        xs_stack = stack.reshape(k, ws, chunk)
        own = jnp.int32(ws // 2)

        def staged_small(q, xs):
            vals = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32)
            mask = (jnp.arange(ws) == own)[:, None]
            red = dispatch.ordered_rowsum(
                jnp.where(mask, xs.astype(jnp.float32), vals)
            )
            return codec_pallas.quantize_batch(red[None], bits, b)

        ns = CB * b * 2 * ws  # a couple of chunks per row
        xsmall = xs_stack[0][:, : ns // ws]
        q_small = codec_pallas.quantize_batch(xsmall, bits, b)
        ref = staged_small(q_small, xsmall)
        got = codec_pallas.sra_epilogue_batch(
            q_small, raw_row=xsmall[ws // 2], own_idx=own
        )
        assert bool(jnp.array_equal(ref.packed, got.packed)) and bool(
            jnp.array_equal(
                jnp.asarray(ref.meta, jnp.float32),
                jnp.asarray(got.meta, jnp.float32),
            )
        ), "sra_epilogue wire mismatch vs the staged oracle"
        print("byte_check: ok")
        qts = [
            codec_pallas.quantize_batch(xs_stack[i], bits, b) for i in range(k)
        ]
        q_stack = jax.tree.map(
            lambda *xs: jnp.stack(xs) if isinstance(xs[0], jax.Array) else xs[0],
            *qts,
        )
        t = scan_time(
            lambda args_: (
                lambda q2: (q2.packed, q2.meta)
            )(codec_pallas.sra_epilogue_batch(
                args_[0], raw_row=args_[1][ws // 2], own_idx=own
            )),
            (q_stack, xs_stack),
        )
    elif args.variant in ("current", "dequant"):
        if args.variant == "current":
            fn = lambda x: (  # noqa: E731
                lambda q: (q.packed, q.meta)
            )(codec_pallas.quantize_batch(x, bits, b))
            t = scan_time(fn, stack)
        else:
            qts = [codec_pallas.quantize_batch(stack[i], bits, b) for i in range(k)]
            q_stack = jax.tree.map(
                lambda *xs: jnp.stack(xs) if isinstance(xs[0], jax.Array) else xs[0],
                *qts,
            )
            t = scan_time(
                lambda q: codec_pallas.dequantize_batch(q, out_dtype=jnp.float32),
                q_stack,
            )
    else:
        # byte-identity check on a small slice (except bound variants)
        if args.variant == "metalane":
            # payload must match the oracle exactly; only the meta LAYOUT
            # differs by design ([32 units | 32 mins | pad] lane-major rows)
            ns = CB * b * 2 * tc
            xsmall = stack[0][:, :ns]
            words, meta = run_variant_kernel(args.variant, xsmall, bits, b, tc)(xsmall)
            ref = codec_pallas.quantize_batch(xsmall, bits, b)
            ref_words = jax.lax.bitcast_convert_type(
                ref.packed.reshape(-1, 128), jnp.int32
            )
            ref_meta = jnp.asarray(ref.meta, jnp.float32).reshape(-1, 2)
            w_ok = bool(jnp.array_equal(words, ref_words))
            u_ok = bool(jnp.array_equal(meta[:, :CB].reshape(-1), ref_meta[:, 0]))
            m_ok = bool(jnp.array_equal(meta[:, CB : 2 * CB].reshape(-1), ref_meta[:, 1]))
            assert w_ok and u_ok and m_ok, (
                f"wire mismatch: words={w_ok} units={u_ok} mins={m_ok}"
            )
            print("byte_check: ok (meta lane-major by design)")
        if args.variant in ("butterfly", "mul"):
            ns = CB * b * 2 * tc
            xsmall = stack[0][:, :ns]
            f_small = run_variant_kernel(args.variant, xsmall, bits, b, tc)
            words, meta = f_small(xsmall)
            ref = codec_pallas.quantize_batch(xsmall, bits, b)
            ref_words = jax.lax.bitcast_convert_type(
                ref.packed.reshape(-1, 128), jnp.int32
            )
            w_ok = bool(jnp.array_equal(words, ref_words))
            m_ok = bool(
                jnp.allclose(meta.reshape(ref.meta.shape), ref.meta.astype(jnp.float32))
            )
            if args.variant == "mul":
                # reciprocal-multiply may legitimately differ in the last ulp;
                # report mismatch rate instead of failing
                mism = float(jnp.mean((words != ref_words).astype(jnp.float32)))
                print(f"byte_check: words_equal={w_ok} mismatch_frac={mism:.2e} meta={m_ok}")
            else:
                assert w_ok and m_ok, f"wire mismatch: words={w_ok} meta={m_ok}"
                print("byte_check: ok")
        f = run_variant_kernel(args.variant, stack[0], bits, b, tc)
        t = scan_time(f, stack)

    # scan_time clamps a non-positive slope to 1e-9 s; at any real payload
    # that means dispatch noise swamped the k-spread (seen 2026-07-31 on a
    # noisy transport day) — record the measurement as unresolved (null
    # metrics, so downstream consumers like project_steprate skip it)
    # rather than logging an absurd throughput.
    unresolved = t <= 1e-8
    rec = {
        "tool": "qbench",
        "variant": args.variant,
        "tc": tc,
        "mb": args.mb,
        "bits": bits,
        "bucket": b,
        "pack": os.environ.get("CGX_PALLAS_PACK", "sum"),
        "encode": os.environ.get("CGX_CODEC_ENCODE", "div"),
    }
    prefix = (
        f"variant={args.variant} tc={tc} mb={args.mb} bits={bits} bucket={b}"
    )
    if unresolved:
        rec["t_ms"] = rec["gbps_in"] = None
        rec["unresolved"] = "slope <= noise; re-run with a larger --k"
        line = (
            f"{prefix} UNRESOLVED (k-spread slope <= dispatch noise; "
            f"re-run with --k {max(args.k * 2, 8)})"
        )
    else:
        rec["t_ms"] = round(t * 1e3, 3)
        rec["gbps_in"] = round(gb / t, 1)
        line = f"{prefix} t={t * 1e3:.3f} ms  {gb / t:.1f} GB/s(in)"
    print(line)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
