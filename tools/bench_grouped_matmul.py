"""Time the forms of the experts' grouped product on the chip, A B A B.

    chiprun -- python tools/bench_grouped_matmul.py [--shapes a,b]
        [--tiles 16,32,128] [--own-only] [--calls N] [--block-mib 8,4]

At the shapes the two expert cells hand ``parallel.moe.dropless_moe`` (their
decode steps and prefills, gate/up and down), times ``jax.lax.ragged_dot``,
``jax.experimental.pallas.ops.tpu.megablox.gmm`` at a few tilings and
``ops.grouped_matmul.grouped_matmul_pallas`` at a few row tiles, every form
in turn and the round repeated, in one process. Prints, a form and shape:
us a call (median over the rounds), us a touched expert's matrix, and the
rate at which the touched matrices were read; then the largest difference
from ``ragged_dot``'s result. The table of PERF.md section 6 (PR 40) is
this script's output (the variant with the weight block cut in two along N,
which lost by 1-6 %, went with the choice). The ``trinity_rows*`` shapes
are a matrix of 18 MiB, which the kernel takes in column blocks
(``grouped_matmul.column_block``; ``--block-mib`` times it under other block
limits than ``MAX_BLOCK_BYTES``): PERF.md section 6, PR 45. Fails off the
TPU.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torch_cgx_tpu.ops import grouped_matmul as gm

# name: (assignments M, experts held E, K, N, experts the router chooses
# among). The assignments fall evenly on the router's experts; those on an
# expert not held lie past the groups' end.
SHAPES = {
    "ling_decode_up": (1024, 128, 2560, 768, 512),
    "ling_decode_down": (1024, 128, 768, 2560, 512),
    "joyai_decode_up": (256, 256, 2048, 768, 256),
    "joyai_decode_down": (256, 256, 768, 2048, 256),
    "ling_prefill256_up": (2048, 128, 2560, 768, 512),
    "ling_prefill512_up": (4096, 128, 2560, 768, 512),
    "ling_prefill512_down": (4096, 128, 768, 2560, 512),
    "joyai_prefill2k_up": (16384, 256, 2048, 768, 256),
    "joyai_prefill3k_down": (24576, 256, 768, 2048, 256),
    # Past what a cell sends: where does ``ragged_dot`` catch up?
    "joyai_rows256_up": (65536, 256, 2048, 768, 256),
    "joyai_rows512_up": (131072, 256, 2048, 768, 256),
    "joyai_rows512_down": (131072, 256, 768, 2048, 256),
    # The window/global cell (PR 41): 48 lanes x 6 a decode step, 4.5 rows a
    # group; a prefill of 8,192 tokens, 768 rows a group, past
    # ``MAX_GROUP_ROWS`` and so ``ragged_dot``'s today.
    "smallthinker_decode_up": (288, 64, 2560, 768, 64),
    "smallthinker_decode_down": (288, 64, 768, 2560, 64),
    "smallthinker_prefill8k_up": (49152, 64, 2560, 768, 64),
    "smallthinker_prefill8k_down": (49152, 64, 768, 2560, 64),
    # Trinity's held share (PR 45): 32 of 256 experts, gate/up and down
    # alike 3072 x 3072, 18 MiB: 1, 2 and 4 rows a held group (64, 128 and
    # 256 lanes' decode step), 16 and 64 (a 1k and a 4k prompt), 128.
    **{f"trinity_rows{r}": (256 * r, 32, 3072, 3072, 256)
       for r in (1, 2, 4, 16, 64, 128)},
}


def own(tm, block_mib=None):
    """The own kernel at row tile ``tm``; ``block_mib`` sets the block limit
    it is traced under (its column blocks follow)."""
    if block_mib is None:
        return functools.partial(gm.grouped_matmul_pallas, tm=tm)

    def traced(lhs, rhs, sizes):
        was, gm.MAX_BLOCK_BYTES = gm.MAX_BLOCK_BYTES, int(block_mib * 2**20)
        try:
            return gm.grouped_matmul_pallas.__wrapped__(lhs, rhs, sizes,
                                                        tm=tm)
        finally:
            gm.MAX_BLOCK_BYTES = was

    return traced


def forms(m, k, n, tiles, own_only, block_mibs=()):
    """name -> function of ``(lhs, rhs, sizes)``: ``ragged_dot``, the own
    kernel at each of ``tiles`` (and under each block limit of
    ``block_mibs``) and, unless ``own_only``, megablox at the same row tiles
    with whole-``K`` whole-``N`` blocks, at 128 x 512 x N and at its default
    tiling."""
    # The package's ``gmm`` is the differentiable wrapper, which hides the
    # module of the same name: the kernel's own function takes a dtype.
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def gmm(tiling):
        return lambda a, b, s: megablox.gmm(
            a, b, s, preferred_element_type=a.dtype, tiling=tiling)

    out = {"ragged_dot": gm.grouped_matmul_xla}
    for tm in tiles:
        out[f"own_tm{tm}"] = own(tm)
        for mib in block_mibs:
            out[f"own_tm{tm}_{mib:g}MiB"] = own(tm, mib)
        if not own_only and m % tm == 0:
            out[f"gmm_{tm}xKxN"] = gmm((tm, k, n))
    if not own_only and m % 128 == 0:
        out["gmm_128x512xN"] = gmm((128, 512, n))
        out["gmm_default"] = gmm((128, 128, 128))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(list(SHAPES)[:9]))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--tiles", default="16,32,128",
                    help="row tiles of the own kernel and of megablox")
    ap.add_argument("--own-only", action="store_true",
                    help="ragged_dot and the own kernel, nothing of megablox")
    ap.add_argument("--block-mib", default="",
                    help="other block limits to time the own kernel under")
    ap.add_argument("--out", default="chiprun_out/bench_grouped_matmul.jsonl")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("this times the chip: run it through chiprun")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []
    for name in args.shapes.split(","):
        m, e, k, n, routed = SHAPES[name]
        rng = np.random.default_rng(0)
        flat = rng.integers(0, routed, size=m)
        flat = np.where(flat < e, flat, e)
        sizes = jnp.asarray(np.bincount(flat, minlength=e + 1)[:e], jnp.int32)
        rows, touched = int(sizes.sum()), int((sizes > 0).sum())
        key = jax.random.PRNGKey(0)
        lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
        rhs = (jax.random.normal(key, (e, k, n), jnp.bfloat16)
               * jnp.bfloat16(k ** -0.5))
        want = np.asarray(gm.grouped_matmul_xla(lhs, rhs, sizes), np.float32)
        fns, times, gaps = {}, {}, {}
        tiles = [int(t) for t in args.tiles.split(",")]
        mibs = [float(b) for b in args.block_mib.split(",") if b]
        for form, fn in forms(m, k, n, tiles, args.own_only, mibs).items():
            try:
                f = jax.jit(fn)
                got = np.asarray(f(lhs, rhs, sizes), np.float32)
            except Exception as err:  # a tiling Mosaic refuses is a finding
                print(f"{name} {form}: refused: {str(err)[:300]}", flush=True)
                continue
            gaps[form] = float(np.abs(got - want)[:rows].max())
            fns[form], times[form] = f, []
        for _ in range(args.rounds):
            for form, f in fns.items():
                f(lhs, rhs, sizes).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = f(lhs, rhs, sizes)
                out.block_until_ready()
                times[form].append((time.perf_counter() - t0) / args.calls)
        for form, ts in times.items():
            us = statistics.median(ts) * 1e6
            line = {
                "shape": name, "form": form, "m": m, "e": e, "k": k, "n": n,
                "rows": rows, "touched": touched, "us_call": us,
                "us_matrix": us / touched,
                "gb_s": touched * k * n * 2 / us / 1e3,
                "max_gap": gaps[form],
                "us_rounds": [t * 1e6 for t in ts],
            }
            lines.append(line)
            print(f"{name:22s} {form:14s} rows {rows:5d} touched {touched:3d}"
                  f" {us:9.1f} us/call {us / touched:7.2f} us/matrix "
                  f"{line['gb_s']:6.1f} GB/s gap {gaps[form]:.3g}",
                  flush=True)
        del lhs, rhs
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
