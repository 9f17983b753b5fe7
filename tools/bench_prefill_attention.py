"""Time the forms of a prompt's attention on the chip, A B A B.

    chiprun -- python tools/bench_prefill_attention.py [--shapes a,b]
        [--tiles 256x512,512x512] [--calls N]

At the shapes the two cells with long prompts hand
``ops.dispatch.prefill_attention`` (SmallThinker's 8,192 positions, 28 query
heads over 4 K/V heads of 128, global and under the window of 4,096; JoyAI's
2,048 and 3,072 positions, 32 heads of 128 + 64 keys and 128 values) and at
prompts of one query block (256 and 512 positions, which is what sets
``prefill_attention.takes_kernel``), times the loop over blocks of 512
queries and ``prefill_attention_pallas`` at its own tiling and at each of
``--tiles`` (queries a tile x keys a block), every form in turn and the
round repeated, in one process. Prints, a form and shape: ms a call (median
over the rounds), TFLOP/s of the work the mask leaves (two products over the
visible pairs), that as a share of the chip's 197, and the mean difference
from a float32 softmax over the same operands beside the loop's. The table
of PERF.md section 6 (PR 43) is this script's output. Fails off the TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torch_cgx_tpu.ops import prefill_attention as pfa

PEAK_TFLOPS = 197.0  # a v5e's bfloat16 peak
Q_BLOCK = 512

# name: (positions S, query heads H, K/V heads Hk, d_rope, window).
SHAPES = {
    "smallthinker_8k_global": (8192, 28, 4, 0, 0),
    "smallthinker_8k_window": (8192, 28, 4, 0, 4096),
    "joyai_2k": (2048, 32, 32, 64, 0),
    "joyai_3k": (3072, 32, 32, 64, 0),
    # One query block: the parent's program unless the kernel wins here too.
    "smallthinker_512_global": (512, 28, 4, 0, 0),
    "smallthinker_512_window": (512, 28, 4, 0, 4096),
    "smallthinker_256_global": (256, 28, 4, 0, 0),
    "joyai_512": (512, 32, 32, 64, 0),
    "joyai_256": (256, 32, 32, 64, 0),
    # Ling's one latent layer: 32 heads too, prompts of 193-511.
    "ling_384": (384, 32, 32, 64, 0),
}


def needed_flops(s, h, d_rope, window):
    """Two products over the pairs the mask leaves: ``2 (d + d_rope) + 2
    dv`` a pair a head."""
    i = np.arange(s)
    pairs = int(np.minimum(i + 1, window).sum() if window else (i + 1).sum())
    return pairs * h * 2 * (128 + d_rope + 128)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--tiles", default="",
                    help="tilings beside the kernel's own: 256x512,512x1024")
    ap.add_argument("--out",
                    default="chiprun_out/bench_prefill_attention.jsonl")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("this times the chip: run it through chiprun")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    tilings = [(0, 0)] + [tuple(int(x) for x in t.split("x"))
                          for t in args.tiles.split(",") if t]
    lines = []
    for name in args.shapes.split(","):
        s, h, hk, dr, window = SHAPES[name]
        scale = 1.0 / np.sqrt(128 + dr)
        keys = jax.random.split(jax.random.PRNGKey(0), 5)

        def arr(key, *shape, std=1.0):
            return (jax.random.normal(key, shape, jnp.float32) * std
                    ).astype(jnp.bfloat16)

        ops = [arr(keys[0], 1, s, h, 128, std=1.5 ** 0.5),
               arr(keys[1], 1, s, hk, 128), arr(keys[2], 1, s, hk, 128)]
        if dr:
            ops += [arr(keys[3], 1, s, h, dr, std=1.5 ** 0.5),
                    arr(keys[4], 1, s, dr)]
        loop = functools.partial(
            pfa.prefill_attention_xla, window=window,
            scale=np.float32(scale), q_block=Q_BLOCK)
        # At the default precision the float32 loop's second product would
        # round the probabilities as the bfloat16 loop does, and flatter it.
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(loop, dtype=jnp.float32))(
                *[x.astype(jnp.float32) for x in ops])
        forms = {"loop": functools.partial(loop, dtype=jnp.bfloat16)}
        for tq, tk in tilings:
            own = pfa.tiles(s, pfa.heads_a_step(h, hk, dr), tq, tk)
            forms["kernel_%dx%d%s" % (*own, "" if tq else "_own")] = (
                functools.partial(pfa.prefill_attention_pallas, window=window,
                                  scale=scale, tq=tq, tk=tk))
        fns, times, gaps = {}, {}, {}
        for form, fn in forms.items():
            try:
                f = jax.jit(fn)
                got = f(*ops).astype(jnp.float32)
            except Exception as err:  # a tiling Mosaic refuses is a finding
                print(f"{name} {form}: refused: {str(err)[:300]}", flush=True)
                continue
            gaps[form] = float(jnp.mean(jnp.abs(got - want)))
            fns[form], times[form] = f, []
        for _ in range(args.rounds):
            for form, f in fns.items():
                f(*ops).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = f(*ops)
                out.block_until_ready()
                times[form].append((time.perf_counter() - t0) / args.calls)
        flops = needed_flops(s, h, dr, window)
        for form, ts in times.items():
            ms = statistics.median(ts) * 1e3
            line = {
                "shape": name, "form": form, "s": s, "h": h, "hk": hk,
                "d_rope": dr, "window": window, "ms_call": ms,
                "needed_tflop": flops / 1e12,
                "tflops": flops / ms / 1e9,
                "peak_pct": 100 * flops / ms / 1e9 / PEAK_TFLOPS,
                "mean_gap": gaps[form], "ms_rounds": [t * 1e3 for t in ts],
            }
            lines.append(line)
            print(f"{name:24s} {form:22s} {ms:9.3f} ms/call "
                  f"{line['tflops']:6.1f} TFLOP/s {line['peak_pct']:5.1f} % "
                  f"gap {gaps[form]:.3g}", flush=True)
        del ops, want
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
