"""Readings for the limits of ``correct``: sound runs and the control.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 25 [--control] [--rehearse-cpu N]

Runs the cell once per seed, each a process of its own like a run of the
benchmark (this parent never imports jax, so each child has the chips), and
prints every number ``correct`` compares. With ``--control`` each child gets
``--control`` too, the one way to it: the configuration's ``control`` block
is laid over the configuration, the nearest lower precision, which has to
come out not correct. For the 8-bit serving configuration that is the
program's own 4-bit KV pages. For the 4-bit training configuration it is
the plain reference put in the program's place with its gradients rounded
to 3 bits (``reference.make_train_reference(gradient_bits=3)``), because
Mosaic refuses the program's own 3-bit path on the chip; such a run builds
no step and times nothing. The benchmark's own runs never do this;
``PERF.md`` holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse-cpu", type=int, default=0)
    args = ap.parse_args(argv)
    verdicts = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        cmd = [sys.executable, str(HERE / "run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--trace", "0"]
        if args.control:
            cmd.append("--control")
        if args.rehearse_cpu:
            cmd += ["--rehearse-cpu", str(args.rehearse_cpu)]
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        lines = proc.stdout.splitlines()
        for line in lines:
            if line.startswith("[bench] check") or "reference:" in line \
                    or "first steps" in line:
                print(line, flush=True)
        if proc.returncode != 0 or not lines:
            print(f"[control] seed {seed}: the run failed (exit code "
                  f"{proc.returncode}), which counts as not correct",
                  flush=True)
            verdicts.append(False)
            continue
        result = json.loads(lines[-1])
        verdicts.append(result["correct"])
        print("[control] " + json.dumps({
            "seed": seed, "control": args.control,
            "correct": result["correct"],
            "checks": {c["name"]: c["value"] for c in result["checks"]},
        }), flush=True)
    print(f"[control] {args.workload} control={args.control}: correct on "
          f"{sum(verdicts)} of {len(verdicts)} seeds", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
