#!/usr/bin/env python3
"""Every call of each compiled program in a kept profiler trace, op by op.

The device plane of a ``jax.profiler`` trace has a line ``XLA Modules`` (one
event a call of a compiled program: ``jit_decode_step``, ``jit_commit``,
``jit_prefill_pages``, ...) and a line ``XLA Ops`` (one event an executed HLO
instruction). This tool cuts the op line by the module line: for each program
its calls, their mean / median / extremes, and the op families (and the named
``cgx_*`` kernels) of a call summed. It is the yardstick the scheduler's own
device account (``cgx.serve.device.*``, docs/OBSERVABILITY.md) is held to.

    python tools/cgx_optable.py <trace_dir>              # the table, as JSON
    python tools/cgx_optable.py <trace_dir> --programs   # a line a program
    python tools/cgx_optable.py <trace_dir> --dispatch   # host call -> device

``<trace_dir>`` is what ``benchmark/run.py --trace 1 --keep-trace`` leaves
under ``.cgx_cache/bench_trace/<cell>/``. ``--programs`` prints calls and
mean / median ms a program, and again for each compiled variant of it (two
prompt lengths are two ``jit_prefill_pages``). ``--dispatch`` prints, for the
calls that found the device idle, when the program began on the device
against the start and the return of the host span that dispatched it
(``cgx.serve.prefill.forward``, ``cgx.serve.dispatch.commit``,
``cgx.serve.dispatch.step``): which of the host's two stamps the device's
start lies at. Needs ``jax`` for ``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DISPATCH_SPANS = {
    "cgx.serve.prefill.forward": "jit_prefill_pages",
    "cgx.serve.dispatch.commit": "jit_commit",
    "cgx.serve.dispatch.step": "jit_decode_step",
}
IDLE_NS = 50_000  # a call began on an idle device: nothing ran this long before


def op_family(text: str) -> tuple:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> (``fusion.12``, ``fusion``)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name, re.sub(r"[.\d]+$", "", name) or name


def load(trace_dir: str, device: int = 0):
    """(modules, ops, host spans) of one chip: each a list of (name, start
    ns, duration ns) in start order; a module's name keeps its variant,
    ``jit_prefill_pages(<id>)``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    mods, ops, host = [], [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    (mods if line.name == "XLA Modules" else ops).extend(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for line in plane.lines for ev in line.events
                        if ev.name in DISPATCH_SPANS)
    return tuple(sorted(evs, key=lambda e: e[1]) for evs in (mods, ops, host))


def program_of(module: str) -> str:
    return re.sub(r"\(.*", "", module)


def summary(ms: list) -> dict:
    return {"calls": len(ms), "ms_mean": statistics.fmean(ms),
            "ms_median": statistics.median(ms),
            "ms_min": min(ms), "ms_max": max(ms)}


def table(mods, ops) -> dict:
    """{program: calls, times, the variants' calls and times (and every
    call's, in order), and a call's op families and named kernels in ms
    (and calls) a call of the program}."""
    per = defaultdict(lambda: {"ms": [], "variants": defaultdict(list),
                               "ops": defaultdict(int),
                               "named": defaultdict(lambda: [0, 0])})
    i = 0
    for module, start, dur in mods:
        rec = per[program_of(module)]
        rec["ms"].append(dur / 1e6)
        rec["variants"][module].append(dur / 1e6)
        while i < len(ops) and ops[i][1] < start:
            i += 1
        while i < len(ops) and ops[i][1] < start + dur:
            name, family = op_family(ops[i][0])
            rec["ops"][family] += ops[i][2]
            if name.startswith("cgx_"):
                rec["named"][family][0] += ops[i][2]
                rec["named"][family][1] += 1
            i += 1
    out = {}
    for program, rec in per.items():
        n = len(rec["ms"])
        ranked = sorted(rec["ops"].items(), key=lambda kv: -kv[1])
        out[program] = dict(
            summary(rec["ms"]),
            variants={v: dict(summary(ms), calls_ms=ms)
                      for v, ms in rec["variants"].items()},
            ops_ms_per_call={k: v / 1e6 / n for k, v in ranked[:16]},
            kernels_ms_and_calls_per_call={
                k: [v[0] / 1e6 / n, v[1] / n]
                for k, v in sorted(rec["named"].items(),
                                   key=lambda kv: -kv[1][0])},
        )
    return out


def program_lines(found: dict) -> list:
    lines = []
    for program, rec in sorted(
            found.items(), key=lambda kv: -kv[1]["calls"] * kv[1]["ms_mean"]):
        lines.append(f"{program}: {rec['calls']} calls, mean "
                     f"{rec['ms_mean']:.3f} ms, median {rec['ms_median']:.3f}")
        if len(rec["variants"]) > 1:
            lines += [f"  {v}: {s['calls']} calls, mean {s['ms_mean']:.3f} "
                      f"ms, median {s['ms_median']:.3f}"
                      for v, s in rec["variants"].items()]
    return lines


def dispatch_lines(mods, host) -> list:
    """For each dispatch span whose program found the device idle: the
    program's start on the device less the span's start, and less its end
    (negative: the device began before the host's call returned)."""
    lags, j, busy_until = defaultdict(list), 0, float("-inf")
    spans = [(s, s + d, DISPATCH_SPANS[name]) for name, s, d in host]
    for module, start, dur in mods:
        idle = start - busy_until >= IDLE_NS
        busy_until = max(busy_until, start + dur)
        program = program_of(module)
        if not idle or program not in DISPATCH_SPANS.values():
            continue
        while j < len(spans) and spans[j][1] < start - 50_000_000:
            j += 1
        near = [s for s in spans[j:j + 64]
                if s[2] == program and s[0] <= start]
        if near:
            began, ended, _ = near[-1]
            lags[program].append(((start - began) / 1e6,
                                  (start - ended) / 1e6))
    return [f"{program}: {len(v)} calls on an idle device began "
            f"{statistics.median(a for a, _ in v):.3f} ms (median) after "
            f"the host span's start, {statistics.median(b for _, b in v):.3f}"
            f" ms after its end" for program, v in sorted(lags.items())]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--out", help="write the table here, not to stdout")
    ap.add_argument("--programs", action="store_true")
    ap.add_argument("--dispatch", action="store_true")
    args = ap.parse_args(argv)
    mods, ops, host = load(args.trace_dir, args.device)
    found = table(mods, ops)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    elif not (args.programs or args.dispatch):
        print(json.dumps(found, indent=1))
    if args.programs:
        print("\n".join(program_lines(found)))
    if args.dispatch:
        print("\n".join(dispatch_lines(mods, host)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
