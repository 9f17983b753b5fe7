"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chips for its lifetime: it reads the cell from
``BENCHMARK.json``, finds the configuration, the traffic mix, the driver and
the per-layer metrics by name, makes weights and inputs from ``--seed`` on
the device, warms every shape the cell uses (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
It exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for, when the program under test is not beside it,
or when set-up has not reached the window by its own deadline.

``--rehearse-cpu N`` runs the same code at the tiny sizes of each file's
``rehearsal`` block on N virtual CPU devices. Its result says ``cpu``; the
driver never uses it, and the real path never falls back to it.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, near enough: set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import spec  # noqa: E402

SETUP_DEADLINE_S = 300.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Context:
    """What a driver is given, and where it leaves what it found."""

    runs = 0

    def __init__(self, bench, loaded, args, rehearse_devices, root=ROOT):
        self.root = Path(root)
        # Set-up counts from the start of the process (of the run, where a
        # test makes several runs in one process).
        self.t0 = T0 if not Context.runs else time.monotonic()
        Context.runs += 1
        self.keep_trace = bool(args.keep_trace)
        self.control = bool(args.control)
        self.bench = bench
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = rehearse_devices > 0
        self.phases = {}
        self.current_phase = "start"
        self.excluded_s = 0.0  # the reference's time before the window
        self.window_start = None
        self.checks = []
        self.device = None
        self.peaks = None
        self.memory_peak_bytes = None
        self.compiles_in_window = 0
        self._in_window = False

    log = staticmethod(log)

    @contextlib.contextmanager
    def phase(self, name: str, excluded: bool = False):
        """Time a phase of set-up; ``excluded`` phases (the reference) are
        taken out of ``setup_s``."""
        self.current_phase = name
        t = time.monotonic()
        try:
            yield
        finally:
            dt = time.monotonic() - t
            self.phases[name] = self.phases.get(name, 0.0) + dt
            if excluded and self.window_start is None:
                self.excluded_s += dt
            self.current_phase = f"after {name}"

    def open_window(self) -> None:
        self.window_start = time.monotonic()
        self.current_phase = "window"
        self._in_window = True

    def close_window(self) -> None:
        self._in_window = False

    @property
    def setup_s(self) -> float:
        return self.window_start - self.t0 - self.excluded_s

    def compare(self, name: str, value, limit, at_most: bool = True) -> bool:
        """One number beside its limit, printed in every run."""
        if value is None or limit is None or value != value:
            ok = False
        else:
            ok = value <= limit if at_most else value >= limit
        self.checks.append(
            {"name": name, "value": value, "limit": limit, "ok": bool(ok)}
        )
        log(f"check {'PASS' if ok else 'FAIL'} {name}: {value!r} "
            f"({'<=' if at_most else '>='} {limit!r})")
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    def read_memory_peak(self) -> None:
        """The peak on the fullest chip. The TPU backend's
        ``peak_bytes_in_use`` counts live arrays only; what a running
        program needs beside them (its temporaries) is reserved apart and
        shows as ``peak_bytes_reserved`` (a program with 2 GiB of
        temporaries: in use +0, reserved 2 GiB; chip run, PR 24). The two
        together are what the chip had to hold."""
        import jax

        peaks = []
        for d in jax.devices()[: len(self.device_ids)]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0))
                         + int(stats.get("peak_bytes_reserved", 0)))
        self.memory_peak_bytes = max(peaks)

    def trace_dir(self) -> str:
        return str(self.root / ".cgx_cache" / "bench_trace" / self.cell["name"])

    def start_trace(self) -> None:
        """The profiler on, into a fresh directory inside the checkout."""
        import jax

        shutil.rmtree(self.trace_dir(), ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir(), profiler_options=options)

    def read_trace(self, found: dict) -> None:
        """The trace reduced, into ``found``; a real run without a device
        plane is refused."""
        from benchmark import trace_reduce

        with self.phase("trace-read"):
            found["trace"] = trace_reduce.load_profile(self.trace_dir())
            if found["trace"]["devices"]:
                found["device_summary"] = trace_reduce.device_summary(
                    found["trace"], self.device_ids)
            elif not self.rehearse:
                raise SystemExit("benchmark: the trace holds no device plane")
            if not self.keep_trace:
                shutil.rmtree(self.trace_dir(), ignore_errors=True)


def apply_env(config: dict) -> None:
    """Every ``CGX_*`` of the caller is dropped, so ``auto`` decides; then
    the configuration's own are set."""
    for k in [k for k in os.environ if k.startswith("CGX_")]:
        del os.environ[k]
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)


def open_device(ctx: Context) -> None:
    """Compile cache, device gate, compile counter. Imports jax."""
    import jax
    from jax import monitoring

    from torch_cgx_tpu.utils import entry

    cache_dir = entry.setup_compile_cache()
    # The eager one-op programs the scheduler dispatches compile in well
    # under a second each; cached too, a warm set-up compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ctx.cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            ctx.cache_events["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            ctx.cache_events["misses"] += 1

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration") and ctx._in_window:
            ctx.compiles_in_window += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    device = entry.device_summary()
    log(f"device {device}  jax {jax.__version__}  compile cache {cache_dir}")
    want = int(ctx.cell["chips"])
    if ctx.rehearse:
        log("REHEARSAL on the cpu backend at a tiny size: not a measurement")
    elif device["platform"] != "tpu":
        raise SystemExit(
            f"benchmark: platform is {device['platform']!r}, not tpu "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    if device["count"] < want:
        raise SystemExit(
            f"benchmark: cell {ctx.cell['name']} needs {want} chip(s), jax "
            f"sees {device['count']}"
        )
    ctx.device = device
    ctx.device_ids = list(range(want))
    if ctx.trace and not ctx.rehearse:
        ctx.peaks = spec.peaks_for(device["kind"], ctx.root)  # unknown: error


def watchdog(ctx: Context) -> None:
    """The run's own deadline: a set-up that has not reached the window by
    then says where it was and ends the process, so that a later time-out
    names its cause."""
    def watch():
        while ctx.window_start is None:
            if time.monotonic() - ctx.t0 > SETUP_DEADLINE_S:
                sys.stderr.write(
                    f"benchmark: set-up did not reach the window in "
                    f"{SETUP_DEADLINE_S:.0f} s; it was in phase "
                    f"{ctx.current_phase!r}; phases so far "
                    f"{json.dumps({k: round(v, 1) for k, v in ctx.phases.items()})}\n"
                )
                sys.stderr.flush()
                os._exit(3)
            time.sleep(1.0)

    threading.Thread(target=watch, daemon=True).start()


def layer_metrics(ctx: Context, found: dict) -> dict:
    """Each per-layer metric of the cell, read by its own file; a reader
    that finds nothing to read returns nothing and is left out."""
    reader_ctx = {
        "trace": found.get("trace"), "counters": found.get("counters"),
        "loop": found.get("loop", {}), "config": ctx.config,
        "traffic": ctx.traffic, "peaks": ctx.peaks,
        "device_ids": ctx.device_ids,
    }
    out = {}
    for m in spec.per_layer_for(ctx.bench, ctx.cell["name"]):
        value = spec.load_reader(m["name"], ctx.root).read(reader_ctx)
        if value is None:
            log(f"per-layer {m['name']}: nothing to read, left out")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", type=int, default=0, metavar="DEVICES")
    ap.add_argument("--control", action="store_true",
                    help="benchmark/control.py only: the configuration's "
                         "lower precision; has to come out not correct, "
                         "and reports no metric")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .cgx_cache/ for "
                         "benchmark/trace_list.py")
    return ap.parse_args(argv)


def run(argv, root=ROOT) -> dict:
    """One run, up to the result object (``main`` prints it). ``--control``
    (``benchmark/control.py`` only) lays the configuration's ``control``
    block, its lower precision, over the configuration. ``root`` is where
    ``BENCHMARK.json`` and the benchmark's data files are read from."""
    args = parse_args(argv)
    if not (ROOT / "torch_cgx_tpu").is_dir():
        sys.stderr.write("benchmark: torch_cgx_tpu/ is not beside "
                         "benchmark/: nothing to measure\n")
        raise SystemExit(2)
    bench = spec.load_benchmark(root)
    loaded = spec.load_cell(bench, args.workload, args.rehearse_cpu > 0, root)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.rehearse_cpu}"
        )
    config = loaded["config"]
    lower = config.pop("control", {})
    if args.control:
        loaded["config"] = config = spec.merge(
            config, {k: lower[k] for k in ("env", "precision") if k in lower})
    apply_env(config)
    ctx = Context(bench, loaded, args, args.rehearse_cpu, root=root)
    watchdog(ctx)
    with ctx.phase("import+device"):
        open_device(ctx)
    driver = spec.load_module("drivers", ctx.traffic["driver"], root)
    found = driver.run(ctx)

    log("set-up phases (s): " + json.dumps(
        {k: round(v, 2) for k, v in ctx.phases.items()}
    ) + f"; compile cache {ctx.cache_events}; compiled inside the window: "
        f"{ctx.compiles_in_window}")
    if ctx.control:
        metrics = {}  # a control run is no measurement
    elif ctx.trace:
        metrics = layer_metrics(ctx, found)
    else:
        values = dict(found["end_to_end"], setup_s=ctx.setup_s)
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in spec.end_to_end_for(bench, ctx.cell["name"])
        }
    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak_bytes)
    result = {
        "correct": ctx.correct, "attempted": int(found["attempted"]),
        "failed": int(found["failed"]), "metrics": metrics, "device": device,
    }
    summary = found.get("device_summary")
    if ctx.trace and summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["checks"] = ctx.checks
    return result


def main(argv) -> int:
    result = run(argv)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    # Everything is written and no process was started: leave without the
    # TPU client's tear-down, which costs every run some 10 s of chip time.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
