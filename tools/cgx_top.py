#!/usr/bin/env python3
"""Live terminal dashboard over a running job's CGX_METRICS_DIR.

``top`` for the compressed data plane: every refresh re-reads the files
the observability stack already writes — the periodic metrics exports
(``metrics-rank<N>.jsonl``, last line per rank), the health engine's
atomically-replaced status snapshots (``health-status-rank<N>.json``),
health event streams (``health-rank<N>.jsonl``) and flight-recorder
dumps — and renders one row per rank:

    rank  steps/s  allreduce p50/p99 (ms)  wire ratio  edges  overlap  sched$  plan$  pred  lag  async$  link  straggler  gen  ws  last fault

* **steps/s** — delta of the ``cgx.step.count`` counter between two
  refreshes (the first frame shows ``-``); bridge-only ranks (no JAX
  step loop) fall back to the allreduce count delta.
* **wire ratio** — ``bytes_in / wire_bytes_out`` over the SRA/Ring
  counters: the live compression ratio actually achieved on the wire.
* **edges** — per-edge ratios of the unified wire plane
  (``cgx.wire.bytes_{raw,wire}.<kind>``), e.g. ``moe:7.9x kv:7.9x`` —
  which non-allreduce traffic classes are compressing and by how much.
* **overlap** — ``cgx.sched.overlap_s / cgx.sched.wall_s``: the live
  share of pipelined-collective wall time hidden under concurrent
  encode compute (the schedule compiler's whole point — ROADMAP item 2;
  ``-`` when no pipelined collective has run).
* **sched$** — schedule-cache hit rate ``hits/(hits+misses)`` from the
  ``cgx.sched.cache_*`` counters (a low rate mid-run means plans are
  being re-derived — churning configs or an invalidation storm).
* **plan$** — step-plan cache hit rate (``cgx.plan.cache_*`` — the
  whole-step planner's LRU, same reading as sched$).
* **pred** — predicted-vs-measured step time (``cgx.plan.pred_ratio``,
  or predicted-step gauge / step-time p50 live): < 1 means the
  planner's cost model underpredicts reality — drift toward the
  ``bench_gate`` prediction floor.
* **lag** — the async cross-slice plane's worst peer staleness in outer
  rounds (the ``cgx.async.lag_rounds`` gauge; ``-`` until an outer
  round has run). Climbing toward ``CGX_ASYNC_MAX_LAG`` means a slice's
  deltas stopped arriving — the eviction vote's early warning.
* **async$** — share of outer rounds where every peer's delta arrived
  on time (``cgx.async.rounds_on_time / cgx.async.rounds``): the
  decoupled exchange's health number, same reading as sched$/plan$.
* **link** — socket-transport link state (``cgx.transport.*``): ``ok``
  while every peer link is connected, ``ok+rN`` after N
  reconnect-and-replay recoveries (the fabric is flaky but the
  supervisor is winning), ``degN`` once N links degraded to the store
  fallback, ``-`` when the plane is off (``CGX_TRANSPORT`` unset).
* **straggler** — the health engine's worst per-peer skew score as
  ``score→peer`` (needs CGX_HEALTH on the ranks).
* **gen** — the recovery generation gauge (``cgx.recovery.generation``).
* **ws** — the live world size (``cgx.recovery.ws``): shrinks on an
  eviction, grows back when the elastic plane admits a joiner — the
  membership story at a glance (``?`` before the first reconfigure
  publishes it).
* **last fault** — newest ``failure`` event in the rank's flight dump.

Plain-refresh by default (ANSI clear + redraw — works over any ssh);
``--curses`` uses the curses alternate screen when a real terminal is
attached. ``--once`` prints a single frame and exits (scripts, tests).

    python tools/cgx_top.py <dir>          # default: $CGX_METRICS_DIR
    python tools/cgx_top.py --once         # one frame, no clear
    python tools/cgx_top.py -n 0.5         # refresh every 0.5 s

Stdlib only.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

_RANK_RE = re.compile(r"rank(\d+)\.jsonl?$")


def _read_last_jsonl(path: str) -> Optional[dict]:
    """Last parseable JSON object in a JSONL file (torn tail tolerated)."""
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - 65536))
            tail = f.read().decode("utf-8", "replace")
    except OSError:
        return None
    for line in reversed(tail.strip().splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def _read_json(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _ranks_in(directory: str) -> List[int]:
    ranks = set()
    for pat in ("metrics-rank*.jsonl", "health-status-rank*.json",
                "flightrec-rank*.jsonl", "spans-rank*.jsonl"):
        for p in glob.glob(os.path.join(directory, pat)):
            m = re.search(r"rank(\d+)\.", os.path.basename(p))
            if m:
                ranks.add(int(m.group(1)))
    return sorted(ranks)


def _flat(snapshot: dict) -> Dict[str, float]:
    """Flatten one typed metrics export line: counters/gauges as-is,
    histogram stats dotted (the instruments.snapshot convention)."""
    out: Dict[str, float] = {}
    out.update(snapshot.get("counters", {}))
    out.update(snapshot.get("gauges", {}))
    for name, stats in (snapshot.get("histograms") or {}).items():
        for k, v in stats.items():
            out[f"{name}.{k}"] = v
    return out


def _last_failure(path: str, cache: dict) -> Optional[dict]:
    """Newest ``failure`` flightrec event (needs a scan, not just the
    last line). Scanning a long dump every frame would make each refresh
    O(file size) per rank, so the result is cached against the file's
    (mtime, size) and only re-scanned when those change."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    sig = (st.st_mtime_ns, st.st_size)
    hit = cache.get(path)
    if hit is not None and hit[0] == sig:
        return hit[1]
    last_fault = None
    try:
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if ev.get("kind") == "failure":
                    last_fault = ev
    except OSError:
        return None
    cache[path] = (sig, last_fault)
    return last_fault


def collect(directory: str, cache: Optional[dict] = None) -> Dict[int, dict]:
    """Per-rank view of the newest on-disk state. ``cache`` (a dict the
    caller keeps across frames) avoids re-scanning unchanged flightrec
    dumps."""
    view: Dict[int, dict] = {}
    fr_cache = cache if cache is not None else {}
    for rank in _ranks_in(directory):
        metrics_line = _read_last_jsonl(
            os.path.join(directory, f"metrics-rank{rank}.jsonl")
        )
        status = _read_json(
            os.path.join(directory, f"health-status-rank{rank}.json")
        )
        view[rank] = {
            "metrics": _flat(metrics_line) if metrics_line else {},
            "ts": (metrics_line or {}).get("ts"),
            "status": status,
            "last_fault": _last_failure(
                os.path.join(directory, f"flightrec-rank{rank}.jsonl"),
                fr_cache,
            ),
        }
    return view


def _fmt_ms(v: Optional[float]) -> str:
    return f"{v * 1e3:.1f}" if isinstance(v, (int, float)) and v else "-"


def _steps_per_s(
    rank: int, m: Dict[str, float], ts: Optional[float], state: dict
) -> str:
    """Counter-delta rate between two frames (state carries the previous
    sample per rank)."""
    count = m.get("cgx.step.count")
    if count is None:
        count = m.get("cgx.collective.allreduce_s.count")
    now = ts if isinstance(ts, (int, float)) else time.time()
    prev = state.get(rank)
    state[rank] = (now, count)
    if count is None or prev is None or prev[1] is None:
        return "-"
    dt = now - prev[0]
    if dt <= 0:
        return "-"
    return f"{(count - prev[1]) / dt:.2f}"


def _wire_ratio(m: Dict[str, float]) -> str:
    bytes_in = sum(m.get(f"cgx.{k}.bytes_in", 0.0) for k in ("sra", "ring"))
    out = sum(m.get(f"cgx.{k}.wire_bytes_out", 0.0) for k in ("sra", "ring"))
    if not out:
        return "-"
    return f"{bytes_in / out:.1f}x"


_EDGE_ABBREV = {
    "moe_a2a": "moe", "ring_kv": "kv", "pp_act": "pp",
    "powersgd_factor": "psgd", "dp_grad": "dp", "xslice_delta": "xd",
    "kv_page": "kvp",
}


def _edge_wire(m: Dict[str, float]) -> str:
    """Per-edge wire ratios from the ``cgx.wire.bytes_{raw,wire}.<kind>``
    counters (the unified wire plane's accounting) — e.g.
    ``moe:7.9x kv:7.9x``; ``-`` when no edge has compressed."""
    parts = []
    for kind, short in _EDGE_ABBREV.items():
        raw = m.get(f"cgx.wire.bytes_raw.{kind}", 0.0)
        wire = m.get(f"cgx.wire.bytes_wire.{kind}", 0.0)
        if wire:
            parts.append(f"{short}:{raw / wire:.1f}x")
    return " ".join(parts) or "-"


def _overlap(m: Dict[str, float]) -> str:
    wall = m.get("cgx.sched.wall_s", 0.0)
    if not wall:
        return "-"
    return f"{min(m.get('cgx.sched.overlap_s', 0.0) / wall, 1.0):.2f}"


def _sched_cache(m: Dict[str, float]) -> str:
    hits = m.get("cgx.sched.cache_hits", 0.0)
    misses = m.get("cgx.sched.cache_misses", 0.0)
    total = hits + misses
    if not total:
        return "-"
    return f"{hits / total * 100:.0f}%"


def _plan_cache(m: Dict[str, float]) -> str:
    """Step-plan cache hit rate (``cgx.plan.cache_*`` — the whole-step
    planner's LRU; a low rate mid-run means plans are being re-derived:
    model churn or an invalidation storm)."""
    hits = m.get("cgx.plan.cache_hits", 0.0)
    misses = m.get("cgx.plan.cache_misses", 0.0)
    total = hits + misses
    if not total:
        return "-"
    return f"{hits / total * 100:.0f}%"


def _pred(m: Dict[str, float]) -> str:
    """Predicted-vs-measured step time: the ``cgx.plan.pred_ratio``
    gauge when the StepPlanner published it, else derived live from the
    predicted-step gauge over the step-time histogram p50. < 1 = the
    cost model underpredicts reality (drift toward the bench_gate
    slack floor)."""
    v = m.get("cgx.plan.pred_ratio", 0.0)
    if not v:
        pred = m.get("cgx.plan.predicted_step_s", 0.0)
        p50 = m.get("cgx.step.time_s.p50", 0.0)
        if pred and p50:
            v = pred / p50
    return f"{v:.2f}" if v else "-"


def _crit(directory: str, state: dict) -> str:
    """Last step window's critical-path dominator (``compute`` /
    ``wire`` / ``wait:r<rank>`` / ``-``) — the engine file is loaded by
    path once per process (guarded: a missing/broken engine renders
    ``-``, never kills the dashboard) and polls tail-bounded reads."""
    eng = state.get("_critpath_engine", False)
    if eng is False:
        try:
            import importlib.util

            p = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "torch_cgx_tpu", "observability", "critpath.py",
            )
            spec = importlib.util.spec_from_file_location(
                "cgx_top_critpath", p
            )
            eng = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(eng)  # type: ignore[union-attr]
        except Exception:
            eng = None
        state["_critpath_engine"] = eng
    if eng is None:
        return "-"
    try:
        return eng.live_dominator(directory) or "-"
    except Exception:
        return "-"


def _async_lag(m: Dict[str, float]) -> str:
    """Worst peer-slice staleness in outer rounds (``cgx.async.
    lag_rounds``) — ``-`` until the async plane has run a round."""
    if not m.get("cgx.async.rounds"):
        return "-"
    return f"{int(m.get('cgx.async.lag_rounds', 0.0))}"


def _async_rate(m: Dict[str, float]) -> str:
    """On-time outer-round rate (``cgx.async.rounds_on_time`` over
    ``cgx.async.rounds``) — the decoupled exchange's health number."""
    total = m.get("cgx.async.rounds", 0.0)
    if not total:
        return "-"
    return f"{m.get('cgx.async.rounds_on_time', 0.0) / total * 100:.0f}%"


def _link(m: Dict[str, float]) -> str:
    """Socket-transport link state (``cgx.transport.*``, ISSUE 20):
    ``-`` until the socket plane has moved a frame; ``ok`` while every
    peer link is connected (``+rN`` after N reconnect-and-replay
    recoveries — the supervisor is working, but the fabric is flaky);
    ``degN`` once N peer links have degraded to the store path (the
    ``degraded_edges`` gauge, falling back to ``link_down`` when only
    counters exported)."""
    if not (
        m.get("cgx.transport.frames_tx")
        or m.get("cgx.transport.frames_rx")
        or m.get("cgx.transport.posts")
    ):
        return "-"
    deg = int(m.get("cgx.transport.degraded_edges", 0.0))
    downs = int(m.get("cgx.transport.link_down", 0.0))
    if deg or downs:
        return f"deg{deg or downs}"
    rec = int(m.get("cgx.transport.reconnects", 0.0))
    return f"ok+r{rec}" if rec else "ok"


def _serve_tps(m: Dict[str, float]) -> str:
    """Serving throughput (``cgx.serve.tokens_per_s`` gauge — EWMA over
    decode steps); ``-`` until the serving plane has generated."""
    v = m.get("cgx.serve.tokens_per_s", 0.0)
    if not v:
        return "-"
    return f"{v:.1f}"


def _serve_ttft(m: Dict[str, float]) -> str:
    """Time-to-first-token p50 in ms (``cgx.serve.ttft_ms`` histogram) —
    the serving SLO controller's latency signal."""
    v = m.get("cgx.serve.ttft_ms.p50")
    if not isinstance(v, (int, float)) or not v:
        return "-"
    return f"{v:.0f}"


def _mem_mb(m: Dict[str, float]) -> str:
    """Ledger total across pools in MiB (``cgx.mem.total_mb``, with the
    peak high-water beside it) — ``-`` until the memory ledger
    (CGX_MEMLEDGER) has sampled."""
    if not m.get("cgx.mem.samples"):
        return "-"
    total = m.get("cgx.mem.total_mb", 0.0)
    peak = m.get("cgx.mem.peak_mb", 0.0)
    return f"{total:.0f}/{peak:.0f}"


def _mem_frag(m: Dict[str, float]) -> str:
    """Worst arena fragmentation (``cgx.mem.arena_frag``: 1 − largest
    free extent / total free; high = free bytes shattered) plus a ``!``
    marker when the ledger currently names leak suspects."""
    if not m.get("cgx.mem.samples"):
        return "-"
    frag = m.get("cgx.mem.arena_frag", 0.0)
    mark = "!" if m.get("cgx.mem.leak_suspects", 0.0) else ""
    return f"{frag:.2f}{mark}"


def _straggler(status: Optional[dict]) -> str:
    scores = (status or {}).get("straggler_scores") or {}
    if not scores:
        return "-"
    peer, score = max(scores.items(), key=lambda kv: kv[1])
    return f"{score:.1f}→r{peer}"


def _last_fault(fault: Optional[dict]) -> str:
    if not fault:
        return "-"
    err = fault.get("error", "?")
    op = fault.get("op")
    return f"{err}({op})" if op else str(err)


def render(directory: str, state: dict) -> str:
    """One dashboard frame as text (pure function of the on-disk state +
    the steps/s delta state — unit-testable)."""
    view = collect(directory, state.setdefault("_fr_cache", {}))
    lines = [
        f"cgx_top — {directory}   "
        f"{time.strftime('%H:%M:%S')}   ranks: {len(view)}"
    ]
    headers = ("rank", "steps/s", "ar_p50ms", "ar_p99ms", "wire",
               "edges", "overlap", "sched$", "plan$", "pred", "crit",
               "lag", "async$", "link", "tok/s", "ttft",
               "mem", "frag", "straggler", "gen", "ws", "last_fault")
    rows: List[Tuple[str, ...]] = []
    events: List[str] = []
    # Cluster-wide (the critical path crosses ranks): one poll per
    # frame, the same cell on every row.
    crit = _crit(directory, state)
    for rank, d in sorted(view.items()):
        m = d["metrics"]
        rows.append((
            str(rank),
            _steps_per_s(rank, m, d.get("ts"), state),
            _fmt_ms(m.get("cgx.collective.allreduce_s.p50")),
            _fmt_ms(m.get("cgx.collective.allreduce_s.p99")),
            _wire_ratio(m),
            _edge_wire(m),
            _overlap(m),
            _sched_cache(m),
            _plan_cache(m),
            _pred(m),
            crit,
            _async_lag(m),
            _async_rate(m),
            _link(m),
            _serve_tps(m),
            _serve_ttft(m),
            _mem_mb(m),
            _mem_frag(m),
            _straggler(d["status"]),
            str(int(m.get("cgx.recovery.generation", 0))),
            str(int(m.get("cgx.recovery.ws", 0)) or "?"),
            _last_fault(d["last_fault"]),
        ))
        for ev in ((d["status"] or {}).get("events_recent") or [])[-3:]:
            events.append(
                f"  r{rank}: {ev.get('kind')} "
                f"value={ev.get('value')} threshold={ev.get('threshold')}"
                + (f" suspect=r{ev.get('suspect')}"
                   if ev.get("suspect") is not None else "")
            )
    if not rows:
        lines.append(
            "(no metrics-rank*/health-status-rank* files yet — is the job "
            "running with CGX_METRICS_DIR set?)"
        )
        return "\n".join(lines)
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) for i, h in enumerate(headers)
    ]

    def fmt(cells):
        return "  ".join(c.rjust(w) for c, w in zip(cells, widths))

    lines.append(fmt(headers))
    lines.append(fmt(tuple("-" * w for w in widths)))
    lines.extend(fmt(r) for r in rows)
    if events:
        lines.append("")
        lines.append("recent health events:")
        lines.extend(events[-8:])
    return "\n".join(lines)


def _loop_plain(directory: str, interval: float) -> int:
    state: dict = {}
    try:
        while True:
            frame = render(directory, state)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


def _loop_curses(directory: str, interval: float) -> int:
    import curses

    state: dict = {}

    def body(scr):
        curses.use_default_colors()
        scr.nodelay(True)
        while True:
            scr.erase()
            for i, line in enumerate(render(directory, state).splitlines()):
                try:
                    scr.addnstr(i, 0, line, curses.COLS - 1)
                except curses.error:
                    break  # frame taller than the terminal
            scr.refresh()
            t_end = time.time() + interval
            while time.time() < t_end:
                if scr.getch() in (ord("q"), 27):
                    return
                time.sleep(0.05)

    curses.wrapper(body)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "directory", nargs="?", default=os.environ.get("CGX_METRICS_DIR"),
        help="metrics dir of the running job (default: $CGX_METRICS_DIR)",
    )
    ap.add_argument(
        "-n", "--interval", type=float, default=2.0,
        help="refresh interval seconds (default 2)",
    )
    ap.add_argument(
        "--once", action="store_true",
        help="print one frame and exit (no screen clear)",
    )
    ap.add_argument(
        "--curses", action="store_true",
        help="curses alternate-screen mode (q to quit)",
    )
    args = ap.parse_args(argv)
    if not args.directory:
        print("cgx_top: no directory given and CGX_METRICS_DIR unset",
              file=sys.stderr)
        return 2
    if not os.path.isdir(args.directory):
        print(f"cgx_top: {args.directory!r} is not a directory",
              file=sys.stderr)
        return 2
    if args.once:
        print(render(args.directory, {}))
        return 0
    if args.curses and sys.stdout.isatty():
        return _loop_curses(args.directory, args.interval)
    return _loop_plain(args.directory, args.interval)


if __name__ == "__main__":
    sys.exit(main())
