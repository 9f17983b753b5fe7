#!/usr/bin/env python3
"""Render a human-readable summary of a CGX_METRICS_DIR.

Reads whatever the observability layer left behind —
``flightrec-rank*.jsonl`` (flight-recorder dumps), ``metrics-rank*.jsonl``
(periodic exporter), ``cluster-report.jsonl`` (leader merges) — and
prints the operator's view: top collectives by time, compression ratios,
fault/corruption tallies, and the failure timeline per rank. Stdlib
only; tolerant of partial/missing files (a chaos run's whole point is
that some rank died mid-write).

    python tools/cgx_report.py [dir]          # default: $CGX_METRICS_DIR
    python tools/cgx_report.py [dir] --json   # machine-readable summary
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple


def _read_jsonl(path: str) -> List[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn tail of a killed writer
    except OSError:
        pass
    return out


def _rank_of(path: str, prefix: str) -> Optional[int]:
    name = os.path.basename(path)
    try:
        return int(name[len(prefix):].split(".")[0])
    except (ValueError, IndexError):
        return None


def load_dir(directory: str) -> dict:
    flight: Dict[int, List[dict]] = {}
    for p in sorted(glob.glob(os.path.join(directory, "flightrec-rank*.jsonl"))):
        r = _rank_of(p, "flightrec-rank")
        if r is not None:
            flight[r] = _read_jsonl(p)
    metrics_files: Dict[int, List[dict]] = {}
    for p in sorted(glob.glob(os.path.join(directory, "metrics-rank*.jsonl"))):
        r = _rank_of(p, "metrics-rank")
        if r is not None:
            metrics_files[r] = _read_jsonl(p)
    cluster = _read_jsonl(os.path.join(directory, "cluster-report.jsonl"))
    return {"flight": flight, "metrics": metrics_files, "cluster": cluster}


def summarize(data: dict) -> dict:
    summary: dict = {"ranks": sorted(data["flight"]), "failures": [],
                     "faults": {}, "collectives": {}, "compression": {},
                     "suspected_dead": [], "counters": {}, "recovery": {},
                     "wire": {}}
    recovery_events: List[dict] = []
    membership_events: List[dict] = []
    transport_events: List[dict] = []
    coll_time: Dict[str, float] = defaultdict(float)
    coll_n: Dict[str, int] = defaultdict(int)
    ratios: Dict[str, List[float]] = defaultdict(list)
    suspects: set = set()
    # Counters are monotonic per rank but a rank may report several times
    # (multiple dumps + exporter lines): take the max WITHIN a rank (its
    # latest total), then sum ACROSS ranks for the cluster tally.
    rank_counters: Dict[int, Dict[str, float]] = defaultdict(dict)
    # Dump headers carry a FLAT snapshot where histograms flatten into
    # stat keys (cgx.x.p50/.mean/...) — summing a p50 across ranks is
    # nonsense, so those suffixes are excluded from the flat fold. The
    # exporter's "counters" dict is typed (true Counters only) and is
    # folded without the exclusion — a genuine counter named *.count
    # (e.g. span.x.count) must not be dropped there.
    hist_suffixes = (".count", ".sum", ".min", ".max", ".mean",
                     ".p50", ".p90", ".p99")

    def _fold_counter(rank: int, k: str, v: float, flat: bool = True) -> None:
        if flat and k.endswith(hist_suffixes):
            return
        cur = rank_counters[rank]
        cur[k] = max(cur.get(k, 0.0), v)

    for rank, events in data["flight"].items():
        for ev in events:
            kind = ev.get("kind")
            if kind == "dump":
                for k, v in (ev.get("metrics") or {}).items():
                    if isinstance(v, (int, float)):
                        _fold_counter(rank, k, v)
            elif kind == "collective":
                op = ev.get("op", "?")
                coll_time[op] += ev.get("seconds", 0.0)
                coll_n[op] += 1
            elif kind in ("sra", "ring"):
                b_in, b_out = ev.get("bytes_in"), ev.get("wire_bytes_out")
                if b_in and b_out:
                    ratios[kind].append(b_in / b_out)
            elif kind == "allreduce_group" and ev.get("wire_ratio"):
                ratios[f"jax_{ev.get('algo', '?')}"].append(ev["wire_ratio"])
            elif kind == "failure":
                # One incident can be recorded twice — the raise site
                # knows key/suspects, the worker loop knows the op. Merge
                # rows with the same (rank, error, message) into one.
                row = {
                    "rank": rank,
                    "error": ev.get("error"),
                    "op": ev.get("op"),
                    "key": ev.get("key"),
                    "suspects": ev.get("suspects"),
                    "message": (ev.get("message") or "")[:160],
                    # Both clocks: wall for humans, monotonic for
                    # cross-rank alignment (tools/cgx_trace.py).
                    "ts": ev.get("ts"),
                    "t_mono": ev.get("t_mono"),
                }
                merged = False
                for f in summary["failures"]:
                    if (
                        f["rank"] == row["rank"]
                        and f["error"] == row["error"]
                        and f["message"] == row["message"]
                    ):
                        for field in ("op", "key", "suspects", "ts",
                                      "t_mono"):
                            if f.get(field) in (None, [], ()):
                                f[field] = row[field]
                        merged = True
                        break
                if not merged:
                    summary["failures"].append(row)
                for s in ev.get("suspects") or []:
                    suspects.add(s)
            elif kind == "heartbeat_suspect":
                for pid in ev.get("pids") or []:
                    suspects.add(f"pid:{pid}")
            elif kind in ("recovery", "recovery_retry"):
                row = {"rank": rank, "ts": ev.get("ts")}
                row.update(
                    {
                        k: v for k, v in ev.items()
                        if k in ("phase", "generation", "evicted",
                                 "survivors", "degrade_vote", "error",
                                 "from_step", "to_step", "epoch",
                                 "abandoned_regions", "key", "op",
                                 "remaining", "ws", "step")
                        and v is not None
                    }
                )
                if kind == "recovery_retry":
                    row["phase"] = "retry"
                recovery_events.append(row)
            elif kind in ("transport_link_down", "transport_reconnect"):
                transport_events.append({
                    "rank": rank, "kind": kind[len("transport_"):],
                    "peer": ev.get("peer"), "why": ev.get("why"),
                    "flushed": ev.get("flushed"),
                    "replay": ev.get("replay"), "ts": ev.get("ts"),
                })
            elif kind == "elastic":
                row = {"rank": rank, "ts": ev.get("ts")}
                row.update(
                    {
                        k: v for k, v in ev.items()
                        if k in ("phase", "generation", "ws", "step",
                                 "join_step", "joiners", "donors",
                                 "intents", "donor_idx", "bytes",
                                 "leaves", "ms")
                        and v is not None
                    }
                )
                membership_events.append(row)
    # Newest exporter line per rank folds in counters the dumps may miss.
    step_p50 = None  # measured step time (the planner section's contrast)
    # Planner gauges are LEVELS, never tallies: they fold max-within-rank
    # into their own table (NOT rank_counters, whose totals sum across
    # ranks — 4 ranks at pred_ratio 0.97 must not report 3.88).
    plan_gauges_by_rank: Dict[int, Dict[str, float]] = defaultdict(dict)
    # Async-plane gauges are levels too (worst lag, wire rate, route H).
    async_gauges: Dict[str, float] = {}
    # Serving-plane gauges (tokens/s, SLO bit budget, occupancy) are
    # levels as well; TTFT arrives as a histogram per rank (worst rank's
    # quantiles are the SLO-relevant view).
    serve_gauges: Dict[str, float] = {}
    serve_ttft: Dict[str, float] = {}
    for rank, lines in data["metrics"].items():
        if not lines:
            continue
        for k, v in (lines[-1].get("counters") or {}).items():
            if isinstance(v, (int, float)):
                _fold_counter(rank, k, v, flat=False)
        for k, v in (lines[-1].get("gauges") or {}).items():
            if isinstance(v, (int, float)) and k.startswith("cgx.plan."):
                g = plan_gauges_by_rank[rank]
                g[k] = max(g.get(k, 0.0), v)
            elif isinstance(v, (int, float)) and k.startswith("cgx.async."):
                async_gauges[k] = max(async_gauges.get(k, 0.0), v)
            elif isinstance(v, (int, float)) and k.startswith("cgx.serve."):
                serve_gauges[k] = max(serve_gauges.get(k, 0.0), v)
        p50 = ((lines[-1].get("histograms") or {}).get("cgx.step.time_s")
               or {}).get("p50")
        if isinstance(p50, (int, float)):
            step_p50 = max(step_p50 or 0.0, p50)
        ttft = (lines[-1].get("histograms") or {}).get("cgx.serve.ttft_ms")
        if isinstance(ttft, dict):
            for stat in ("p50", "p90", "p99", "count"):
                v = ttft.get(stat)
                if isinstance(v, (int, float)):
                    serve_ttft[stat] = max(serve_ttft.get(stat, 0.0), v)
    totals: Counter = Counter()
    for per_rank in rank_counters.values():
        for k, v in per_rank.items():
            totals[k] += v
    # Planner decision/prediction gauges can also arrive via the dump
    # headers' flat snapshot; scrub them from the summed totals (levels,
    # not tallies) — the planner section below reports them max-folded.
    _PLAN_GAUGE_PREFIXES = (
        "cgx.plan.slice_", "cgx.plan.predicted_", "cgx.plan.pred_",
        "cgx.plan.bridge_chunks",
    )
    for k in [k for k in totals if k.startswith(_PLAN_GAUGE_PREFIXES)]:
        del totals[k]
    # Async-plane gauges (levels, not tallies) scrub the same way —
    # 4 ranks at lag 2 must not report lag 8 in the summed totals (the
    # exporter-line fold above already max-folded them per rank).
    _ASYNC_GAUGE_PREFIXES = (
        "cgx.async.lag", "cgx.async.wire_gbps", "cgx.async.backlog",
        "cgx.async.route_",
    )
    for k in [k for k in totals if k.startswith(_ASYNC_GAUGE_PREFIXES)]:
        del totals[k]
    # Serving-plane gauges scrub the same way (tokens/s, pool_free and
    # the SLO bit budget are levels — the serve section reports them
    # max-folded from the exporter lines).
    _SERVE_GAUGE_PREFIXES = (
        "cgx.serve.tokens_per_s", "cgx.serve.batch_occupancy",
        "cgx.serve.pool_free", "cgx.serve.slo_bits_budget",
        "cgx.serve.send_backlog",
    )
    for k in [k for k in totals if k.startswith(_SERVE_GAUGE_PREFIXES)]:
        del totals[k]
    # The socket transport's degraded-edge count is a level too (how
    # many peer links are CURRENTLY on the store fallback) — 4 ranks
    # each reporting 1 degraded edge is 1 edge per rank, not 4 summed.
    totals.pop("cgx.transport.degraded_edges", None)
    summary["counters"] = dict(totals)
    summary["faults"] = {
        k[len("cgx.faults."):]: int(v)
        for k, v in totals.items()
        if k.startswith("cgx.faults.")
    }
    summary["collectives"] = {
        op: {"count": coll_n[op], "total_s": round(t, 6)}
        for op, t in sorted(coll_time.items(), key=lambda kv: -kv[1])
    }
    summary["compression"] = {
        k: {"n": len(v), "mean_ratio": round(sum(v) / len(v), 3),
            "min_ratio": round(min(v), 3), "max_ratio": round(max(v), 3)}
        for k, v in ratios.items() if v
    }
    summary["suspected_dead"] = sorted(suspects, key=str)
    # Recovery section: the ladder's audit trail. Counters give the
    # cluster totals (generation bumps, evictions, replayed steps); the
    # event rows give the per-rank story in time order.
    evicted: set = set()
    for ev in recovery_events:
        for g in ev.get("evicted") or []:
            evicted.add(g)
    rec_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.recovery.")
    }
    if recovery_events or rec_counters:
        gens = [
            ev["generation"] for ev in recovery_events
            if isinstance(ev.get("generation"), (int, float))
        ]
        summary["recovery"] = {
            "events": sorted(
                recovery_events, key=lambda e: (e.get("ts") or 0)
            ),
            "generation": int(max(gens)) if gens else 0,
            "evicted": sorted(evicted),
            "counters": rec_counters,
        }
    # Membership section: the elastic plane's audit trail (PR 16). Join
    # lifecycle counters are cluster totals; the generation / ws are the
    # newest levels any event reported; joiners and donors accumulate
    # over every grow the run saw.
    el_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.elastic.")
    }
    if membership_events or el_counters:
        membership_events.sort(key=lambda e: (e.get("ts") or 0))
        joiners: set = set()
        donor_ranks: set = set()
        el_gens: List[int] = []
        ws = None
        last_join_ms = None
        for ev in membership_events:
            for g in ev.get("joiners") or []:
                joiners.add(int(g))
            for g in ev.get("donors") or []:
                donor_ranks.add(int(g))
            if isinstance(ev.get("generation"), (int, float)):
                el_gens.append(int(ev["generation"]))
            if isinstance(ev.get("ws"), (int, float)):
                ws = int(ev["ws"])
            if isinstance(ev.get("ms"), (int, float)):
                last_join_ms = float(ev["ms"])
        summary["membership"] = {
            "events": membership_events,
            "generation": max(el_gens) if el_gens else 0,
            "ws": ws,
            "joiners": sorted(joiners),
            "donors": sorted(donor_ranks),
            "grows": int(el_counters.get("cgx.elastic.grows", 0)),
            "joins": int(el_counters.get("cgx.elastic.joins", 0)),
            "aborts": int(el_counters.get("cgx.elastic.join_aborts", 0)),
            "last_join_ms": last_join_ms,
            "counters": el_counters,
        }
    # Unified wire plane: per-edge byte tallies (counters, summed across
    # ranks) + the closed-loop controller's current bit gauges (taken as
    # max-within-rank then max across ranks — a width is a level, not a
    # tally, so summing would be nonsense).
    edge_bytes: Dict[str, Dict[str, float]] = defaultdict(dict)
    for k, v in totals.items():
        if k.startswith("cgx.wire.bytes_raw."):
            edge_bytes[k[len("cgx.wire.bytes_raw."):]]["raw_bytes"] = v
        elif k.startswith("cgx.wire.bytes_wire."):
            edge_bytes[k[len("cgx.wire.bytes_wire."):]]["wire_bytes"] = v
    for kind, d in edge_bytes.items():
        w = d.get("wire_bytes", 0.0)
        d["ratio"] = round(d.get("raw_bytes", 0.0) / w, 3) if w else 0.0
    ctl_bits: Dict[str, float] = {}
    for per_rank in rank_counters.values():
        for k, v in per_rank.items():
            if k.startswith("cgx.wire.bits."):
                label = k[len("cgx.wire.bits."):]
                ctl_bits[label] = max(ctl_bits.get(label, 0.0), v)
    wire_counters = {
        k: v for k, v in totals.items()
        if k.startswith("cgx.wire.")
        and not k.startswith(("cgx.wire.bytes_", "cgx.wire.bits."))
    }
    if edge_bytes or ctl_bits or wire_counters:
        summary["wire"] = {
            "edges": dict(edge_bytes),
            "controller_bits": ctl_bits,
            "counters": wire_counters,
        }
    # Whole-step planner (parallel/planner.py): plan-cache efficiency,
    # the cost model's predicted step time vs the measured one, and the
    # per-slice decisions the plan staged. Counters sum across ranks;
    # the prediction/decision gauges take max-within-rank then
    # max-across (a decision is a level, not a tally).
    plan_counters = {
        k: v for k, v in totals.items()
        if k.startswith("cgx.plan.")
        and not k.startswith(
            ("cgx.plan.slice_", "cgx.plan.predicted_", "cgx.plan.pred_",
             "cgx.plan.bridge_chunks")
        )
    }
    plan_gauges: Dict[str, float] = {}
    plan_slices: Dict[str, Dict[str, int]] = defaultdict(dict)
    for per_rank in list(rank_counters.values()) + list(
        plan_gauges_by_rank.values()
    ):
        for k, v in per_rank.items():
            if k.startswith("cgx.plan.slice_chunks."):
                label = k[len("cgx.plan.slice_chunks."):]
                plan_slices[label]["chunks"] = int(
                    max(plan_slices[label].get("chunks", 0), v)
                )
            elif k.startswith("cgx.plan.slice_bits."):
                label = k[len("cgx.plan.slice_bits."):]
                plan_slices[label]["bits"] = int(
                    max(plan_slices[label].get("bits", 0), v)
                )
            elif k in ("cgx.plan.predicted_step_s", "cgx.plan.pred_ratio",
                       "cgx.plan.bridge_chunks"):
                plan_gauges[k] = max(plan_gauges.get(k, 0.0), v)
    if plan_counters or plan_gauges or plan_slices:
        hits = plan_counters.get("cgx.plan.cache_hits", 0.0)
        misses = plan_counters.get("cgx.plan.cache_misses", 0.0)
        measured = step_p50
        summary["planner"] = {
            "cache_hit_rate": (
                round(hits / (hits + misses), 3) if hits + misses else None
            ),
            "predicted_step_s": plan_gauges.get("cgx.plan.predicted_step_s"),
            "measured_step_s": measured,
            "pred_ratio": plan_gauges.get("cgx.plan.pred_ratio"),
            "bridge_chunks": plan_gauges.get("cgx.plan.bridge_chunks"),
            "slices": {k: dict(v) for k, v in sorted(plan_slices.items())},
            "counters": plan_counters,
        }
    # Codec plane: lowering ledger + producer-fuse consumption (counters
    # summed across ranks).
    codec_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.codec.")
    }
    if codec_counters:
        summary["codec"] = {"counters": codec_counters}
    # Asynchronous cross-slice plane (PR 13): outer-round progress,
    # on-time rate, worst lag and the sender's measured DCN rate.
    # Counters sum across ranks; gauges are levels (max-folded above).
    async_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.async.")
    }
    if async_counters or async_gauges:
        rounds = async_counters.get("cgx.async.rounds", 0.0)
        on_time = async_counters.get("cgx.async.rounds_on_time", 0.0)
        summary["async"] = {
            "rounds": int(rounds),
            "on_time_rate": (
                round(on_time / rounds, 3) if rounds else None
            ),
            "worst_lag_rounds": int(
                async_gauges.get("cgx.async.lag_rounds", 0.0)
            ),
            "wire_gbps": async_gauges.get("cgx.async.wire_gbps") or None,
            "route_h": (
                int(async_gauges["cgx.async.route_h"])
                if async_gauges.get("cgx.async.route_h") else None
            ),
            "counters": async_counters,
        }
    # Serving plane (PR 15): request/token throughput, TTFT quantiles
    # (worst rank), KV-page traffic and the SLO controller's budget.
    serve_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.serve.")
    }
    if serve_counters or serve_gauges or serve_ttft:
        kv_raw = totals.get("cgx.wire.bytes_raw.kv_page", 0.0)
        kv_wire = totals.get("cgx.wire.bytes_wire.kv_page", 0.0)
        summary["serve"] = {
            "requests": int(
                serve_counters.get("cgx.serve.requests_completed", 0)
            ),
            "tokens": int(
                serve_counters.get("cgx.serve.tokens_generated", 0)
            ),
            "tokens_per_s": (
                serve_gauges.get("cgx.serve.tokens_per_s") or None
            ),
            "ttft_ms": {k: round(v, 3) for k, v in serve_ttft.items()}
            or None,
            "kv_wire_ratio": (
                round(kv_raw / kv_wire, 3) if kv_wire else None
            ),
            "prefill_failovers": int(
                serve_counters.get("cgx.serve.prefill_failovers", 0)
            ),
            "slo_bits_budget": (
                int(serve_gauges["cgx.serve.slo_bits_budget"])
                if serve_gauges.get("cgx.serve.slo_bits_budget") else None
            ),
            "counters": serve_counters,
        }
    # Socket transport plane (ISSUE 20): frame/byte tallies and the
    # supervisor's recovery counters sum across ranks; degraded_edges is
    # a level (max within a rank, summed across ranks would double-count
    # nothing but max across ranks hides per-rank edges — each rank
    # supervises its OWN links, so the cluster-wide edge count is the
    # SUM of each rank's latest level). The link_down / reconnect event
    # rows give the per-edge story in time order.
    tp_counters = {
        k: v for k, v in totals.items() if k.startswith("cgx.transport.")
    }
    deg_by_rank: Dict[int, float] = {}
    for rank, per_rank in rank_counters.items():
        v = per_rank.get("cgx.transport.degraded_edges")
        if v:
            deg_by_rank[rank] = max(deg_by_rank.get(rank, 0.0), v)
    for rank, lines in data["metrics"].items():
        if not lines:
            continue
        g = (lines[-1].get("gauges") or {}).get(
            "cgx.transport.degraded_edges"
        )
        if isinstance(g, (int, float)) and g:
            deg_by_rank[rank] = max(deg_by_rank.get(rank, 0.0), g)
    deg_edges = sum(deg_by_rank.values())
    if tp_counters or transport_events or deg_edges:
        summary["transport"] = {
            "posts": int(tp_counters.get("cgx.transport.posts", 0)),
            "frames_tx": int(tp_counters.get("cgx.transport.frames_tx", 0)),
            "frames_rx": int(tp_counters.get("cgx.transport.frames_rx", 0)),
            "bytes_tx": int(tp_counters.get("cgx.transport.bytes_tx", 0)),
            "bytes_rx": int(tp_counters.get("cgx.transport.bytes_rx", 0)),
            "resends": int(tp_counters.get("cgx.transport.resends", 0)),
            "reconnects": int(
                tp_counters.get("cgx.transport.reconnects", 0)
            ),
            "crc_drops": int(tp_counters.get("cgx.transport.crc_drops", 0)),
            "dedup_drops": int(
                tp_counters.get("cgx.transport.dedup_drops", 0)
            ),
            "link_down": int(tp_counters.get("cgx.transport.link_down", 0)),
            "degraded_posts": int(
                tp_counters.get("cgx.transport.degraded_posts", 0)
            ),
            "degraded_edges": int(deg_edges),
            "events": sorted(
                transport_events, key=lambda e: (e.get("ts") or 0)
            ),
            "counters": tp_counters,
        }
    if data["cluster"]:
        summary["cluster"] = data["cluster"][-1]
    return summary


def _fmt_table(rows: List[Tuple], headers: Tuple) -> str:
    rows = [tuple(str(c) for c in r) for r in rows]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    def line(cells):
        return "  ".join(c.ljust(w) for c, w in zip(cells, widths)).rstrip()
    out = [line(headers), line(tuple("-" * w for w in widths))]
    out.extend(line(r) for r in rows)
    return "\n".join(out)


def render(summary: dict) -> str:
    parts: List[str] = []
    parts.append(f"ranks with flight data: {summary['ranks'] or 'none'}")
    if summary["failures"]:
        parts.append("\n== failures ==")
        for f in summary["failures"]:
            who = f"rank {f['rank']}"
            sus = (
                f" suspected dead rank(s): {f['suspects']}"
                if f.get("suspects")
                else ""
            )
            op = f" op={f['op']}" if f.get("op") else ""
            key = f" key={f['key']}" if f.get("key") else ""
            clocks = ""
            if f.get("ts") is not None:
                clocks = f" ts={f['ts']}"
            if f.get("t_mono") is not None:
                clocks += f" t_mono={f['t_mono']}"
            parts.append(f"  {who}: {f['error']}{op}{key}{sus}{clocks}")
            if f.get("message"):
                parts.append(f"      {f['message']}")
    if summary["suspected_dead"]:
        parts.append(
            f"\nsuspected dead: {summary['suspected_dead']}"
        )
    if summary["faults"]:
        parts.append("\n== injected faults (CGX_FAULTS) ==")
        for mode, n in sorted(summary["faults"].items()):
            parts.append(f"  {mode}: {n}")
    if summary["collectives"]:
        parts.append("\n== top collectives by time ==")
        rows = [
            (op, d["count"], f"{d['total_s'] * 1e3:.1f}")
            for op, d in summary["collectives"].items()
        ]
        parts.append(_fmt_table(rows, ("op", "count", "total_ms")))
    if summary["compression"]:
        parts.append("\n== compression ratios (bytes in / wire bytes) ==")
        rows = [
            (k, d["n"], d["mean_ratio"], d["min_ratio"], d["max_ratio"])
            for k, d in sorted(summary["compression"].items())
        ]
        parts.append(_fmt_table(rows, ("path", "n", "mean", "min", "max")))
    if summary.get("recovery"):
        rec = summary["recovery"]
        parts.append(
            f"\n== recovery (generation {rec['generation']}, "
            f"evicted {rec['evicted'] or 'none'}) =="
        )
        for k, v in sorted(rec["counters"].items()):
            parts.append(f"  {k}: {v:g}")
        rows = [
            (
                ev.get("rank"),
                ev.get("phase", "?"),
                ev.get("generation", ""),
                ev.get("evicted") or ev.get("key") or ev.get("error") or "",
                (
                    f"{ev.get('from_step')}->{ev.get('to_step')}"
                    if ev.get("from_step") is not None
                    else ev.get("step", "")
                ),
            )
            for ev in rec["events"]
        ]
        if rows:
            parts.append(
                _fmt_table(rows, ("rank", "phase", "gen", "detail", "step"))
            )
    if summary.get("membership"):
        mem = summary["membership"]
        parts.append(
            f"\n== membership (generation {mem['generation']}, "
            f"ws {mem['ws'] if mem['ws'] is not None else '?'}) =="
        )
        parts.append(
            f"  grows: {mem['grows']}  joins: {mem['joins']}  "
            f"aborts: {mem['aborts']}  "
            f"joiners: {mem['joiners'] or 'none'}  "
            f"donors: {mem['donors'] or 'none'}"
        )
        if mem.get("last_join_ms") is not None:
            parts.append(f"  last_join_ms: {mem['last_join_ms']:.1f}")
        for k, v in sorted(mem["counters"].items()):
            parts.append(f"  {k}: {v:g}")
        rows = [
            (
                ev.get("rank"),
                ev.get("phase", "?"),
                ev.get("generation", ""),
                ev.get("joiners") or ev.get("donor_idx", ""),
                (ev.get("step") if ev.get("step") is not None
                 else ev.get("join_step", "")),
            )
            for ev in mem["events"]
        ]
        if rows:
            parts.append(
                _fmt_table(rows, ("rank", "phase", "gen", "joiners", "step"))
            )
    if summary.get("wire"):
        w = summary["wire"]
        parts.append("\n== wire (per-edge bytes, unified wire plane) ==")
        rows = [
            (
                kind,
                f"{d.get('raw_bytes', 0.0) / 1e6:.2f}",
                f"{d.get('wire_bytes', 0.0) / 1e6:.2f}",
                f"{d.get('ratio', 0.0):.1f}x",
            )
            for kind, d in sorted(w.get("edges", {}).items())
        ]
        if rows:
            parts.append(
                _fmt_table(rows, ("edge", "raw_MB", "wire_MB", "ratio"))
            )
        if w.get("controller_bits"):
            parts.append("  controller bits:")
            for label, b in sorted(w["controller_bits"].items()):
                parts.append(f"    {label}: {int(b)}")
        for k, v in sorted(w.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("planner"):
        p = summary["planner"]
        parts.append("\n== planner (whole-step mega-schedule) ==")
        if p.get("cache_hit_rate") is not None:
            parts.append(f"  plan cache hit rate: {p['cache_hit_rate']:.1%}")
        if p.get("predicted_step_s"):
            line = (
                f"  predicted step: {p['predicted_step_s'] * 1e3:.2f} ms"
            )
            if p.get("measured_step_s"):
                line += (
                    f"  measured p50: {p['measured_step_s'] * 1e3:.2f} ms"
                    f"  (pred/meas "
                    f"{p['predicted_step_s'] / p['measured_step_s']:.2f})"
                )
            parts.append(line)
        if p.get("pred_ratio"):
            parts.append(f"  pred_ratio gauge: {p['pred_ratio']:.2f}")
        if p.get("bridge_chunks"):
            parts.append(
                f"  bridge depth hint: {int(p['bridge_chunks'])} chunks"
            )
        if p.get("slices"):
            rows = [
                (label, d.get("chunks", "-"), d.get("bits", "-"))
                for label, d in p["slices"].items()
            ]
            parts.append(_fmt_table(rows, ("slice", "chunks", "bits")))
        for k, v in sorted(p.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("async"):
        a = summary["async"]
        parts.append("\n== async (decoupled cross-slice plane) ==")
        parts.append(f"  outer rounds: {a['rounds']}")
        if a.get("on_time_rate") is not None:
            parts.append(f"  on-time rate: {a['on_time_rate']:.1%}")
        parts.append(f"  worst peer lag: {a['worst_lag_rounds']} round(s)")
        if a.get("wire_gbps"):
            parts.append(
                f"  sender DCN rate: {a['wire_gbps']:.4f} GB/s"
            )
        if a.get("route_h"):
            parts.append(f"  planner route H: {a['route_h']}")
        for k, v in sorted(a.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("serve"):
        s = summary["serve"]
        parts.append("\n== serve (paged quantized KV serving plane) ==")
        parts.append(
            f"  requests completed: {s['requests']}  tokens: {s['tokens']}"
        )
        if s.get("tokens_per_s"):
            parts.append(f"  tokens/s (EWMA): {s['tokens_per_s']:.2f}")
        if s.get("ttft_ms"):
            t = s["ttft_ms"]
            parts.append(
                "  ttft ms (worst rank): "
                f"p50={t.get('p50', 0):.1f} p90={t.get('p90', 0):.1f} "
                f"p99={t.get('p99', 0):.1f} n={int(t.get('count', 0))}"
            )
        if s.get("kv_wire_ratio"):
            parts.append(
                f"  kv page wire ratio: {s['kv_wire_ratio']:.2f}x"
            )
        if s.get("slo_bits_budget"):
            parts.append(
                f"  SLO controller bit budget: {s['slo_bits_budget']}"
            )
        if s.get("prefill_failovers"):
            parts.append(
                f"  prefill failovers: {s['prefill_failovers']} "
                "(streams degraded to local prefill)"
            )
        for k, v in sorted(s.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("transport"):
        t = summary["transport"]
        parts.append("\n== transport (supervised socket data plane) ==")
        parts.append(
            f"  posts: {t['posts']}  "
            f"frames tx/rx: {t['frames_tx']}/{t['frames_rx']}  "
            f"bytes tx/rx: {t['bytes_tx'] / 1e6:.2f}/"
            f"{t['bytes_rx'] / 1e6:.2f} MB"
        )
        parts.append(
            f"  reconnects: {t['reconnects']}  resends: {t['resends']}  "
            f"crc drops: {t['crc_drops']}  "
            f"dedup drops: {t['dedup_drops']}"
        )
        if t["link_down"] or t["degraded_edges"]:
            parts.append(
                f"  DEGRADED edges: {t['degraded_edges']} "
                f"(link_down events: {t['link_down']}, "
                f"posts routed via store fallback: {t['degraded_posts']})"
            )
        rows = [
            (
                ev.get("rank"),
                ev.get("kind", "?"),
                ev.get("peer", ""),
                ev.get("why") or "",
                (
                    f"flushed={ev.get('flushed')}"
                    if ev.get("flushed") is not None
                    else f"replay={ev.get('replay')}"
                    if ev.get("replay") is not None
                    else ""
                ),
            )
            for ev in t["events"]
        ]
        if rows:
            parts.append(
                _fmt_table(rows, ("rank", "event", "peer", "why", "detail"))
            )
        for k, v in sorted(t.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("codec"):
        c = summary["codec"]
        parts.append("\n== codec (kernel lowerings + producer fuse) ==")
        for k, v in sorted(c.get("counters", {}).items()):
            parts.append(f"  {k}: {v:g}")
    # cgx.recovery.* counters are NOT repeated here — the recovery
    # section above is their home.
    interesting = {
        k: v for k, v in summary["counters"].items()
        if any(t in k for t in (
            "bridge_timeout", "wire_corrupt", "wire_reread", "nonfinite",
            "heartbeat", "pressure", "shutdown",
        )) and v
    }
    if interesting:
        parts.append("\n== incident counters ==")
        for k, v in sorted(interesting.items()):
            parts.append(f"  {k}: {v:g}")
    if summary.get("cluster"):
        c = summary["cluster"]
        parts.append(
            f"\n== cluster report (last) == ws={c.get('world_size')} "
            f"reporting={c.get('ranks_reporting')} "
            f"missing={c.get('missing_ranks')}"
        )
    if summary.get("analysis"):
        a = summary["analysis"]
        parts.append("\n== static analysis (tools/analysis) ==")
        if a.get("error"):
            parts.append(f"  unavailable: {a['error']}")
        else:
            state = (
                "clean" if a.get("clean") else f"{a.get('count')} finding(s)"
            )
            parts.append(
                f"  {state} across {a.get('files_checked')} files "
                f"({a.get('elapsed_s')}s)"
            )
            for rule, n in sorted((a.get("by_rule") or {}).items()):
                parts.append(f"  {rule}: {n}")
    if summary.get("critpath"):
        cp = summary["critpath"]
        parts.append("\n== critpath (distributed critical path) ==")
        parts.append(f"  steps analyzed: {cp['steps']}")
        total = sum(cp["dominators"].values()) or 1
        for name, n in sorted(
            cp["dominators"].items(), key=lambda kv: -kv[1]
        ):
            parts.append(
                f"  dominator {name}: {n} step(s) ({100.0 * n / total:.0f}%)"
            )
        for e in cp["edges"]:
            parts.append(
                f"  edge {e['kind']} r{e['src']}->r{e['dst']}: "
                f"exposed {e['exposed_s'] * 1e3:.2f} ms ({e['key']})"
            )
        if cp["ttft_mean_ms"]:
            t = cp["ttft_mean_ms"]
            parts.append(
                f"  ttft decomposition (mean over {cp['requests']} "
                "request(s), ms): "
                + " ".join(f"{k}={v:.2f}" for k, v in t.items())
            )
    if summary.get("memory"):
        mm = summary["memory"]
        parts.append("\n== memory (per-rank byte ledger) ==")
        parts.append(
            f"  ranks with ledger data: {mm['ranks']}   "
            f"total {mm['total_mb']:.1f} MB   peak {mm['peak_mb']:.1f} MB"
        )
        rows = [
            (
                p["pool"],
                f"{p['used_mb']:.2f}",
                f"{p['frag']:.2f}" if p.get("frag") is not None else "-",
                f"{p['tte_s']:.0f}s" if p.get("tte_s") is not None else "-",
            )
            for p in mm["pools"]
        ]
        if rows:
            parts.append(_fmt_table(rows, ("pool", "used_mb", "frag", "tte")))
        if mm["leak_suspects"]:
            parts.append(
                "  LEAK suspects (alloc−release grew all window): "
                + ", ".join(mm["leak_suspects"])
            )
        for f in mm["findings"][-4:]:
            parts.append(
                f"  {f.get('kind')} owner={f.get('owner')} "
                f"value={f.get('value')} threshold={f.get('threshold')}"
            )
    if len(parts) == 1:
        parts.append("(no events recorded — was CGX_METRICS_DIR set?)")
    return "\n".join(parts)


def _critpath_summary(directory: str) -> Optional[dict]:
    """Condensed critical-path block (ISSUE 17): dominator histogram,
    top slowest cross-rank edges, and the mean TTFT decomposition —
    None (section omitted) when no span files exist or the engine file
    is missing/broken. Loaded by path: this tool stays stdlib-only."""
    import importlib.util

    if not glob.glob(os.path.join(directory, "spans-rank*.jsonl")):
        return None
    try:
        p = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "torch_cgx_tpu", "observability", "critpath.py",
        )
        spec = importlib.util.spec_from_file_location(
            "cgx_report_critpath", p
        )
        eng = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(eng)  # type: ignore[union-attr]
        report = eng.analyze(directory, use_cache=False)
    except Exception:
        return None
    ttft: Dict[str, float] = defaultdict(float)
    n_req = 0
    for r in report["requests"].values():
        if r["ttft_s"] is None:
            continue
        n_req += 1
        for k, v in r["components"].items():
            ttft[k] += v
    return {
        "steps": len(report["steps"]),
        "dominators": report["dominators"],
        "edges": report["edges"][:3],
        "ttft_mean_ms": (
            {k: round(v / n_req * 1e3, 3) for k, v in sorted(ttft.items())}
            if n_req else {}
        ),
        "requests": n_req,
    }


def _memory_summary(directory: str) -> Optional[dict]:
    """Condensed memory-plane block (ISSUE 18): each rank's LAST
    ``mem-rank<N>.jsonl`` snapshot folded into cluster totals, a pool
    table (used MB / fragmentation / forecast time-to-exhaustion), leak
    suspects, and the most recent findings — None (section omitted)
    when no ledger files exist (CGX_MEMLEDGER off)."""
    last_by_rank: Dict[int, dict] = {}
    for path in glob.glob(os.path.join(directory, "mem-rank*.jsonl")):
        rank = _rank_of(path, "mem-rank")
        recs = _read_jsonl(path)
        if rank is None or not recs:
            continue
        last_by_rank[rank] = recs[-1]
    if not last_by_rank:
        return None
    pools: Dict[str, dict] = {}
    findings: List[dict] = []
    suspects: set = set()
    for rank, snap in sorted(last_by_rank.items()):
        for row in snap.get("pools") or ():
            name = row.get("pool", "?")
            p = pools.setdefault(
                name, {"pool": name, "used_mb": 0.0, "frag": None,
                       "tte_s": None},
            )
            p["used_mb"] += (row.get("used_bytes") or 0) / (1 << 20)
            frag = row.get("frag")
            if frag is not None:
                p["frag"] = max(p["frag"] or 0.0, frag)
            tte = row.get("tte_s")
            if tte is not None and (p["tte_s"] is None or tte < p["tte_s"]):
                p["tte_s"] = tte
        for f in snap.get("findings") or ():
            findings.append({**f, "rank": rank})
            if f.get("kind") == "mem_leak" and f.get("owner"):
                suspects.add(f["owner"])
    return {
        "ranks": sorted(last_by_rank),
        "total_mb": sum(s.get("total_mb") or 0.0
                        for s in last_by_rank.values()),
        "peak_mb": max(s.get("peak_mb") or 0.0
                       for s in last_by_rank.values()),
        "pools": sorted(pools.values(), key=lambda p: -p["used_mb"]),
        "leak_suspects": sorted(suspects),
        "findings": findings,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "directory", nargs="?", default=os.environ.get("CGX_METRICS_DIR"),
        help="metrics dir (default: $CGX_METRICS_DIR)",
    )
    ap.add_argument("--json", action="store_true", help="print JSON summary")
    ap.add_argument(
        "--analysis", action="store_true",
        help="embed the whole-program analyzer's status (ISSUE 14: the "
             "same payload as `python -m tools.analysis --json`)",
    )
    args = ap.parse_args(argv)
    if not args.directory:
        print("cgx_report: no directory given and CGX_METRICS_DIR unset",
              file=sys.stderr)
        return 2
    if not os.path.isdir(args.directory):
        print(f"cgx_report: {args.directory!r} is not a directory",
              file=sys.stderr)
        return 2
    summary = summarize(load_dir(args.directory))
    summary["critpath"] = _critpath_summary(args.directory)
    summary["memory"] = _memory_summary(args.directory)
    if args.analysis:
        try:
            sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
            from tools import analysis as _analysis

            summary["analysis"] = _analysis.analyzer_status()
        except Exception as e:  # report must render even if lint can't run
            summary["analysis"] = {"error": str(e), "clean": False,
                                   "count": -1}
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        print(render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
