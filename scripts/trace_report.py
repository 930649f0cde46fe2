#!/usr/bin/env python
"""Per-phase latency report + multi-process merger over Chrome traces.

Reads the trace the obs tracer exports (``Tracer.export_chrome`` /
``scripts/lm_bench.py --trace`` / a live ``/trace`` opsd route) back
into numbers a human can act on:

- a per-phase table — count, p50/p90/p95/p99, mean, total wall — over
  every duration ("X") event name. Percentiles here are EXACT (the file
  holds every sample), unlike the registry's bucketed estimates, so
  this is also the oracle the histogram tests pin against.
- one reconstructed per-request span tree: the busiest ``req:<id>``
  track's events nested by time containment — the submit→queue→admit
  (prefill)→decode→finish lifecycle, as the scheduler recorded it.

Merge mode (``--merge DUMP...``) collects per-process dumps — each
normalized to its own t=0 in its own monotonic clock domain — into ONE
trace on a shared wall-clock axis: every dump carries a ``clockSync``
block (``origin_mono_s`` plus a simultaneous (mono, wall) sample taken
at export), so an event's wall time is
``wall_at_export - mono_at_export + origin_mono_s + ts``. Each dump
becomes its own pid row (named via the dump's ``process`` field), and
because the parameter-server wire codec propagates ``(trace_id,
span_id)``, a worker's ``ps/push`` and the PS-side ``ps/handle_push``/
``ps/apply`` spans join on ``args.trace_id`` across the process
boundary. On top of the join, ``--merge`` prints the per-unit
critical-path table — queue (comms backlog) vs wire (client round
trips) vs lock (PS apply under the buffer lock) vs train — with the
straggler unit first, plus a replay-stable digest over the set of
completed units (seeded ``FaultPlan`` chaos runs reproduce it).

Device mode (``--xplane PATH --reports FILE...``) reads a profiler
capture (an ``.xplane.pb``, or the directory it lies under) made anywhere
— ``/profile?action=start|stop`` on a live engine, ``jax.profiler`` by
hand — and prints its device time by model part: every operation joined,
by its instruction's name, to the ``ProgramReport`` of the program call
that covers it (``obs.devprof.device_seconds_by_part``). A reports file
is what ``obs.programs.save_reports`` wrote from
``engine.program_report("prefill")`` / ``("decode")`` or a trainer's
``program_report()``, or a compiled program's own text
(``compiled.as_text()``).

Usage:
    python scripts/trace_report.py TRACE.json [--tree-req ID]
        [--tenant ID]
    python scripts/trace_report.py --merge D1.json D2.json...
        [--out MERGED.json]
    python scripts/trace_report.py --xplane CAPTURE --reports R.json...
(importable: ``report(path) -> str``, ``merge_dumps``, ``unit_table``,
``unit_chain_digest``, ``device_report`` and ``main(argv)``).
"""

from __future__ import annotations

import argparse
import json
import sys
import zlib
from typing import Dict, List, Optional, Union


def load_events(path: str) -> List[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return [e for e in events if e.get("ph") == "X"]


def track_names(path: str) -> Dict[int, str]:
    """tid → thread-name from the trace's "M" metadata events."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return {
        e["tid"]: e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }


def percentile(sorted_vals: List[float], q: float) -> float:
    """Exact linear-interpolated quantile of an ASCENDING sample list."""
    if not sorted_vals:
        raise ValueError("empty sample list")
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * frac


def phase_table(events: List[dict]) -> List[dict]:
    """One row per span name: count + exact latency percentiles (s),
    sorted by total wall descending."""
    by_name: Dict[str, List[float]] = {}
    for e in events:
        if e.get("dur", 0) <= 0:
            continue  # instants carry no duration signal
        by_name.setdefault(e["name"], []).append(e["dur"] / 1e6)
    rows = []
    for name, vals in by_name.items():
        vals.sort()
        rows.append({
            "phase": name,
            "count": len(vals),
            "p50_s": percentile(vals, 0.50),
            "p90_s": percentile(vals, 0.90),
            "p95_s": percentile(vals, 0.95),
            "p99_s": percentile(vals, 0.99),
            "mean_s": sum(vals) / len(vals),
            "total_s": sum(vals),
        })
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def build_tree(events: List[dict]) -> List[dict]:
    """Nest one track's events by time containment: parent = the
    innermost longer span whose [ts, ts+dur] covers the child's."""
    nodes = [
        {"event": e, "start": e["ts"], "end": e["ts"] + e.get("dur", 0),
         "children": []}
        for e in events
    ]
    # Outermost first: earlier start, then longer duration, so a stack
    # walk assigns each node to the deepest still-open enclosing span.
    nodes.sort(key=lambda n: (n["start"], -(n["end"] - n["start"])))
    roots: List[dict] = []
    stack: List[dict] = []
    eps = 1.0  # µs slack: clock reads inside a span can tie its edges
    for node in nodes:
        while stack and node["start"] > stack[-1]["end"] + eps:
            stack.pop()
        while stack and node["end"] > stack[-1]["end"] + eps:
            stack.pop()  # overlaps but not contained: not a child
        (stack[-1]["children"] if stack else roots).append(node)
        stack.append(node)
    return roots


def pick_request_track(events: List[dict], names: Dict[int, str],
                       req_id: Optional[int] = None) -> Optional[int]:
    """The tid to draw the sample tree from: the requested ``req:<id>``
    track, else the busiest completed-request track."""
    req_tids = {t for t, n in names.items() if n.startswith("req:")}
    if req_id is not None:
        want = f"req:{req_id}"
        for tid, name in names.items():
            if name == want:
                return tid
        return None
    best, best_key = None, (-1, -1)
    for tid in req_tids:
        evs = [e for e in events if e["tid"] == tid]
        done = any(
            e["name"] == "request"
            and (e.get("args") or {}).get("status") == "completed"
            for e in evs
        )
        try:
            rid = int(names[tid].split(":", 1)[1])
        except ValueError:
            rid = -1
        # Tie-break toward the LATEST request: early ones carry XLA
        # compile inside prefill and misrepresent steady state.
        if done and (len(evs), rid) > best_key:
            best, best_key = tid, (len(evs), rid)
    return best


def tenant_tracks(events: List[dict], names: Dict[int, str],
                  tenant: str) -> set:
    """tids of ``req:<id>`` tracks belonging to ``tenant``: the
    scheduler stamps every ``request`` span (and the engine every
    ``submit`` instant) with a ``tenant`` arg, untagged requests as
    ``default`` — so membership is read off the events themselves."""
    tids = set()
    for e in events:
        if (e.get("args") or {}).get("tenant") != tenant:
            continue
        if names.get(e["tid"], "").startswith("req:"):
            tids.add(e["tid"])
    return tids


def format_tree(roots: List[dict], indent: str = "") -> List[str]:
    lines = []
    for node in roots:
        e = node["event"]
        dur_ms = e.get("dur", 0) / 1e3
        args = e.get("args") or {}
        extra = " ".join(
            f"{k}={v}" for k, v in args.items() if k != "req_id"
        )
        what = (
            f"@{e['ts'] / 1e3:.3f}ms" if e.get("dur", 0) == 0
            else f"{dur_ms:.3f}ms"
        )
        lines.append(f"{indent}{e['name']:<12} {what}"
                     + (f"  [{extra}]" if extra else ""))
        lines.extend(format_tree(node["children"], indent + "  "))
    return lines


def report(path: str, req_id: Optional[int] = None,
           tenant: Optional[str] = None) -> str:
    events = load_events(path)
    names = track_names(path)
    out = [f"# Trace report: {path}", ""]
    if tenant is not None:
        # One tenant's view: phase table and tree restricted to the
        # request tracks whose spans carry this tenant tag.
        tids = tenant_tracks(events, names, tenant)
        events = [e for e in events if e["tid"] in tids]
        out[0] += f" (tenant={tenant}, {len(tids)} request lanes)"
        if not tids:
            out.append(f"(no request tracks tagged tenant={tenant})")
            return "\n".join(out) + "\n"
    if not events:
        out.append("(no duration events)")
        return "\n".join(out)
    window_s = (
        max(e["ts"] + e.get("dur", 0) for e in events)
        - min(e["ts"] for e in events)
    ) / 1e6
    n_req = sum(1 for n in names.values() if n.startswith("req:"))
    out.append(
        f"{len(events)} span events over {window_s:.3f}s across "
        f"{len(names)} tracks ({n_req} request lanes)"
    )
    out += ["", "## Per-phase latency (seconds, exact percentiles)", ""]
    header = (f"{'phase':<22}{'count':>7}{'p50':>11}{'p90':>11}"
              f"{'p95':>11}{'p99':>11}{'mean':>11}{'total':>11}")
    out += [header, "-" * len(header)]
    for r in phase_table(events):
        out.append(
            f"{r['phase']:<22}{r['count']:>7}"
            f"{r['p50_s']:>11.6f}{r['p90_s']:>11.6f}{r['p95_s']:>11.6f}"
            f"{r['p99_s']:>11.6f}{r['mean_s']:>11.6f}{r['total_s']:>11.4f}"
        )
    tid = pick_request_track(events, names, req_id)
    if tid is not None:
        out += ["", f"## Sample request lifecycle ({names[tid]})", ""]
        tree = build_tree([e for e in events if e["tid"] == tid])
        out.extend(format_tree(tree))
    return "\n".join(out) + "\n"


# -- multi-process merge ----------------------------------------------------


def _load_doc(dump: Union[str, dict]) -> dict:
    if isinstance(dump, str):
        with open(dump) as f:
            return json.load(f)
    return dump


def _wall_base(doc: dict) -> Optional[float]:
    """Wall-clock seconds of the dump's normalized t=0, from its
    ``clockSync`` block: the (mono, wall) pair sampled at export maps
    the recording clock to wall time, and ``origin_mono_s`` is t=0 in
    the recording clock."""
    cs = doc.get("clockSync")
    if not cs:
        return None
    return (cs["wall_s_at_export"] - cs["mono_s_at_export"]
            + cs["origin_mono_s"])


def merge_dumps(dumps: List[Union[str, dict]], out: Optional[str] = None,
                names: Optional[List[str]] = None) -> dict:
    """Merge per-process Chrome-trace dumps onto one wall-clock axis.

    Each dump becomes its own pid (with a ``process_name`` metadata row
    from the dump's ``process`` field / ``names``); "X" events are
    shifted by the dump's clockSync offset so simultaneous wall-clock
    moments in different processes line up, then re-normalized so the
    earliest event across ALL dumps sits at t=0. ``droppedSpans``
    totals are summed — a merged trace built from lossy rings says so.
    """
    docs = [_load_doc(d) for d in dumps]
    bases: List[Optional[float]] = []
    for i, doc in enumerate(docs):
        has_events = any(
            e.get("ph") == "X" for e in doc.get("traceEvents", ())
        )
        base = _wall_base(doc) if has_events else None
        if has_events and base is None:
            raise ValueError(
                f"dump {i} has span events but no clockSync block; "
                "cannot align clocks (re-export with export_chrome)"
            )
        bases.append(base)
    live = [b for b in bases if b is not None]
    t0 = min(live) if live else 0.0
    merged: List[dict] = []
    dropped = 0
    proc_names = []
    for pid, (doc, base) in enumerate(zip(docs, bases), start=1):
        name = doc.get("process")
        if names is not None and names[pid - 1]:
            name = names[pid - 1]
        if not name:
            name = f"proc{pid}"
        proc_names.append(name)
        merged.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": name}})
        for e in doc.get("traceEvents", ()):
            e = dict(e)
            e["pid"] = pid
            if e.get("ph") == "X":
                e["ts"] = (base - t0) * 1e6 + e["ts"]
            merged.append(e)
        dropped += int(doc.get("droppedSpans", 0))
    result = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "mergedFrom": proc_names,
        "droppedSpans": dropped,
    }
    if out is not None:
        with open(out, "w") as f:
            json.dump(result, f)
    return result


# The per-unit critical-path decomposition: span names owned by each
# phase. "wire" is the CLIENT's view of a round trip (it contains the
# server's handle time plus the socket itself); "lock" is the PS-side
# apply under the buffer lock (+ WAL durability).
_UNIT_PHASES = (
    ("queue", ("comms/queued",)),
    ("wire", ("ps/pull", "ps/push")),
    ("lock", ("ps/apply",)),
    ("train", ("async/train",)),
)


def unit_table(doc: Union[str, dict]) -> List[dict]:
    """Per-(epoch, partition) critical-path rows from a (merged) trace:
    every span carrying a ``trace_id`` joins its unit's ``async/unit``
    root — including PS-side spans from another process's dump — and the
    unit's wall splits into queue / wire / lock / train / other.
    Sorted straggler-first (longest total)."""
    doc = _load_doc(doc)
    events = [e for e in doc.get("traceEvents", ())
              if e.get("ph") == "X" and (e.get("args") or {}).get("trace_id")]
    by_trace: Dict[str, List[dict]] = {}
    for e in events:
        by_trace.setdefault(e["args"]["trace_id"], []).append(e)
    rows = []
    for trace_id, evs in by_trace.items():
        root = next((e for e in evs if e["name"] == "async/unit"), None)
        if root is None:
            continue  # a serving request or orphan fragment, not a unit
        args = root.get("args") or {}

        def total(names):
            return sum(
                e.get("dur", 0) for e in evs if e["name"] in names
            ) / 1e6

        row = {
            "trace": trace_id[:8],
            "epoch": args.get("epoch"),
            "partition": args.get("partition"),
            "worker": args.get("worker"),
            "spans": len(evs),
        }
        accounted = 0.0
        for phase, names in _UNIT_PHASES:
            row[f"{phase}_s"] = total(names)
            accounted += row[f"{phase}_s"]
        row["total_s"] = root.get("dur", 0) / 1e6
        row["other_s"] = max(row["total_s"] - accounted, 0.0)
        rows.append(row)
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def unit_chain_digest(doc: Union[str, dict]) -> int:
    """Order-independent digest over the SET of completed units (their
    ``(epoch, partition)`` identities — never the random trace ids or
    timings), so two replays of the same seeded ``FaultPlan`` chaos run
    produce the same value even though threads interleave differently.
    A re-queued unit re-run by a survivor dedupes into one entry."""
    doc = _load_doc(doc)
    units = set()
    for e in doc.get("traceEvents", ()):
        if e.get("ph") != "X" or e.get("name") != "async/unit":
            continue
        args = e.get("args") or {}
        if args.get("epoch") is not None and args.get("partition") is not None:
            units.add((str(args["epoch"]), str(args["partition"])))
    digest = 0
    for epoch, part in units:
        digest ^= zlib.crc32(f"{epoch}/{part}".encode())
    return digest & 0xFFFFFFFF


def format_unit_table(rows: List[dict]) -> List[str]:
    header = (f"{'unit':<12}{'worker':>8}{'queue':>10}{'wire':>10}"
              f"{'lock':>10}{'train':>10}{'other':>10}{'total':>10}"
              f"{'spans':>7}")
    lines = [header, "-" * len(header)]
    for i, r in enumerate(rows):
        unit = f"e{r['epoch']}/p{r['partition']}"
        mark = " <- straggler" if i == 0 and len(rows) > 1 else ""
        lines.append(
            f"{unit:<12}{str(r['worker']):>8}"
            f"{r['queue_s']:>10.4f}{r['wire_s']:>10.4f}{r['lock_s']:>10.4f}"
            f"{r['train_s']:>10.4f}{r['other_s']:>10.4f}"
            f"{r['total_s']:>10.4f}{r['spans']:>7}{mark}"
        )
    return lines


def merge_report(dumps: List[str], out: Optional[str] = None) -> str:
    merged = merge_dumps(dumps, out=out)
    n_span = sum(1 for e in merged["traceEvents"] if e.get("ph") == "X")
    lines = [
        f"# Merged trace: {len(dumps)} dumps "
        f"({', '.join(merged['mergedFrom'])}), {n_span} span events",
    ]
    if merged["droppedSpans"]:
        lines.append(f"WARNING: {merged['droppedSpans']} spans were "
                     "dropped by bounded rings before export")
    if out:
        lines.append(f"wrote {out}")
    rows = unit_table(merged)
    if rows:
        lines += ["", "## Per-unit critical path (seconds)", ""]
        lines += format_unit_table(rows)
        lines += ["", f"unit_chain_digest: "
                      f"{unit_chain_digest(merged):#010x} "
                      f"({len(rows)} unit traces)"]
    else:
        lines.append("(no async/unit traces — nothing to decompose)")
    return "\n".join(lines) + "\n"


def device_report(xplane: str, report_files: List[str], top: int = 40) -> str:
    """A capture's device time by model part, as text."""
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from elephas_tpu.obs import devprof
    from elephas_tpu.obs.programs import load_reports

    reports = [r for path in report_files for r in load_reports(path)]
    if os.path.isdir(xplane):
        xplane = devprof.find_xplane(xplane)
    return devprof.format_by_part(
        devprof.device_seconds_by_part(xplane, reports), top)


def main(argv: Optional[List[str]] = None) -> str:
    parser = argparse.ArgumentParser(
        description="Per-phase percentiles + request tree from a trace, "
                    "or a clock-aligned multi-process merge (--merge)"
    )
    parser.add_argument("trace", nargs="*",
                        help="Chrome trace_event JSON file(s)")
    parser.add_argument("--xplane", default=None,
                        help="a profiler capture (.xplane.pb or its "
                             "directory): print device time by model part")
    parser.add_argument("--reports", nargs="+", default=[],
                        help="with --xplane: the programs' reports "
                             "(obs.programs.save_reports) or compiled texts")
    parser.add_argument("--top", type=int, default=40,
                        help="with --xplane: rows a program")
    parser.add_argument("--merge", action="store_true",
                        help="merge per-process dumps (clockSync-aligned) "
                             "and print the per-unit critical-path table")
    parser.add_argument("--tree-req", type=int, default=None,
                        help="draw the tree for this req_id")
    parser.add_argument("--tenant", default=None,
                        help="restrict the phase table and tree to one "
                             "tenant's request tracks (untagged "
                             "requests are tenant 'default')")
    parser.add_argument("--out", default=None,
                        help="write the merged trace (--merge) or the "
                             "report text to this file")
    args = parser.parse_args(argv)
    if args.xplane:
        if not args.reports:
            parser.error("--xplane needs --reports")
        text = device_report(args.xplane, args.reports, args.top)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text)
        print(text, end="")
        return text
    if not args.trace:
        parser.error("a trace file, or --xplane with --reports")
    if args.merge:
        text = merge_report(args.trace, out=args.out)
        print(text, end="")
        return text
    if len(args.trace) > 1:
        parser.error("multiple trace files require --merge")
    text = report(args.trace[0], req_id=args.tree_req,
                  tenant=args.tenant)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text, end="")
    return text


if __name__ == "__main__":
    main()
