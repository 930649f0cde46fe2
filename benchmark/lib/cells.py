"""Finding a cell's files by the names in `BENCHMARK.json`.

A cell `<config>.<traffic>` is `configs/<config>.json`, `traffic/<traffic>.json`
(which names its driver under `drivers/`) and, for each per-layer metric
that lists the cell, `metrics/<name>.json` (which names its reader under
`readers/` and the reader's arguments). A later change adds cells, mixes,
configurations and metrics as files and entries and edits nothing here.

`--overlay DIR` looks in `DIR` first (a `BENCHMARK.json` and the same
directories), so that a test can add a throw-away cell beside the real ones.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class Cell:
    def __init__(self, name: str, overlay: Optional[str] = None):
        self.roots = ([overlay] if overlay else []) + [CHECKOUT]
        self.bench = self._json("BENCHMARK.json")
        entry = next((w for w in self.bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(known: {[w['name'] for w in self.bench['workloads']]})")
        self.name, self.chips = name, entry["chips"]
        cfg_entry = next(c for c in self.bench["configs"] if c["name"] == entry["config"])
        self.config = self._json(cfg_entry["file"])
        self.traffic = self._json(self._bench_path("traffic", entry["traffic"] + ".json"))
        self.end_to_end = [m for m in self.bench["end_to_end"] if self._lists(m)]
        self.per_layer = [m for m in self.bench["per_layer"] if self._lists(m)]

    def _lists(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def _bench_path(self, *parts: str) -> str:
        return os.path.join(self.bench["paths"][0], *parts)

    def find(self, relative: str) -> str:
        for root in self.roots:
            path = os.path.join(root, relative)
            if os.path.exists(path):
                return path
        raise SystemExit(f"missing file {relative!r} (looked under {self.roots})")

    def _json(self, relative: str) -> dict:
        with open(self.find(relative)) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        """`drivers/<name>.py`, `readers/<name>.py`, `references/<name>.py`."""
        path = self.find(self._bench_path(kind, name + ".py"))
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric_file(self, name: str) -> dict:
        return self._json(self._bench_path("metrics", name + ".json"))

    def sized(self, section: dict, rehearse: bool) -> dict:
        """A file's sizes, with its `rehearsal` overrides laid over them for
        a CPU rehearsal (tiny sizes; such a run never prints a metric)."""
        out = {k: v for k, v in section.items() if k != "rehearsal"}
        if rehearse:
            for k, v in section.get("rehearsal", {}).items():
                out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(out.get(k), dict) else v
        return out


def process_age_s() -> float:
    """Seconds since this process started (interpreter start-up included)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _IMPORTED_AT


_IMPORTED_AT = time.monotonic()


def read_per_layer(cell: Cell, run: Any) -> Dict[str, dict]:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell.per_layer:
        spec = cell.metric_file(metric["name"])
        value = cell.module("readers", spec["reader"]).read(run, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def device_report(devices: List, trace=None) -> dict:
    peak, parts = 0, {}
    for d in devices:
        # HBM a chip had occupied at its peak: live buffers plus what XLA
        # reserved for its programs' temporaries. The two are disjoint
        # (largest_free_block = limit - in_use - reserved on the v5e), and
        # the allocator's `peak_bytes_in_use` alone does not see the second.
        stats = d.memory_stats() or {}
        held = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        if held >= peak:
            peak, parts = held, {k: stats.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak, "memory_parts": parts}
    if trace is not None:
        report["busy_s"], report["window_s"] = trace.busy_s, trace.window_s
    return report


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
