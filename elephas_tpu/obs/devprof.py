"""On-demand device profiling: trace capture + memory watermarks.

The ROADMAP's "serving on a real chip" item needs two hooks preinstalled
before any TPU shows up, and both are useful on CPU today:

- ``DeviceProfiler`` — a start/stop bridge over ``jax.profiler``'s
  trace capture, guarded by a non-blocking capture lock (XLA allows one
  active capture per process; a second ``start`` answers *busy* instead
  of corrupting the first). Dumps land next to the WAL when mounted on
  a PS (same placement as the kill-path flight dump — one directory
  holds everything needed to debug an incarnation), or in a temp dir
  otherwise. The opsd ``/profile`` route drives it remotely:
  ``?action=start`` / ``?action=stop`` / bare GET for status.
- ``device_memory_snapshot`` / ``record_device_memory`` — per-device
  live-buffer byte watermarks surfaced as ``device_mem_bytes{device=}``
  gauges and sampled into the history ring. Backends differ wildly
  here: TPU/GPU runtimes answer ``device.memory_stats()``, CPU usually
  answers ``None`` — so the probe tries ``memory_stats``, falls back to
  summing ``live_buffers()`` sizes, and reports nothing rather than
  guessing. Every probe is exception-guarded: a broken runtime query
  must never take down the sampler thread driving it.

- ``device_seconds_by_part`` — a capture reduced by model part: each
  device event joined, by its instruction's name, to the
  ``obs.programs.ProgramReport`` of the program call that covers it. A
  profiler mounted beside an engine or a trainer (``reports=``) adds the
  table to ``stop()``'s answer as ``by_part``;
  ``scripts/trace_report.py --xplane PATH --reports FILE`` prints it for
  a capture made elsewhere.

The profiler's starter/stopper are injectable so tests exercise the
lock protocol and dump lifecycle without importing jax at all.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import tempfile
import threading
import time
import warnings
from typing import Callable, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "DeviceProfiler",
    "device_memory_snapshot",
    "device_seconds_by_part",
    "format_by_part",
    "record_device_memory",
]

# the annotation ``start`` leaves in a capture, beside its ``time.monotonic``
PROFILE_MARK = "devprof/mark_open"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
NO_PROGRAM = "no_program"


def _jax_start_trace(out_dir: str) -> None:
    import jax

    jax.profiler.start_trace(out_dir)


def _jax_stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def _jax_mark(name: str) -> None:
    import jax

    with jax.profiler.TraceAnnotation(name):
        time.sleep(0.0005)


# -- a capture by model part --------------------------------------------------

Event = Tuple[float, float, str]  # start, end (seconds on the trace's clock), name


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a capture's directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read_device_events(xplane_path: str) -> dict:
    """``{"chips": [(modules, ops), ...], "mark_s": float | None}``: the
    ``XLA Modules`` and ``XLA Ops`` lines of every ``/device:TPU:<n>`` plane
    (one event a program call, named ``jit_<function>(<fingerprint>)``; one
    an operation, named by its HLO text) and where ``PROFILE_MARK`` lies on
    the trace's clock. Read with nothing but ``jax.profiler.ProfileData``."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    chips, mark = {}, None
    for plane in data.planes:
        device = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device is None:
                if mark is None:
                    mark = next((ev.start_ns * 1e-9 for ev in line.events
                                 if ev.name == PROFILE_MARK), None)
            elif line.name in ("XLA Modules", "XLA Ops"):
                chips.setdefault(int(device.group(1)), {})[line.name] = [
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                    for ev in line.events]
    if not chips:
        raise ValueError(f"{xplane_path}: no /device:TPU:<n> plane — nothing ran on a TPU")
    return {"chips": [(chips[n].get("XLA Modules", []), chips[n].get("XLA Ops", []))
                      for n in sorted(chips)], "mark_s": mark}


def _self_seconds(events: List[Event]) -> List[float]:
    """Each event's seconds less what the events inside it cover (a
    ``while`` less its body), in the order given."""
    own = [b - a for a, b, _ in events]
    stack: List[int] = []
    for i in sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1])):
        a, b, _ = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return own


def seconds_by_part(chips: Iterable[Tuple[List[Event], List[Event]]], reports) -> dict:
    """Device seconds by (program, part, kind) from the module and
    operation events of each chip. An operation belongs to the program
    call that covers its start and counts its self time. ``rows`` hold
    what joined to an instruction of that program's report (``bytes``:
    what the data-moving ones wrote, over all their events;
    ``in_flight_s``: of an asynchronous pair, from its ``-start``'s begin
    to its ``-done``'s end, where ``seconds`` is the ``-done``'s wait alone;
    ``mixed``: a fusion of more than one part, under its root's); ``unjoined`` the
    operations of a reported program whose names its report lacks;
    ``unreported_s`` the seconds of programs that have no report, and of
    operations outside every program call."""
    from elephas_tpu.obs.programs import instruction_name, kind_of

    by_name = {r.program: r for r in reports}
    programs: Dict[str, dict] = {}
    rows: Dict[tuple, dict] = {}
    unjoined: Dict[tuple, dict] = {}
    unreported: Dict[str, float] = {}
    for modules, ops in chips:
        calls = sorted((a, b, name.split("(", 1)[0]) for a, b, name in modules)
        starts = [c[0] for c in calls]
        for a, b, program in calls:
            if program not in by_name:
                continue
            if program not in programs:
                programs[program] = {
                    "calls": 0, "device_s": 0.0, "joined_s": 0.0, "unjoined_s": 0.0,
                    "mixed_s": 0.0,
                    "copy_bytes_per_call": by_name[program].copy_bytes()}
            programs[program]["calls"] += 1
            programs[program]["device_s"] += b - a
        began: Dict[str, float] = {}  # a `-start`'s begin, until its `-done`
        for (a, b, name), own in zip(ops, _self_seconds(ops)):
            i = bisect.bisect_right(starts, a) - 1
            program = calls[i][2] if i >= 0 and a < calls[i][1] else NO_PROGRAM
            report = by_name.get(program)
            if report is None:
                unreported[program] = unreported.get(program, 0.0) + own
                continue
            ins = report.lookup(name)
            if ins is None:
                key = (program, kind_of(instruction_name(name)))
                row = unjoined.setdefault(key, {
                    "program": program, "kind": key[1], "seconds": 0.0, "calls": 0})
                programs[program]["unjoined_s"] += own
            else:
                key = (program, ins.part, ins.kind, ins.mixed)
                row = rows.setdefault(key, {
                    "program": program, "part": ins.part, "kind": ins.kind,
                    "seconds": 0.0, "calls": 0, "bytes": 0, "in_flight_s": 0.0,
                    "mixed": ins.mixed})
                if ins.moves_data:
                    row["bytes"] += ins.out_bytes
                if ins.opcode.endswith("-start"):
                    began[ins.name] = a
                elif ins.start is not None:
                    row["in_flight_s"] += b - began.pop(ins.start, a)
                programs[program]["joined_s"] += own
                if ins.mixed:
                    programs[program]["mixed_s"] += own
            row["seconds"] += own
            row["calls"] += 1

    def ordered(table):
        return sorted(table.values(), key=lambda r: -r["seconds"])

    return {"programs": programs, "rows": ordered(rows), "unjoined": ordered(unjoined),
            "unreported_s": unreported}


def device_seconds_by_part(xplane_path: str, reports) -> dict:
    """A capture (``.xplane.pb``) reduced by model part against the
    ``ProgramReport``s of the programs that ran in it: see
    ``seconds_by_part``. ``mark_s`` is where ``PROFILE_MARK`` lies on the
    trace's clock (None where the capture holds none)."""
    read = read_device_events(xplane_path)
    return {**seconds_by_part(read["chips"], reports), "mark_s": read["mark_s"]}


def format_by_part(table: dict, top: int = 40) -> str:
    """``device_seconds_by_part``'s answer as text: a program a block, its
    parts by device time a call, then what did not join."""
    lines = []
    for program, p in table["programs"].items():
        calls, op_s = max(p["calls"], 1), p["joined_s"] + p["unjoined_s"]
        lines.append(
            f"{program}: {p['calls']} calls, {p['device_s'] / calls * 1e3:.3f} ms a call; "
            f"operations {op_s:.4f} s, joined {p['joined_s']:.4f} s "
            f"({100 * p['joined_s'] / op_s if op_s else 0:.1f} %), mixed "
            f"{p['mixed_s']:.4f} s, unjoined {p['unjoined_s']:.4f} s; copies "
            f"{p['copy_bytes_per_call'] / 1e6:.3f} MB a call")
        # GB/s: bytes over the seconds in flight for an asynchronous pair (the
        # copy ran beside other work: a lower bound), over ms/call for the others
        lines.append(f"  {'ms/call':>9} {'share':>6} {'MB/call':>9} {'flight ms':>9} "
                     f"{'GB/s':>7}  {'kind':<34} part")
        blank = f"{'':>9} {'':>9} {'':>7}"
        mine = [r for r in table["rows"] if r["program"] == program]
        for r in mine[:top]:
            over = r["in_flight_s"] or r["seconds"]
            moved = blank if not r["bytes"] else (
                f"{r['bytes'] / calls / 1e6:9.3f} "
                + (f"{r['in_flight_s'] / calls * 1e3:9.4f} " if r["in_flight_s"]
                   else f"{'':>9} ")
                + (f"{r['bytes'] / over / 1e9:7.1f}" if over else f"{'':>7}"))
            lines.append(
                f"  {r['seconds'] / calls * 1e3:9.4f} {100 * r['seconds'] / op_s:5.1f}% "
                f"{moved}  {r['kind'] + (' (mixed)' if r['mixed'] else ''):<34} {r['part']}")
        rest = sum(r["seconds"] for r in mine[top:])
        if rest:
            lines.append(f"  {rest / calls * 1e3:9.4f} {100 * rest / op_s:5.1f}% "
                         f"{blank}  ({len(mine) - top} more rows)")
        for r in (u for u in table["unjoined"] if u["program"] == program):
            lines.append(f"  {r['seconds'] / calls * 1e3:9.4f} {100 * r['seconds'] / op_s:5.1f}% "
                         f"{blank}  {r['kind']:<34} (unjoined: not in the report)")
    for program, s in sorted(table["unreported_s"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{program}: {s:.4f} s of operations, no report")
    return "\n".join(lines) + "\n"


class DeviceProfiler:
    """Start/stop trace capture with a capture lock (see module doc).

    ``start`` answers ``{"status": "started", ...}`` or
    ``{"status": "busy", ...}`` — never raises for the already-capturing
    case, because the remote caller poking ``/profile?action=start``
    twice deserves a 409-shaped answer, not a stack trace. Runtime
    failures from the underlying profiler *are* surfaced (as
    ``{"status": "error", ...}``) so a misconfigured backend is visible.

    ``start`` leaves ``PROFILE_MARK`` in the capture (a
    ``TraceAnnotation``; ``marker`` injectable, none beside an injected
    starter) and answers with the ``time.monotonic`` at which it did: the
    two tie the trace's clock to the host tracer's. ``reports`` is a
    callable giving the ``ProgramReport``s of the engine or trainer the
    profiler is mounted beside (it may compile: it is called at ``stop``,
    never before); with it ``stop`` answers ``by_part`` too, the capture
    reduced by ``device_seconds_by_part``.
    """

    def __init__(self, out_dir: Optional[str] = None,
                 starter: Callable[[str], None] = _jax_start_trace,
                 stopper: Callable[[], None] = _jax_stop_trace,
                 clock=time.monotonic,
                 marker: Optional[Callable[[str], None]] = None,
                 reports: Optional[Callable[[], list]] = None):
        self.out_dir = out_dir
        self._starter = starter
        self._stopper = stopper
        self.clock = clock
        if marker is None and starter is _jax_start_trace:
            marker = _jax_mark
        self._marker = marker
        self._reports = reports
        self._mark: Optional[dict] = None
        self._lock = threading.Lock()
        self._capturing = False
        self._capture_dir: Optional[str] = None
        self._started_at: Optional[float] = None
        self.captures = 0  # completed start→stop cycles

    def _resolve_dir(self, out_dir: Optional[str]) -> str:
        d = out_dir or self.out_dir
        if d is None:
            d = os.path.join(tempfile.gettempdir(), "elephas-profile")
        os.makedirs(d, exist_ok=True)
        return d

    def start(self, out_dir: Optional[str] = None) -> Dict[str, object]:
        with self._lock:
            if self._capturing:
                return {"status": "busy", "dir": self._capture_dir,
                        "since_s": self.clock() - self._started_at}
            d = self._resolve_dir(out_dir)
            try:
                self._starter(d)
            except Exception as exc:
                return {"status": "error", "error": repr(exc), "dir": d}
            self._capturing = True
            self._capture_dir = d
            self._started_at = self.clock()
            self._mark = None
            if self._marker is not None:
                self._mark = {"name": PROFILE_MARK, "monotonic_s": time.monotonic()}
                self._marker(PROFILE_MARK)
            return {"status": "started", "dir": d, "mark": self._mark}

    def stop(self) -> Dict[str, object]:
        with self._lock:
            if not self._capturing:
                return {"status": "idle"}
            d, t0 = self._capture_dir, self._started_at
            try:
                self._stopper()
            except Exception as exc:
                # The capture is unrecoverable either way; release the
                # lock so a retry can start fresh.
                self._capturing = False
                self._capture_dir = None
                self._started_at = None
                return {"status": "error", "error": repr(exc), "dir": d}
            self._capturing = False
            self._capture_dir = None
            self._started_at = None
            self.captures += 1
            mark = self._mark
            doc = {"status": "stopped", "dir": d,
                   "duration_s": self.clock() - t0, "mark": mark}
        # outside the lock: the reports may compile and the capture takes
        # seconds to read, and ``status`` or the next ``start`` need not wait
        if self._reports is not None:
            doc["by_part"] = self._by_part(d, mark)
        return doc

    def _by_part(self, capture_dir: str, mark: Optional[dict]) -> dict:
        """The capture by model part; a failure (no device plane on this
        backend, a program that will not lower) is the answer, not a raise."""
        try:
            table = device_seconds_by_part(find_xplane(capture_dir), self._reports())
        except Exception as exc:
            return {"error": repr(exc)}
        if mark is not None and table["mark_s"] is not None:
            # seconds to add to a trace time to get the host's monotonic clock
            table["to_monotonic_s"] = mark["monotonic_s"] - table["mark_s"]
        return table

    def status(self) -> Dict[str, object]:
        with self._lock:
            doc: Dict[str, object] = {
                "capturing": self._capturing,
                "captures": self.captures,
                "dir": self._capture_dir or self.out_dir,
            }
            if self._capturing:
                doc["since_s"] = self.clock() - self._started_at
            return doc


def device_memory_snapshot() -> Dict[str, int]:
    """Per-device live bytes: ``{"TFRT_CPU_0": 123456, ...}``.

    Tries ``device.memory_stats()["bytes_in_use"]`` (TPU/GPU runtimes),
    falls back to summing ``live_buffers()`` sizes (works on CPU in
    current jaxlib), and silently skips devices that answer neither —
    an empty dict is an honest answer on an uninstrumented backend.
    """
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return {}
    out: Dict[str, int] = {}
    for d in devices:
        name = f"{d.platform}_{d.id}"
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            out[name] = int(stats["bytes_in_use"])
            continue
        try:
            with warnings.catch_warnings():
                # jaxlib deprecates per-device live_buffers() but it is
                # the only per-DEVICE attribution CPU offers today;
                # don't let every scrape print the notice.
                warnings.simplefilter("ignore", DeprecationWarning)
                out[name] = sum(int(b.nbytes) for b in d.live_buffers())
        except Exception:
            continue
    return out


def record_device_memory(registry=None) -> Dict[str, int]:
    """Probe device memory and set ``device_mem_bytes{device=}`` gauges.

    This is the ``extra_fn`` a ``HistorySampler`` runs before each tick,
    so the watermarks are fresh in the snapshot the tick records. Returns
    the probe result (handy for the ``/profile`` status body).
    """
    if registry is None:
        from elephas_tpu import obs

        registry = obs.default_registry()
    snap = device_memory_snapshot()
    if snap:
        gauge = registry.gauge(
            "device_mem_bytes",
            help="live device buffer bytes, by device",
            labelnames=("device",))
        for name, nbytes in snap.items():
            gauge.labels(device=name).set(nbytes)
    return snap
