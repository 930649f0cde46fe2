"""From a profiler trace (`.xplane.pb`) to busy and idle time, time by
program and by operation, and the idle gaps by what the host was doing.

Read with nothing but JAX (`jax.profiler.ProfileData`). A device plane is
`/device:TPU:<n>`; its line `XLA Modules` has one event per program call,
named `jit_<function>(<fingerprint>)`, and its line `XLA Ops` one event per
operation, named by its HLO text (`%fusion.12 = ...`). The benchmark marks
the two ends of the traced window with `TraceAnnotation`s of its own
(`MARK_OPEN`, `MARK_CLOSE`), which land in the host plane and tie the
trace's clock to the host's monotonic clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

MARK_OPEN, MARK_CLOSE = "bench/mark_open", "bench/mark_close"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SHORT_GAP_S = 20e-6
SHORT_GAP = "between_kernels_lt_20us"


def op_kind(hlo_name: str) -> str:
    """`%convert_reduce_fusion.12 = f32[...] fusion(...)` -> `convert_reduce_fusion`."""
    name = hlo_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def program_name(module_event: str) -> str:
    """`jit__paged_decode_impl(1234)` -> `jit__paged_decode_impl`."""
    return module_event.split("(", 1)[0]


def union_seconds(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of intervals, and the gaps between its parts."""
    busy, gaps, end = 0.0, [], None
    for a, b in sorted(intervals):
        if end is None:
            busy, end = b - a, b
        elif a > end:
            gaps.append((end, a))
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy, gaps


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds by name, an enclosing event (a `while` around its body)
    counted without what its children cover."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    close(float("inf"))
    return out


@dataclass
class ChipTrace:
    busy_s: float = 0.0
    gaps: List[Tuple[float, float]] = field(default_factory=list)
    program_calls: Dict[str, List[float]] = field(default_factory=dict)
    op_seconds: Dict[str, float] = field(default_factory=dict)


@dataclass
class DeviceTrace:
    window_s: float
    chips: List[ChipTrace]
    # seconds to add to a trace time to get the host's monotonic clock
    to_monotonic_s: Optional[float] = None
    open_s: float = 0.0

    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def program_seconds(self, program: str) -> float:
        """Device seconds inside calls of `program`, summed over chips."""
        return sum(sum(c.program_calls.get(program, ())) for c in self.chips)

    def calls(self, program: str) -> List[float]:
        return [d for c in self.chips for d in c.program_calls.get(program, ())]

    def top_ops(self, n: int = 10) -> List[list]:
        total: Dict[str, float] = {}
        for c in self.chips:
            for k, v in c.op_seconds.items():
                total[k] = total.get(k, 0.0) + v
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def gaps_by_span(self, spans: List[Tuple[str, float, float]], n: int = 10) -> List[list]:
        """Idle seconds by the innermost host span (name, begin, end on the
        monotonic clock) that covers each gap's middle."""
        total: Dict[str, float] = {}
        shift = self.to_monotonic_s or 0.0
        spans = sorted(spans, key=lambda s: s[2] - s[1])  # innermost first
        for c in self.chips:
            for a, b in c.gaps:
                if b - a < _SHORT_GAP_S:
                    name = SHORT_GAP
                else:
                    mid = (a + b) / 2 + shift
                    name = next((s[0] for s in spans if s[1] <= mid <= s[2]),
                                "no_span")
                total[name] = total.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_trace(path: str, marks: Optional[Dict[str, float]] = None) -> DeviceTrace:
    """`marks`: monotonic seconds at which the benchmark entered its
    `MARK_OPEN` and `MARK_CLOSE` annotations. Without the annotations in
    the trace the window is the span of the device's own events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    found: Dict[str, float] = {}
    device_planes = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (MARK_OPEN, MARK_CLOSE) and ev.name not in found:
                    found[ev.name] = ev.start_ns * 1e-9
    if not device_planes:
        raise ValueError(f"{path}: no /device:TPU:<n> plane — nothing ran on a TPU")

    per_chip = []
    for plane in sorted(device_planes, key=lambda p: int(_DEVICE.match(p.name).group(1))):
        lines = {line.name: line for line in plane.lines}
        mods = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                 program_name(ev.name)) for ev in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                op_kind(ev.name)) for ev in lines["XLA Ops"].events] \
            if "XLA Ops" in lines else []
        per_chip.append((mods, ops))

    every = [e for mods, ops in per_chip for e in ops] or \
        [e for mods, ops in per_chip for e in mods]
    if not every:
        raise ValueError(f"{path}: the device planes hold no operation")
    lo = found.get(MARK_OPEN, min(e[0] for e in every))
    hi = found.get(MARK_CLOSE, max(e[1] for e in every))
    to_mono = None
    if marks and MARK_OPEN in found:
        to_mono = marks[MARK_OPEN] - found[MARK_OPEN]

    chips = []
    for mods, ops in per_chip:
        chip = ChipTrace()
        clipped = [(max(a, lo), min(b, hi), k) for a, b, k in ops if b > lo and a < hi]
        chip.busy_s, inner = union_seconds([(a, b) for a, b, _ in clipped])
        if clipped:
            first = min(a for a, _, _ in clipped)
            last = max(b for _, b, _ in clipped)
            chip.gaps = ([(lo, first)] if first > lo else []) + inner + \
                ([(last, hi)] if hi > last else [])
        else:
            chip.gaps = [(lo, hi)]
        mods_in = sorted((a, b, p) for a, b, p in mods if a >= lo and b <= hi)
        for a, b, p in mods_in:
            chip.program_calls.setdefault(p, []).append(b - a)
        # an operation belongs to the program call that covers its start
        starts = [m[0] for m in mods_in]
        named = []
        for a, b, k in clipped:
            i = bisect.bisect_right(starts, a) - 1
            prog = mods_in[i][2] if i >= 0 and a < mods_in[i][1] else "no_program"
            named.append((a, b, f"{prog}/{k}"))
        chip.op_seconds = self_times(named)
        chips.append(chip)
    return DeviceTrace(window_s=hi - lo, chips=chips, to_monotonic_s=to_mono, open_s=lo)


class TraceCapture:
    """Starts and stops the profiler around a part of the window and
    leaves the two marks in it."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.marks: Dict[str, float] = {}
        self.active = False

    def start(self) -> None:
        import jax
        import shutil

        shutil.rmtree(self.out_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's Python frames are not read
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.active = True
        self._mark(MARK_OPEN)

    def _mark(self, name: str) -> None:
        import jax

        self.marks[name] = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            time.sleep(0.0005)

    def mark_close(self) -> None:
        """The traced part ends here; `stop` may come later, where its
        seconds of stall disturb nothing."""
        if self.active and MARK_CLOSE not in self.marks:
            self._mark(MARK_CLOSE)

    def stop(self) -> None:
        import jax

        self.mark_close()
        jax.profiler.stop_trace()
        self.active = False

    def result(self) -> DeviceTrace:
        """Reduce what was captured (after the window: parsing is slow)."""
        import shutil

        try:
            return reduce_trace(find_xplane(self.out_dir), self.marks)
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
