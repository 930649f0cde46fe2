"""The counting rule.

Every end-to-end rate is work committed inside the window divided by the
window's own length. Both ends of the window are instants at which the
device is drained, and work is counted at the finest grain at which the
program commits it: one decode step's tokens (the `step` events the
program's `ServingMetrics` hands to its sink) and one prefill chunk's
valid tokens (the `valid` argument of `scheduler.chunk_prefill_fn`).
Finished requests are never counted against a deadline: the backlog's
window is a whole job of a size fixed before it opens, and ends when the
job does.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List


class StepSink:
    """A `sink` for `ServingMetrics`: keeps every event, stamped on the
    window's clock."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.steps: List[dict] = []
        self.requests: List[dict] = []

    def log(self, step, **fields) -> None:
        fields["t"] = self.clock()
        if fields.get("event") == "step":
            self.steps.append(fields)
        elif fields.get("event") == "request":
            self.requests.append(fields)


class ChunkCounter:
    """Wraps `scheduler.chunk_prefill_fn`; remembers each call's `slot`,
    `start` and `valid` (device scalars: read only after the window, never
    inside it)."""

    def __init__(self, fn: Callable, clock: Callable[[], float]):
        self.fn = fn
        self.clock = clock
        self.calls: List[tuple] = []
        self._detail: List[tuple] = []

    def __call__(self, tokens, slot, start, valid):
        self.calls.append((self.clock(), slot, start, valid))
        return self.fn(tokens, slot, start, valid)

    @property
    def detail(self) -> List[tuple]:
        """(time, slot, start, valid) of every call, as whole numbers."""
        for t, slot, start, valid in self.calls[len(self._detail):]:
            self._detail.append((t, int(slot), int(start), int(valid)))
        return self._detail


@dataclass
class Window:
    t_open: float
    t_close: float
    prompt_tokens: int = 0
    output_tokens: int = 0
    steps: List[dict] = field(default_factory=list)
    chunk_calls: int = 0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def tokens_per_s(self) -> float:
        return (self.prompt_tokens + self.output_tokens) / self.seconds


def count_window(sink: StepSink, chunks: ChunkCounter, t_open: float,
                 t_close: float) -> Window:
    """Work committed in the window: steps that ended in (t_open, t_close]
    (an event is stamped when its step ends) and chunks dispatched in
    [t_open, t_close] (a call is stamped when it is made; the window closes
    on a drained device, so whatever was dispatched is done)."""
    w = Window(t_open, t_close)
    w.steps = [s for s in sink.steps if t_open < s["t"] <= t_close]
    w.output_tokens = sum(int(s["step_tokens"]) for s in w.steps)
    inside = [valid for t, _, _, valid in chunks.detail if t_open <= t <= t_close]
    w.prompt_tokens, w.chunk_calls = sum(inside), len(inside)
    return w


def run_job_window(step: Callable[[], object], top_up: Callable[[], None],
                   unfinished: Callable[[], bool], drain_device: Callable[[], None],
                   clock: Callable[[], float]) -> tuple:
    """Drive `step` with the backlog topped up before each until the job
    is done. The window opens at the first top-up on an empty engine and
    closes once the device is drained after the step that finishes the
    job's last request: the work in it is the job, whole, whatever the
    time it took, and no deadline cuts it."""
    t_open = clock()
    while True:
        top_up()
        step()
        if not unfinished():
            break
    drain_device()
    return t_open, clock()
