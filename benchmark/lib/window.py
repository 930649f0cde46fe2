"""The counting rule.

Every end-to-end rate is work committed inside the window divided by the
window's own length. Both ends of the window are instants at which the
device is drained, and work is counted at the finest grain at which the
program commits it, from the program's own `step` events (one a scheduler
step, handed to the benchmark's sink by `ServingMetrics`): `step_tokens`,
the tokens one decode step gave, and `prefill_tokens` / `prefill_chunks`,
the valid prompt tokens and the chunks the step dispatched. The benchmark
wraps no function of the program: a step that gains an argument, or a
model that carries state through it, counts as before.

An event is stamped when its step ends, so a chunk is counted at the end
of the step that dispatched it and not at its dispatch. At the window's
edges that changes nothing: the window opens on a drained device with no
step begun, and closes on a drained device after the last step has ended,
so every step that dispatched work in the window also ended in it.
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


def count_window(sink: StepSink, t_open: float, t_close: float) -> Window:
    """Work committed in the window: the steps that ended in (t_open,
    t_close], their decode tokens and the prompt tokens of the chunks they
    dispatched (the window closes on a drained device, so whatever was
    dispatched is done)."""
    w = Window(t_open, t_close)
    w.steps = [s for s in sink.steps if t_open < s["t"] <= t_close]
    w.output_tokens = sum(int(s["step_tokens"]) for s in w.steps)
    w.prompt_tokens = sum(int(s["prefill_tokens"]) for s in w.steps)
    w.chunk_calls = sum(int(s["prefill_chunks"]) for s in w.steps)
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
