"""The counting rule, pinned with a fake clock: a rate is work committed in
the window over the window's own length, at the grain of one decode step
and one prefill chunk. The backlog's window is a whole job, so no deadline
can move its work; where a window does end at a deadline (the open loop's),
moving it by less than one step moves no count by more than one step's
tokens (one chunk's for prefill)."""

import pytest

from lib.window import ChunkCounter, StepSink, count_window, run_job_window

STEP_S, LANES, CHUNK = 0.1, 8, 128


class FakeEngine:
    """Every step takes `step_s`, emits LANES tokens and runs one chunk."""

    def __init__(self, step_s=STEP_S):
        self.now, self.step_s, self.steps = 0.0, step_s, 0
        self.sink = StepSink(self.clock)
        self.chunks = ChunkCounter(lambda *a: None, self.clock)

    def clock(self):
        return self.now

    def step(self):
        self.chunks(None, 0, 0, CHUNK)
        self.now += self.step_s
        self.steps += 1
        self.sink.log(0, event="step", step_tokens=LANES, active_slots=LANES,
                      step_seconds=self.step_s, queue_depth=16)

    def until(self, seconds):
        """A window that ends at a deadline, as the open loop's does."""
        t_open = self.clock()
        while self.clock() - t_open < seconds:
            self.step()
        return count_window(self.sink, self.chunks, t_open, self.clock())

    def job(self, steps):
        """A window that ends when a job of `steps` steps is done."""
        first = self.steps
        t_open, t_close = run_job_window(self.step, lambda: None,
                                         lambda: self.steps - first < steps,
                                         lambda: None, self.clock)
        return count_window(self.sink, self.chunks, t_open, t_close)


@pytest.mark.parametrize("shift", [0.0, 0.01, 0.049, 0.051, 0.099])
def test_deadline_moved_by_less_than_a_step(shift):
    base, moved = FakeEngine().until(10.0), FakeEngine().until(10.0 + shift)
    assert abs(moved.output_tokens - base.output_tokens) <= LANES
    assert abs(moved.prompt_tokens - base.prompt_tokens) <= CHUNK
    # the window closes at a step's end, never at the deadline itself
    assert moved.seconds == pytest.approx(round(moved.seconds / STEP_S) * STEP_S)
    assert moved.tokens_per_s == pytest.approx(base.tokens_per_s, rel=1e-9)


@pytest.mark.parametrize("step_s", [0.05, 0.1, 0.13])
def test_a_jobs_work_does_not_depend_on_the_programs_speed(step_s):
    w = FakeEngine(step_s).job(200)
    assert w.output_tokens == 200 * LANES and w.prompt_tokens == 200 * CHUNK
    assert w.seconds == pytest.approx(200 * step_s)
    assert w.tokens_per_s == pytest.approx((LANES + CHUNK) / step_s)


def test_work_outside_the_window_is_not_counted():
    eng = FakeEngine()
    eng.step()  # warm-up work, before the window opens
    w = eng.job(10)
    assert w.output_tokens == 10 * LANES and w.chunk_calls == 10
