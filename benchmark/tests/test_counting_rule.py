"""The counting rule, pinned with a fake clock: a rate is work committed in
the window over the window's own length, at the grain of one decode step
and one prefill chunk. The backlog's window is a whole job, so no deadline
can move its work; where a window does end at a deadline (the open loop's),
moving it by less than one step moves no count by more than one step's
tokens (one chunk's for prefill). The counts come from the program's own
`step` events; on recorded events they are what the parent's wrappers of
`scheduler.chunk_prefill_fn` and `decode_fn` counted in the same run."""

import pytest

from lib import serve
from lib.window import StepSink, count_window, run_job_window
from test_counts import recorded

STEP_S, LANES, CHUNK = 0.1, 8, 128


class FakeEngine:
    """Every step takes `step_s`, emits LANES tokens and runs one chunk."""

    def __init__(self, step_s=STEP_S):
        self.now, self.step_s, self.steps = 0.0, step_s, 0
        self.sink = StepSink(self.clock)

    def clock(self):
        return self.now

    def step(self):
        self.now += self.step_s
        self.steps += 1
        self.sink.log(0, event="step", step_tokens=LANES, active_slots=LANES,
                      prefill_tokens=CHUNK, prefill_chunks=1,
                      step_seconds=self.step_s, queue_depth=16)

    def until(self, seconds):
        """A window that ends at a deadline, as the open loop's does."""
        t_open = self.clock()
        while self.clock() - t_open < seconds:
            self.step()
        return count_window(self.sink, t_open, self.clock())

    def job(self, steps):
        """A window that ends when a job of `steps` steps is done."""
        first = self.steps
        t_open, t_close = run_job_window(self.step, lambda: None,
                                         lambda: self.steps - first < steps,
                                         lambda: None, self.clock)
        return count_window(self.sink, t_open, t_close)


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


@pytest.mark.parametrize("name", ["events_chat.json", "events_batch.json"])
def test_recorded_events_count_as_the_wrappers_counted(name):
    rec, run = recorded(name)
    w = count_window(run.sink, rec["t_open"], rec["t_close"])
    assert [w.prompt_tokens, w.output_tokens, len(w.steps), w.chunk_calls] == \
        rec["parent"]["window_counts"]
    steps, chunks = serve.lengths_before_each_step(run, rec["t_open"], rec["t_close"])
    assert steps == rec["parent"]["window_steps"] and len(chunks) == w.chunk_calls
    _, traced = serve.lengths_before_each_step(run, *rec["marks"])
    assert [list(c) for c in traced] == rec["parent"]["traced_chunks"] != []


def test_the_window_opens_on_a_frozen_heap_and_the_reference_on_a_thawed_one():
    """A full pass of the collector inside the window scans what the window
    allocated and no more (PERF.md, section 2); the engine's cycles are
    collectable again before the reference runs."""
    import gc
    from types import SimpleNamespace

    run = SimpleNamespace(setup_s=0.0, engine=object())
    thawed = gc.get_freeze_count()  # the interpreter keeps a few hundred of its own
    try:
        serve.open_window(run)
        assert gc.get_freeze_count() > thawed + 1000 and gc.isenabled() and run.setup_s > 0
        serve.free_program(run)
        assert gc.get_freeze_count() == thawed and run.engine is None
    finally:
        gc.unfreeze()
