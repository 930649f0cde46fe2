"""Closed backlog: an offline batch job. The job is `cycles` whole cycles
of the traffic file's requests, as many as `--seconds` holds at the file's
`cycle_s`, fixed before the window opens. The queue is topped up before
every scheduler step so that it holds the file's `backlog` for as long as
the job has requests left; the window closes when the last of them has
finished and the device is drained. The rate is the job's tokens, counted
as the program commits them (the counting rule, `lib/window.py`), over the
time the job took: every request's prompt and answer are in it whole,
whatever the program's speed. Finished requests go to `attempted` and
`failed` only.
"""

from __future__ import annotations

import time

from lib import serve
from lib.stats import check
from lib.window import count_window, run_job_window


def drive(cell, args) -> dict:
    run = serve.build(cell, args.seed, args.rehearse, bool(args.trace))
    tr = run.extra["traffic"]
    total = max(1, round(args.seconds / tr["cycle_s"])) * tr["cycle"]
    serve.plan_requests(run, args.seed, total)
    serve.warm_up(run, args.seed)
    traced_s = serve.trace_length(run, bool(args.trace))
    state = {"next": 0, "traced_from": None}

    def finished() -> int:
        return sum(1 for s in run.sent if s.result is not None)

    def top_up():
        while state["next"] < total and len(run.engine.queue) < tr["backlog"]:
            run.submit(state["next"], None)
            state["next"] += 1

    def step():
        # the traced part: a few seconds from the job's middle, while the
        # slots and the queue are still full
        if traced_s is not None:
            now = time.monotonic()
            if state["traced_from"] is None:
                if finished() >= tr["trace"]["after_finished"] * total:
                    run.capture.start()
                    state["traced_from"] = now
            elif now - state["traced_from"] >= traced_s:
                run.capture.mark_close()
        run.step()

    run.drain_device()
    serve.open_window(run)
    t_open, t_close = run_job_window(step, top_up, lambda: finished() < total,
                                     run.drain_device, time.monotonic)
    serve.close_trace(run)
    run.window = count_window(run.sink, t_open, t_close)
    serve.finish_trace(run)
    failed = sum(1 for s in run.sent if s.result.status != "completed")
    run.extra["ttft_ms"] = [s.result.ttft_s * 1e3 for s in run.sent
                            if s.result.ttft_s is not None]
    # an answer's first token comes with its prompt's last chunk, the others
    # one a decode step: the window holds the job, no more and no less
    asked = sum(run.schedule.prompt_lens[:total]) + sum(run.schedule.output_lens[:total]) - total
    end_to_end = {"setup_s": run.setup_s, "serve_tokens_per_s": run.window.tokens_per_s}
    return serve.conclude(run, args, attempted=total, failed=failed, end_to_end=end_to_end,
                          checks={"requests_failed": check(failed, 0, "equal"),
                                  "job_tokens_committed": check(
                                      run.window.prompt_tokens + run.window.output_tokens,
                                      asked, "equal")})
