"""Open loop: requests are submitted when they are due, whether or not
earlier ones have finished. A request is timed from when it was due; how
late the generator ran is reported. Requests due in the window are drained
after it, outside the timed length, so that each of them has a latency.
"""

from __future__ import annotations

import time

from lib import serve
from lib.stats import WORST_MS, check, percentile
from lib.window import count_window


def drive(cell, args) -> dict:
    run = serve.build(cell, args.seed, args.rehearse, bool(args.trace))
    rate = run.extra["traffic"]["arrivals"]["rate_per_s"]
    serve.plan_requests(run, args.seed, int(rate * args.seconds) + 1)
    serve.warm_up(run, args.seed)
    due = run.schedule.due_s
    n_due = sum(1 for d in due if d < args.seconds)
    # the traced part is the window's last seconds
    traced_s = serve.trace_length(run, bool(args.trace))
    traced_from = None if traced_s is None else args.seconds - min(traced_s, args.seconds * 0.6)
    t0 = serve.open_window(run)
    nxt = 0
    while True:
        elapsed = time.monotonic() - t0
        while nxt < n_due and due[nxt] <= elapsed:
            run.submit(nxt, t0 + due[nxt])
            nxt += 1
        if elapsed >= args.seconds:
            break
        if traced_from is not None and not run.capture.active and elapsed >= traced_from:
            run.capture.start()
        if run.engine.scheduler.has_work:
            run.step()
        else:
            wait = due[nxt] - elapsed if nxt < n_due else args.seconds - elapsed
            time.sleep(min(0.002, max(0.0, wait)))
    run.drain_device()
    t_close = time.monotonic()
    serve.close_trace(run)
    run.window = count_window(run.sink, t0, t_close)
    backlog_at_close = len(run.engine.queue) + run.engine.scheduler.active_count
    deadline = time.monotonic() + 90.0
    while any(s.result is None for s in run.sent) and time.monotonic() < deadline:
        run.step()
    run.drain_device()
    serve.finish_trace(run)

    def ms(sent, field):
        r = sent.result
        if r is None or r.status != "completed" or getattr(r, field) is None:
            return WORST_MS
        late = sent.submitted - sent.due if field == "ttft_s" else 0.0
        return (getattr(r, field) + late) * 1e3

    tpot = [ms(s, "itl_s_avg") for s in run.sent]
    failed = sum(1 for s in run.sent if s.result is None or s.result.status != "completed")
    run.extra.update(
        tpot_ms=tpot, ttft_ms=[ms(s, "ttft_s") for s in run.sent],
        backlog_at_close=backlog_at_close,
        generator_late_ms_p95=percentile(run.lateness_s, 95) * 1e3 if run.lateness_s else 0.0,
        generator_late_ms_max=max(run.lateness_s, default=0.0) * 1e3)
    end_to_end = {"setup_s": run.setup_s}
    if tpot:
        end_to_end["tpot_ms_p95"] = percentile(tpot, 95)
        end_to_end["tpot_ms_p50"] = percentile(tpot, 50)
    return serve.conclude(run, args, attempted=len(run.sent), failed=failed,
                          end_to_end=end_to_end,
                          checks={"requests_answered": check(len(run.sent) - failed,
                                                             len(run.sent), "equal")})
