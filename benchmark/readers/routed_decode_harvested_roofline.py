"""`routed_decode_roofline` for a traced part that holds a job's first decode
step: each traced step's needed bytes take the experts it touched from the
program's own counter on that step's event (`moe_experts_touched`), through
the family's `decode_cost(cfg, lengths, touched=)`, and a step whose event
lacks the counter (it launched a decode and had harvested none: a job's
first) is left out and stands at the mean of the others, where
`routed_decode_roofline` reads nothing for the whole part. A program whose
events never carry the counter, or a family whose cost function does not
take it, reads nothing."""

import inspect

from lib import counts
from lib.peaks import peaks
from lib.xplane import MARK_CLOSE, MARK_OPEN


def read(run, program: str, counter: str = "moe_experts_touched"):
    cost = getattr(run.family, "decode_cost", None)
    if run.trace is None or cost is None or \
            "touched" not in inspect.signature(cost).parameters:
        return None
    device_s = run.trace.program_seconds(program)
    traced = len(run.trace.calls(program))
    t0, t1 = run.capture.marks[MARK_OPEN], run.capture.marks[MARK_CLOSE]
    steps = [s for s in run.sink.steps
             if s["lane_lengths"] and s.get(counter) is not None
             and t0 <= s["t"] - s["step_seconds"] <= t1]
    if not steps or not traced or device_s <= 0:
        return None
    peak = peaks(run.peak["kind"])
    least = sum(counts.roofline_seconds(
        *cost(run.cfg, s["lane_lengths"], touched=s[counter]), peak)[0] for s in steps)
    run.extra.setdefault("needed", {})[f"{program}/touched"] = {
        "calls": len(steps), "seconds": least, "traced_calls": traced,
        "device_seconds": device_s,
        "touched_mean": sum(s[counter] for s in steps) / len(steps)}
    # the steps counted stand for every traced call: those left out, and the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(steps)) / device_s
