"""`serving_program_roofline` of the decode program for a family whose
routed layers read only the experts that got a token: each traced step's
needed bytes take the experts it touched from the program's own counter on
that step's event (`moe_experts_touched`, summed over the routed layers),
through the family's `decode_cost(cfg, lengths, touched=)`. A step's event
carries the lanes of the decode it launched and the counters of the decode
it harvested, the step before: over the traced part the two sums differ by
a step's worth at either end. A program whose events lack the counter, or a
family whose cost function does not take it, reads nothing."""

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
             if s["lane_lengths"] and t0 <= s["t"] - s["step_seconds"] <= t1]
    if not steps or not traced or device_s <= 0 or \
            any(s.get(counter) is None for s in steps):
        return None
    peak = peaks(run.peak["kind"])
    least = sum(counts.roofline_seconds(
        *cost(run.cfg, s["lane_lengths"], touched=s[counter]), peak)[0] for s in steps)
    run.extra.setdefault("needed", {})[f"{program}/touched"] = {
        "calls": len(steps), "seconds": least, "traced_calls": traced,
        "touched_mean": sum(s[counter] for s in steps) / len(steps)}
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(steps)) / device_s
