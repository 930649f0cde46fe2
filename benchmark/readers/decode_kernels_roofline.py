"""The least time the chip could take for what some of a decode step's
kernels needed (the family's cost function named by `cost`, of each traced
step's `lane_lengths`) over the device time of the operations named in
`kernels` inside calls of `program`, in the traced part of the window. A
family without the cost function, or a program in which none of the kernels
ran (an earlier commit's), reads nothing."""

from lib import counts, serve
from lib.peaks import peaks
from lib.xplane import MARK_CLOSE, MARK_OPEN


def read(run, program: str, kernels: list, cost: str):
    needed = getattr(run.family, cost, None)
    if run.trace is None or needed is None:
        return None
    device_s = sum(c.op_seconds.get(f"{program}/{kernel}", 0.0)
                   for c in run.trace.chips for kernel in kernels)
    traced = len(run.trace.calls(program))
    marks = run.capture.marks
    steps, _ = serve.lengths_before_each_step(run, marks[MARK_OPEN], marks[MARK_CLOSE])
    if not steps or not traced or device_s <= 0:
        return None
    peak = peaks(run.peak["kind"])
    least = sum(counts.roofline_seconds(*needed(run.cfg, lengths), peak)[0]
                for lengths in steps)
    run.extra.setdefault("needed", {})[f"{program}/{'+'.join(kernels)}"] = {
        "calls": len(steps), "seconds": least, "traced_calls": traced,
        "device_seconds": device_s}
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(steps)) / device_s
