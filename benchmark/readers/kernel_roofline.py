"""The least time the chip could take for what one kernel's calls needed
(the family's cost function named by `cost`, of a chunk's valid tokens)
over the device time of the operations named `kernel` inside calls of
`program`, in the traced part of the window. A family without the cost
function, or a program without the kernel (an earlier commit's), reads
nothing."""

from lib import counts, serve
from lib.peaks import peaks
from lib.xplane import MARK_CLOSE, MARK_OPEN


def read(run, program: str, kernel: str, cost: str):
    needed = getattr(run.family, cost, None)
    if run.trace is None or needed is None:
        return None
    device_s = sum(c.op_seconds.get(f"{program}/{kernel}", 0.0) for c in run.trace.chips)
    traced = len(run.trace.calls(program))
    marks = run.capture.marks
    _, chunks = serve.lengths_before_each_step(run, marks[MARK_OPEN], marks[MARK_CLOSE])
    if not chunks or not traced or device_s <= 0:
        return None
    peak = peaks(run.peak["kind"])
    least = sum(counts.roofline_seconds(*needed(run.cfg, valid), peak)[0]
                for _, valid in chunks)
    run.extra.setdefault("needed", {})[f"{program}/{kernel}"] = {
        "calls": len(chunks), "seconds": least, "traced_calls": traced,
        "device_seconds": device_s}
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(chunks)) / device_s
