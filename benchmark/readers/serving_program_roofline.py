"""The least time the chip could take for what a serving program's calls
needed (the family's `decode_cost` / `chunk_cost`: live KV, valid tokens)
over the device time those calls took, in the traced part of the window.
`kind` is `decode` or `chunk`. The needed work is the family's, whatever
program implements it."""

from lib import counts, serve
from lib.peaks import peaks
from lib.xplane import MARK_CLOSE, MARK_OPEN


def read(run, program: str, kind: str):
    if run.trace is None:
        return None
    device_s = run.trace.program_seconds(program)
    traced = len(run.trace.calls(program))
    marks = run.capture.marks
    steps, chunks = serve.lengths_before_each_step(run, marks[MARK_OPEN], marks[MARK_CLOSE])
    peak = peaks(run.peak["kind"])
    if kind == "decode":
        costs = [run.family.decode_cost(run.cfg, lengths) for lengths in steps]
    else:
        costs = [run.family.chunk_cost(run.cfg, start, valid) for start, valid in chunks]
    if not costs or not traced or device_s <= 0:
        return None
    least = sum(counts.roofline_seconds(f, b, peak)[0] for f, b in costs)
    run.extra.setdefault("needed", {})[program] = {"calls": len(costs), "seconds": least,
                                                  "traced_calls": traced}
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(costs)) / device_s
