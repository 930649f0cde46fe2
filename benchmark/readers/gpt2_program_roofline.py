"""The least time the chip could take for what a GPT-2 program's calls
needed (`lib/counts.py`: live KV, valid tokens) over the device time those
calls took, in the traced part of the window. `kind` is `decode` or `chunk`.
"""

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
        costs = [counts.gpt2_decode_cost(run.cfg, lengths) for lengths in steps]
    else:
        costs = [counts.gpt2_chunk_cost(run.cfg, start, valid) for start, valid in chunks]
    if not costs or not traced or device_s <= 0:
        return None
    least = sum(counts.roofline_seconds(f, b, peak)[0] for f, b in costs)
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(costs)) / device_s
