"""`kernel_roofline` for a kernel whose needed work depends on more of a
chunk than its valid tokens: the family's cost function named by `cost` is
called with the arguments of each traced `step/prefill_chunk` span that
`span_args` names (a chunk's `start`, `valid`, or a counter the program put
on the span), and the least time for that work stands over the device time
of the operations named `kernel` inside calls of `program`. A family without
the cost function, a program without the kernel, or a span without one of
the arguments reads nothing."""

from lib import counts
from lib.peaks import peaks
from lib.xplane import MARK_CLOSE, MARK_OPEN


def read(run, program: str, kernel: str, cost: str, span_args: list):
    needed = getattr(run.family, cost, None)
    if run.trace is None or needed is None:
        return None
    from elephas_tpu import obs

    device_s = sum(c.op_seconds.get(f"{program}/{kernel}", 0.0) for c in run.trace.chips)
    traced = len(run.trace.calls(program))
    t0, t1 = run.capture.marks[MARK_OPEN], run.capture.marks[MARK_CLOSE]
    chunks = [e.args for e in obs.default_tracer().events()
              if e.name == "step/prefill_chunk" and t0 <= e.begin_s <= t1]
    if not chunks or not traced or device_s <= 0 or \
            any(name not in args for args in chunks for name in span_args):
        return None
    peak = peaks(run.peak["kind"])
    least = sum(counts.roofline_seconds(
        *needed(run.cfg, *[args[name] for name in span_args]), peak)[0] for args in chunks)
    run.extra.setdefault("needed", {})[f"{program}/{kernel}"] = {
        "calls": len(chunks), "seconds": least, "traced_calls": traced,
        "device_seconds": device_s}
    # host calls in the marks and device calls in the trace differ by the
    # calls in flight at either mark
    return 100.0 * least * (traced / len(chunks)) / device_s
