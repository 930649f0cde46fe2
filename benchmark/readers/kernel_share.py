"""Device time of the operations named `kernel` inside calls of `program`
over the device time of those calls, percent, in the traced part of the
window. A program without the kernel (an earlier commit's, or a body that
runs elsewhere) reads nothing."""


def read(run, program: str, kernel: str):
    if run.trace is None:
        return None
    inside = sum(c.op_seconds.get(f"{program}/{kernel}", 0.0) for c in run.trace.chips)
    whole = run.trace.program_seconds(program)
    return 100.0 * inside / whole if whole > 0 and inside > 0 else None
