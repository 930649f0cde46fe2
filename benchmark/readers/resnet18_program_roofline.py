"""The least time the chip could take for the training steps inside one
call of the epoch program (forward and backward FLOPs and bytes of
ResNet-18 by layer shapes, `lib/counts.py`) over the device time of the
program's calls in the traced epochs."""

from lib import counts
from lib.peaks import peaks


def read(run, program: str):
    if run.trace is None:
        return None
    calls = run.trace.calls(program)
    if not calls:
        return None
    flops, nbytes = counts.resnet18_step_cost(run.cfg, run.batch)
    least = counts.roofline_seconds(flops, nbytes, peaks(run.peak["kind"]))[0] * run.steps
    return 100.0 * least * len(calls) / sum(calls)
