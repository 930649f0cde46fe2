"""A percentile of the device time of one call of a program, from the
`XLA Modules` events of the traced part of the window."""

from lib.stats import percentile


def read(run, program: str, q: float):
    calls = run.trace.calls(program) if run.trace is not None else []
    return percentile(calls, q) * 1e3 if calls else None
