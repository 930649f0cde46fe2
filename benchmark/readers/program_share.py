"""Device time inside calls of one program over the device's busy time."""


def read(run, program: str):
    if run.trace is None:
        return None
    busy = sum(c.busy_s for c in run.trace.chips)
    seconds = run.trace.program_seconds(program)
    return 100.0 * seconds / busy if busy > 0 and seconds > 0 else None
