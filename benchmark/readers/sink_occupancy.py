"""Slots in use over slots, weighted by scheduler step, from the `step`
events the program's `ServingMetrics` handed to the benchmark's sink."""


def read(run):
    steps = run.window.steps if run.window else []
    if not steps:
        return None
    slots = run.serving["max_slots"]
    return 100.0 * sum(s["active_slots"] for s in steps) / (slots * len(steps))
