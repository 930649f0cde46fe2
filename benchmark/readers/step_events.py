"""A percentile, or the step-weighted mean, of one number per `step`
event of the window (`ServingMetrics.record_step`'s fields): `field`, less
`minus`, over `over`, times `scale`. A program whose events lack a field
reads nothing."""

from lib.stats import percentile


def read(run, field: str, minus: str = None, over: str = None, scale: float = 1.0,
         q: float = None):
    values = []
    for step in (run.window.steps if run.window else []):
        parts = [step.get(name) for name in (field, minus, over) if name]
        if any(p is None for p in parts):
            continue
        value = step[field] - (step[minus] if minus else 0.0)
        if over:
            if not step[over]:
                continue
            value /= step[over]
        values.append(value * scale)
    if not values:
        return None
    return percentile(values, q) if q is not None else sum(values) / len(values)
