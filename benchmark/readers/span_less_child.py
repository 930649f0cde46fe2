"""A percentile, over the spans named `span` inside the last span named
`inside_last`, of each one's length less the `child` spans inside it:
an epoch's `train/epoch` without its `train/epoch/wait` is the host's
own time in that epoch. No such span, or none with a child: nothing."""

from lib.stats import percentile
from readers.span_seconds import last_span


def read(run, span: str, child: str, inside_last: str, q: float):
    outer = last_span(run.spans, inside_last)
    if outer is None:
        return None
    _, lo, hi = outer
    children = [s for s in run.spans if s[0] == child]
    values = []
    for name, a, b in run.spans:
        if name != span or a < lo or b > hi:
            continue
        inner = [d - c for _, c, d in children if a <= c and d <= b]
        if inner:
            values.append((b - a) - sum(inner))
    return percentile(values, q) if values else None
