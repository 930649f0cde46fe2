"""Seconds under the program's tracer spans (`run.spans`: name, begin, end
on the monotonic clock), chosen by `names` or by `prefix`, as the length
of their union (so a `compile/cache_load` inside its `compile/backend`
counts once). `inside_last` keeps the spans that lie within the last span
of that name (the timed `fit`); `before_window` those that end before the
window opens (serving: the window's `t_open`; a `fit` run: the begin of
its last `fit` span). No such span, as on an untraced run or a program
without them: nothing."""

from lib.xplane import union_seconds


def last_span(spans, name: str):
    found = [s for s in spans if s[0] == name]
    return max(found, key=lambda s: s[1]) if found else None


def read(run, names=None, prefix: str = None, inside_last: str = None,
         before_window: bool = False):
    spans = [s for s in run.spans
             if s[0] in (names or ()) or (prefix and s[0].startswith(prefix))]
    if inside_last:
        outer = last_span(run.spans, inside_last)
        if outer is None:
            return None
        spans = [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2]]
    if before_window:
        if getattr(run.window, "t_open", None) is not None:
            edge = run.window.t_open
        else:
            fit = last_span(run.spans, "fit")
            if fit is None:
                return None
            edge = fit[1]
        spans = [s for s in spans if s[2] <= edge]
    # nothing also where all that matched has no length (`note_retrace`'s
    # instants under `compile/`, all that an older program leaves there)
    return union_seconds([(a, b) for _, a, b in spans])[0] or None
