"""A percentile of one field of the `GenerationResult`s of the requests
the run sent (`queue_s`), times `scale`; with `gaps`, of the gaps between
consecutive entries of a per-token field (`token_times`), all tokens of
all finished requests. A program whose results lack the field reads
nothing."""

from lib.stats import percentile


def read(run, field: str, q: float, scale: float = 1.0, gaps: bool = False):
    values = []
    for sent in run.sent:
        value = getattr(sent.result, field, None)
        if value is None:
            continue
        if gaps:
            values.extend(b - a for a, b in zip(value, value[1:]))
        else:
            values.append(value)
    return percentile(values, q) * scale if values else None
