"""A percentile of a per-request series the driver kept (`ttft_ms`, timed
from when the request was due or submitted; `tpot_ms`)."""

from lib.stats import percentile


def read(run, series: str, q: float):
    values = run.extra.get(series)
    return percentile(values, q) if values else None
