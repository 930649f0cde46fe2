"""A percentile of the timed fit's epoch times (gaps between epoch ends)."""

from lib.stats import percentile


def read(run, q: float):
    return percentile(run.epoch_seconds, q) if run.epoch_seconds else None
