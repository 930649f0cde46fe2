"""Percentiles and the result line."""

from __future__ import annotations

import json
import sys
from typing import Dict, Sequence

import numpy as np

WORST_MS = 1e6  # what a failed or refused request counts as, in any latency


def percentile(values: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def passes(checks: Dict[str, dict]) -> bool:
    return all(c["ok"] for c in checks.values())


def print_result(attempted: int, failed: int, metrics: Dict[str, dict], device: dict,
                 checks: Dict[str, dict], controls: Dict[str, Dict[str, dict]],
                 breakdown=None, extra=None) -> None:
    """The last line of standard output, and each number compared beside
    its limit as the last lines of standard error. A control (`--control
    1`) is something put in the program's place that has to come out not
    correct; its numbers go through the same comparison and are printed
    before the program's, and never count towards `correct`."""
    line = {"correct": passes(checks), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line["notes"] = extra
    if controls:
        line["control_correct"] = {name: passes(cs) for name, cs in controls.items()}
        line["control_checks"] = controls
    line["checks"] = checks
    sys.stdout.flush()
    shown = [(f"control {name}: ", cs) for name, cs in controls.items()] + [("", checks)]
    for prefix, group in shown:
        for name, c in group.items():
            print(f"{prefix}check {name}: value {c['value']!r} limit {c['limit']!r} "
                  f"({c['rule']}) -> {'ok' if c['ok'] else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def check(value, limit, rule: str = "at_most") -> dict:
    """`at_most`: value <= limit; `at_least`: value >= limit; `equal`."""
    ok = {"at_most": value <= limit, "at_least": value >= limit,
          "equal": value == limit}[rule]
    return {"value": value, "limit": limit, "rule": rule, "ok": bool(ok)}
