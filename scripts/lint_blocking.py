#!/usr/bin/env python
"""Compatibility shim over ``elephas_tpu.analysis.legacy``.

The eight lint domains that grew here (host-sync, serving-clock,
ps-pickle, resilience-clock, metric-naming, kind-vocab, route-vocab,
pool-boundary) now live in the analysis subsystem, where they share the
AST walker, pragma machinery, and rule registry with the concurrency
analyzers — run ``python -m elephas_tpu.analysis`` for the full driver
(``--list-rules`` for the inventory). This module re-exports the
historical functional API unchanged so existing imports and the tier-1
suite (``tests/test_lint_blocking.py``) keep working; running it as a
script behaves exactly as before.
"""

import os
import sys

# runnable as ``python scripts/lint_blocking.py`` from a bare checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elephas_tpu.analysis.legacy import (  # noqa: E402,F401
    CLOCK_PRAGMA,
    KIND_PRAGMA,
    METRIC_PRAGMA,
    PICKLE_PRAGMA,
    PICKLE_SANCTIONED,
    POOL_PRAGMA,
    POOL_SANCTIONED,
    PRAGMA,
    ROUTE_PRAGMA,
    SANCTIONED,
    Violation,
    lint_file,
    lint_kind_file,
    lint_kind_package,
    lint_metric_file,
    lint_metric_package,
    lint_package,
    lint_pickle_file,
    lint_pickle_package,
    lint_pool_file,
    lint_pool_package,
    lint_resilience_file,
    lint_resilience_package,
    lint_route_file,
    lint_route_package,
    load_registered_vocab,
    load_route_vocab,
    main,
)

if __name__ == "__main__":
    import sys

    sys.exit(1 if main() else 0)
