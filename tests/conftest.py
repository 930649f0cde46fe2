"""Test harness: 8 virtual CPU devices.

The reference tests distributed semantics on Spark ``local[N]`` threads
(SURVEY.md §4); the TPU-native translation is
``--xla_force_host_platform_device_count=8`` fake CPU devices — real
mesh/shard_map/psum semantics, no TPU required. This must run before JAX
initializes a backend, hence the env/config mutation at conftest import.
"""

import os

_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()

import jax  # noqa: E402

# Whatever JAX_PLATFORMS says (the machine with the chip defaults to the
# TPU), config.update outranks it and keeps the suite on the virtual CPU
# mesh — a chip belongs to one process at a time.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def make_blobs(n=512, num_classes=4, dim=20, seed=0, one_hot=True, spread=3.0):
    """Linearly-separable Gaussian blobs — the synthetic stand-in for the
    reference's tiny MNIST fixtures (fast, deterministic, convergeable)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=spread, size=(num_classes, dim))
    labels = rng.integers(0, num_classes, size=n)
    features = centers[labels] + rng.normal(scale=1.0, size=(n, dim))
    features = features.astype(np.float32)
    if one_hot:
        eye = np.eye(num_classes, dtype=np.float32)
        return features, eye[labels]
    return features, labels.astype(np.int32)


@pytest.fixture()
def blobs():
    return make_blobs()


def rehearsal_engine(cell: str):
    """The serving engine of one of the benchmark's cells at its files'
    `rehearsal` sizes, as `benchmark/lib/serve.py` builds it (weights from
    a seed)."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "benchmark")
    for path in (bench, os.path.dirname(bench)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from lib import cells, serve

    return serve.build(cells.Cell(cell), 1, True, False).engine


def assert_report_is_whole(report, layers=None):
    """What holds of every `obs.programs.ProgramReport`: each instruction
    that issues device work has a part, nearly all of them a scope's; every
    `-done` finds its `-start` and carries its part; a mixed fusion lists its
    parts; `copy_bytes()` is what the data-moving instructions write. With
    `layers` > 1, the model's layers have collapsed into one row."""
    import re

    from elephas_tpu.obs.programs import _FREE, UNSCOPED

    ins = report.instructions
    working = [i for i in ins.values() if i.opcode not in _FREE]
    assert working and all(i.part for i in ins.values())
    unscoped = [i.name for i in working if i.part == UNSCOPED]
    assert len(unscoped) <= max(3, 0.03 * len(working)), unscoped
    for i in ins.values():
        if i.opcode.endswith("-done"):
            assert i.start in ins, i
            assert (i.part, i.operand) == (ins[i.start].part, ins[i.start].operand), i
        assert not i.mixed or (i.opcode == "fusion" and len(i.parts) >= 2), i
    copies = report.copies()
    assert all(i.moves_data and not i.opcode.endswith("-start") for i in copies)
    assert report.copy_bytes() == sum(i.out_bytes for i in copies)
    assert sum(row[1] for row in report.copies_by_part()) == report.copy_bytes()
    if layers and layers > 1:
        parts = set(report.parts())
        assert not [p for p in parts if re.search(r"(^|/)(Layer|Block|blocks)_\d+(/|$)", p)], parts
        assert any(re.search(r"(^|/)(Layer|Block|blocks)_\*/", p) for p in parts), parts


# -- runtime lock sanitizer ---------------------------------------------------

#: Concurrency suites run with the lock sanitizer ON: every
#: ``make_lock``-routed lock (buffer version guard, RWLock, telemetry
#: store, flight recorder, alert engine, request queue, fleet
#: router/replica, snapshot-encode cache) order-checks each acquisition
#: against the statically derived graph (ANALYSIS.json) plus every
#: order observed in-process, and RAISES on inversion instead of
#: deadlocking CI. Other suites keep the zero-overhead plain-lock path.
_SANITIZED_SUITES = {
    "test_hogwild_races",
    "test_rwlock",
    "test_opsd",
    "test_fleet",
    "test_fleet_serving",
    "test_locksan",
}


@pytest.fixture(autouse=True)
def _lock_sanitizer(request):
    mod = getattr(request.node, "module", None)
    name = (mod.__name__ if mod is not None else "").rsplit(".", 1)[-1]
    if name not in _SANITIZED_SUITES or name == "test_locksan":
        # test_locksan drives enable()/disable() itself
        yield
        return
    from pathlib import Path

    from elephas_tpu.utils import locksan

    analysis = Path(__file__).resolve().parent.parent / "ANALYSIS.json"
    locksan.enable(analysis_path=analysis if analysis.exists() else None)
    try:
        yield
    finally:
        locksan.disable()
