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
