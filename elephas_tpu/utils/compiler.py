"""Backend compile options for the hot jitted programs.

One measured knob so far: ``xla_tpu_scoped_vmem_limit_kib`` (reachable
only via ``jax.jit(..., compiler_options=...)`` — this build's
``XLA_FLAGS`` parser rejects TPU flags). The r4 sweep measured raising
the scoped-VMEM budget to 96 MiB at **+4–5% on the bare flagship
ResNet-18 train step** (33.8k → 35.3k samples/sec, repeated 40-step
runs) — but a per-workload A/B on the real parity fits showed it is NOT
a safe default:

| workload (full fit, steady) | default | 96 MiB |
|---|---|---|
| CIFAR ResNet-18 hogwild | 33.3k | 32.1k (−3%) |
| MNIST CNN async         | 65.1k | 65.5k (neutral) |
| IMDB LSTM estimator     | 34.9k | **19.9k (−43%)** |

The scan-heavy LSTM regresses catastrophically, and the gains on the
bare conv step do not survive the real fit. The knob therefore ships
OPT-IN: set ``$ELEPHAS_SCOPED_VMEM_KIB`` (e.g. ``98304``) to apply it
to every hot program (train/eval/predict across all trainers, bench,
and sweeps — they share this helper so measurements match production);
unset or ``0`` compiles with backend defaults.
"""

from __future__ import annotations

import collections
import logging
import os
import time
from typing import Dict, Optional

import jax

logger = logging.getLogger("elephas_tpu")

# Retrace-storm detection: a hot program retracing this many times
# inside the window is no longer "a shape changed once" — something is
# feeding it fresh shapes/dtypes per call and silently recompiling on
# the hot path. The 4th retrace in 60s files a flight-recorder anomaly.
_RETRACE_STORM_COUNT = 4
_RETRACE_STORM_WINDOW_S = 60.0
_retrace_times: Dict[str, collections.deque] = {}


def note_retrace(program: str, **args) -> None:
    """Record a (re)trace of a hot program on the global observability
    layer: a ``retrace_total{program=...}`` counter bump and an instant
    ``compile/<program>`` event on the default tracer.

    Call this from inside a jitted function's Python body — the body
    only runs when XLA (re)traces it, so a surprise retrace (a silent
    10× regression when it happens per step) becomes a visible counter
    and a trace marker instead of nothing. The serving engine wires its
    prefill/decode bodies through here; tests pin those at one trace
    each. Repeated retraces of one program inside a short window are a
    *retrace storm* and additionally land in the flight recorder.
    """
    from elephas_tpu import obs

    obs.default_registry().counter(
        "retrace_total",
        help="hot-program (re)traces across the process",
        labelnames=("program",),
    ).labels(program=program).inc()
    obs.default_tracer().instant(f"compile/{program}", **args)
    now = time.monotonic()
    times = _retrace_times.setdefault(
        program, collections.deque(maxlen=_RETRACE_STORM_COUNT))
    times.append(now)
    if (len(times) == _RETRACE_STORM_COUNT
            and now - times[0] <= _RETRACE_STORM_WINDOW_S):
        obs.default_flight_recorder().note(
            "retrace_storm", "warn", program=program,
            retraces=_RETRACE_STORM_COUNT,
            window_s=round(now - times[0], 3),
        )
    logger.debug("retrace: %s %s", program, args or "")


# JAX's own duration events -> span names on the default tracer.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache_load",
}
_compile_spans_installed = False


def _on_compile_event(event: str, duration_secs: float, **kwargs) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    from elephas_tpu import obs

    tracer = obs.default_tracer()
    if not tracer.enabled:
        return
    # JAX reports an event as it ends: the span is [now - duration, now],
    # on the thread that compiled, inside whatever span caused it.
    now = tracer.clock()
    args = {"program": kwargs["fun_name"]} if "fun_name" in kwargs else {}
    tracer.record(name, now - duration_secs, now, **args)


def install_compile_spans() -> None:
    """Turn JAX's trace / lower / backend-compile / cache-load duration
    events into retroactive ``compile/*`` spans on the default tracer
    (arg ``program``: JAX's ``fun_name`` where it passes one). One
    listener for the process, registered by ``obs.enable_tracing()``;
    it returns at once while the default tracer is disabled. A cache
    hit shows as a ``compile/cache_load`` inside its ``compile/backend``.
    ``note_retrace``'s instants stay: they name the two serving programs
    and count them, these time every program."""
    global _compile_spans_installed
    if _compile_spans_installed:
        return
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    _compile_spans_installed = True


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return its directory.

    Entry scripts call this once before their first compile — never the
    package on import, and never the tests (a compile for a described,
    unattached chip is written to the cache but cannot be read back).
    ``$JAX_COMPILATION_CACHE_DIR`` set: JAX already honours it, so
    nothing is configured here. Unset: the fixed ``<checkout>/.jax_cache``
    — the directory is part of the cache key, so it must not move
    between runs.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cache_dir = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def tpu_compiler_options() -> Optional[dict]:
    """Compiler options for jitting hot train/eval programs.

    None (backend defaults) unless ``$ELEPHAS_SCOPED_VMEM_KIB`` opts in;
    always None off-TPU. A malformed value warns and is ignored rather
    than silently changing compile behavior.
    """
    if jax.default_backend() != "tpu":
        return None
    kib = os.environ.get("ELEPHAS_SCOPED_VMEM_KIB")
    if not kib:
        return None
    try:
        value = int(kib)
    except ValueError:
        logger.warning(
            "ELEPHAS_SCOPED_VMEM_KIB=%r is not an integer; compiling with "
            "backend defaults", kib,
        )
        return None
    if value <= 0:
        return None
    return {"xla_tpu_scoped_vmem_limit_kib": str(value)}


# The measured-separable candidate (see module docstring's A/B table):
# +4–5% on conv-heavy steps, −43% on the scan-heavy LSTM — exactly why a
# MEASUREMENT per workload, not a default, must pick it.
_SCOPED_VMEM_CANDIDATE_KIB = 98304


def autotune_candidates():
    """``[(label, compiler_options)]`` worth A/B-ing for a hot program.

    One entry (nothing to tune) off-TPU or when the user already forced
    an option set via ``$ELEPHAS_SCOPED_VMEM_KIB`` — an explicit choice
    always wins over the autotuner, and is LABELED as such so the
    recorded ``compile_autotune`` never claims 'default' for a fit that
    actually compiled with the forced knob."""
    if jax.default_backend() != "tpu":
        return [("default", None)]
    base = tpu_compiler_options()
    if base is not None:
        return [("env_forced", base)]
    return [
        ("default", None),
        (
            "scoped_vmem_96m",
            {"xla_tpu_scoped_vmem_limit_kib": str(_SCOPED_VMEM_CANDIDATE_KIB)},
        ),
    ]


def autotune_compile_options(build, run, force, steps: int = 24, candidates=None):
    """One-shot per-workload compile-option A/B (VERDICT r4 #5).

    ``build(opts) -> fn`` compiles the workload's hot program with one
    candidate's options; ``run(fn) -> out`` DISPATCHES it once
    (no blocking); ``force(out)`` makes its result real (fetch a
    scalar that depends on the whole step).
    Each candidate is compiled, warmed with one forced run, then timed
    over ``steps`` dispatches with ONE trailing force — a force per
    step would bill a host↔device round-trip to every step and drain
    the dispatch queue the steady state keeps full. The fastest
    candidate wins.

    Returns ``(winner_label, winner_options, ms_per_step_table)``.
    With a single candidate (off-TPU / env-forced) nothing is timed —
    the only candidate is returned with an empty table, so callers can
    gate unconditionally on ``autotune=True``.
    """
    import time

    from elephas_tpu import obs

    if candidates is None:
        candidates = autotune_candidates()
    if len(candidates) == 1:
        label, opts = candidates[0]
        return label, opts, {}
    table = {}
    by_label = {}
    tracer = obs.default_tracer()
    for label, opts in candidates:
        with tracer.span(f"compile/autotune:{label}"):
            fn = build(opts)
            force(run(fn))  # compile + warm
        t0 = time.perf_counter()
        out = None
        for _ in range(steps):
            out = run(fn)
        force(out)
        table[label] = (time.perf_counter() - t0) / steps * 1e3
        by_label[label] = opts
    winner = min(table, key=table.get)
    logger.info(
        "compile autotune: %r wins — %s",
        winner,
        {k: f"{v:.2f}ms" for k, v in table.items()},
    )
    return winner, by_label[winner], table
