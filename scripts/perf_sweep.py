"""Perf sweep for the flagship CIFAR-10 ResNet-18 train step (VERDICT r2 #1).

Measures samples/sec for a grid of {batch size × norm dtype × input dtype}
variants of the exact step bench.py times, plus XLA's own FLOP estimate so
MFU can be stated honestly. Optionally captures a jax.profiler trace of
the best variant (--trace DIR).

Usage:  python scripts/perf_sweep.py [--trace /tmp/trace] [--steps 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def build_step(norm_dtype: str, batch: int, input_dtype: str):
    from elephas_tpu.api.compile import CompiledModel
    from elephas_tpu.engine.step import init_train_state, make_train_step
    from elephas_tpu.models import get_model

    module = get_model(
        "resnet18", num_classes=10, width=64, dtype="bfloat16", norm_dtype=norm_dtype
    )
    compiled = CompiledModel(
        module,
        optimizer={"name": "momentum", "learning_rate": 0.1},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=(32, 32, 3),
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 32, 32, 3)).astype(input_dtype)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)]
    device = jax.devices()[0]
    x, y = jax.device_put(x, device), jax.device_put(y, device)
    from elephas_tpu.utils.compiler import tpu_compiler_options

    # Same compile options as bench.py/the shipped trainers — the sweep
    # must measure the program production actually runs.
    step = jax.jit(
        make_train_step(compiled), donate_argnums=(0,),
        compiler_options=tpu_compiler_options(),
    )
    state = jax.device_put(init_train_state(compiled), device)
    return step, state, x, y


def measure(step, state, x, y, steps: int, warmup: int = 5):
    for _ in range(warmup):
        state, metrics = step(state, x, y)
    float(metrics["loss"])  # a scalar fetch forces the chain
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, x, y)
    float(metrics["loss"])
    dt = time.perf_counter() - t0
    return dt / steps, state


def flops_estimate(step, state, x, y) -> float:
    try:
        cost = step.lower(state, x, y).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception as exc:  # cost analysis is best-effort
        print(f"  (cost_analysis unavailable: {exc})", file=sys.stderr)
        return 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--trace", type=str, default=None,
                    help="capture a profiler trace of the best variant here")
    ap.add_argument("--batches", type=int, nargs="*", default=[512, 1024, 2048])
    args = ap.parse_args()

    print(f"devices={jax.devices()}", file=sys.stderr)
    results = []
    for norm_dtype in ("float32", "bfloat16"):
        for input_dtype in ("float32", "bfloat16"):
            for batch in args.batches:
                step, state, x, y = build_step(norm_dtype, batch, input_dtype)
                fl = flops_estimate(step, state, x, y)
                sec, state = measure(step, state, x, y, args.steps)
                rate = batch / sec
                tflops = fl / sec / 1e12 if fl else 0.0
                row = {
                    "batch": batch,
                    "norm_dtype": norm_dtype,
                    "input_dtype": input_dtype,
                    "step_ms": round(sec * 1e3, 3),
                    "samples_per_sec": round(rate, 1),
                    "xla_flops_per_step": fl,
                    "achieved_tflops": round(tflops, 1),
                }
                results.append(row)
                print(json.dumps(row), flush=True)
                del step, state, x, y

    best = max(results, key=lambda r: r["samples_per_sec"])
    print("# best:", json.dumps(best))

    if args.trace:
        step, state, x, y = build_step(best["norm_dtype"], best["batch"],
                                       best["input_dtype"])
        sec, state = measure(step, state, x, y, 5)  # warm/compiled
        with jax.profiler.trace(args.trace):
            for _ in range(10):
                state, metrics = step(state, x, y)
            float(metrics["loss"])
        print(f"# trace written to {args.trace}", file=sys.stderr)


if __name__ == "__main__":
    main()
