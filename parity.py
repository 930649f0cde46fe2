#!/usr/bin/env python
"""Parity harness: the five BASELINE eval configs, end-to-end.

Runs every driver surface (``SparkModel``, ``ElephasEstimator``,
``HyperParamModel``) on the BASELINE.json workloads and emits one JSON
line per config::

    {"config": ..., "mode": ..., "samples_per_sec": ..., "final_val_acc": ...,
     "real_data": ..., "epochs": ..., "train_rows": ...}

Data resolution: real datasets when present under ``$ELEPHAS_DATA_DIR``
(see ``elephas_tpu/data/datasets.py`` for drop-in file formats), else
deterministic synthetic stand-ins — ``real_data`` records which was used;
only real-data rows are comparable to published MNIST/CIFAR/IMDB numbers.

Usage::

    python parity.py                 # all five configs
    python parity.py --quick        # small slices (CI smoke)
    python parity.py --configs mnist_mlp_sync,cifar10_resnet18_hogwild
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


class EpochTimer:
    """Fit callback recording a wall-clock timestamp at every epoch end.

    Steady-state throughput comes from epochs 2..N (the span between the
    first and last timestamp): epoch 1 pays jit compilation, so dividing
    total rows by total wall time understates the framework's real rate
    by orders of magnitude on short runs (VERDICT r2 weak #1).
    """

    def __init__(self):
        self.times = []

    def __call__(self, epoch, state, metrics):
        self.times.append(time.perf_counter())


def micro_control() -> float:
    """Pinned micro-workload measuring THIS session's effective machine
    speed, run once at harness start: single device, synthetic
    fixed-seed data, fixed shapes — nothing a code change under test
    touches. Its steady samples/sec lands in every emitted row as
    ``control_samples_per_sec``, so rows from different sessions compare
    via ``ratio_to_control`` instead of raw rates (PARITY.md round 5:
    same-code throughput moved 10–45% day-to-day over the earlier
    installation's link to the chip, which silently eats cross-session
    comparisons).
    """
    import jax
    from elephas_tpu import compile_model
    from elephas_tpu.data.rdd import ShardedDataset
    from elephas_tpu.engine.sync import SyncTrainer
    from elephas_tpu.models import get_model
    from elephas_tpu.parallel.mesh import build_mesh

    rng = np.random.default_rng(0)  # pinned: identical tensors every run
    n, dim = 4096, 784
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    net = compile_model(
        get_model("mlp", features=(128, 128), num_classes=10),
        optimizer={"name": "adam", "learning_rate": 1e-3},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=(dim,),
    )
    mesh = build_mesh(num_data=1, devices=[jax.devices()[0]])
    trainer = SyncTrainer(net, mesh, frequency="epoch")
    data = ShardedDataset(x, y, 1)
    trainer.fit(data, epochs=1, batch_size=64)  # compile + warm-up
    epochs = 3
    t0 = time.perf_counter()
    trainer.fit(data, epochs=epochs, batch_size=64)
    return n * epochs / (time.perf_counter() - t0)


def _record(name, mode, history, n_rows, epochs, secs, real, timer=None, extra=None):
    val_keys = [k for k in history if k.startswith("val_") and "acc" in k]
    acc_keys = [k for k in history if "acc" in k and not k.startswith("val_")]
    if timer is not None and len(timer.times) >= 2:
        span = timer.times[-1] - timer.times[0]
        rate = n_rows * (len(timer.times) - 1) / span
        timing = "steady_state"  # excludes epoch 1 (compile + warmup)
    else:
        rate = n_rows * epochs / secs
        timing = "total_incl_compile"
    rec = {
        "config": name,
        "mode": mode,
        "samples_per_sec": round(rate, 2),
        "timing": timing,
        "total_secs": round(secs, 2),
        "final_val_acc": round(float(history[val_keys[0]][-1]), 4) if val_keys else None,
        "final_train_acc": round(float(history[acc_keys[0]][-1]), 4) if acc_keys else None,
        "real_data": real,
        "epochs": epochs,
        "train_rows": n_rows,
    }
    if extra:
        rec.update(extra)
    return rec


# ----------------------------------------------------------------- configs


def mnist_mlp_sync(quick: bool):
    """BASELINE config 1: MNIST MLP, synchronous, 4 partitions."""
    from elephas_tpu import SparkModel, compile_model, to_simple_rdd
    from elephas_tpu.data.datasets import load_mnist, one_hot
    from elephas_tpu.models import get_model

    (xtr, ytr), (xte, yte), real = load_mnist()
    if quick:
        xtr, ytr = xtr[:2048], ytr[:2048]
        xte, yte = xte[:512], yte[:512]
    x = xtr.astype(np.float32) / 255.0
    y = one_hot(ytr, 10)
    xv = xte.astype(np.float32) / 255.0
    yv = one_hot(yte, 10)
    net = compile_model(
        get_model("mlp", features=(128, 128), num_classes=10, dropout_rate=0.1),
        optimizer={"name": "adam", "learning_rate": 1e-3},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=x.shape[1:],
    )
    epochs = 2 if quick else 5
    model = SparkModel(net, mode="synchronous", frequency="epoch", num_workers=4)
    timer = EpochTimer()
    t0 = time.perf_counter()
    history = model.fit(
        to_simple_rdd(None, x, y, 4), epochs=epochs, batch_size=32,
        validation_data=(xv, yv), callbacks=[timer],
    )
    secs = time.perf_counter() - t0
    return _record("mnist_mlp_sync", "synchronous", history, len(x), epochs, secs,
                   real, timer)


def mnist_cnn_async(quick: bool):
    """BASELINE config 2: MNIST CNN, asynchronous PS."""
    from elephas_tpu import SparkModel, compile_model, to_simple_rdd
    from elephas_tpu.data.datasets import load_mnist, one_hot
    from elephas_tpu.models import get_model

    (xtr, ytr), (xte, yte), real = load_mnist()
    if quick:
        xtr, ytr = xtr[:2048], ytr[:2048]
        xte, yte = xte[:512], yte[:512]
    x = (xtr.astype(np.float32) / 255.0)[..., None]  # NHWC
    y = one_hot(ytr, 10)
    xv = (xte.astype(np.float32) / 255.0)[..., None]
    yv = one_hot(yte, 10)
    net = compile_model(
        get_model("cnn", channels=(32, 64), dense_width=128, num_classes=10),
        optimizer={"name": "adam", "learning_rate": 1e-3},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=x.shape[1:],
    )
    epochs = 2 if quick else 3
    import jax

    n_workers = len(jax.devices())
    model = SparkModel(net, mode="asynchronous", frequency="epoch", num_workers=n_workers)
    timer = EpochTimer()
    t0 = time.perf_counter()
    history = model.fit(
        to_simple_rdd(None, x, y, n_workers), epochs=epochs, batch_size=64,
        validation_data=(xv, yv), callbacks=[timer],
    )
    secs = time.perf_counter() - t0
    if getattr(model, "last_epoch_end_times", None):
        timer.times = model.last_epoch_end_times  # true worker cadence
    return _record("mnist_cnn_async", "asynchronous", history, len(x), epochs, secs,
                   real, timer)


def imdb_lstm_estimator(quick: bool):
    """BASELINE config 3: IMDB LSTM through the ML-pipeline estimator."""
    from elephas_tpu.data.datasets import load_imdb
    from elephas_tpu.data.dataframe import to_data_frame
    from elephas_tpu.ml.ml_model import ElephasEstimator

    maxlen = 120 if quick else 200
    (xtr, ytr), (xte, yte), real = load_imdb(num_words=20000, maxlen=maxlen)
    if quick:
        xtr, ytr = xtr[:2048], ytr[:2048]
        xte, yte = xte[:512], yte[:512]
    df = to_data_frame(None, xtr.astype(np.float32), ytr.astype(np.float32))
    epochs = 2 if quick else 3
    import jax

    n_workers = len(jax.devices())
    timer = EpochTimer()
    est = ElephasEstimator(
        callbacks=[timer],
        keras_model_config={
            "name": "lstm",
            "kwargs": {
                "vocab_size": 20000, "embed_dim": 64, "hidden_dim": 64,
                "num_classes": 2,
            },
            "input_shape": [maxlen],
            "input_dtype": "int32",
        },
        optimizer_config={"name": "adam", "learning_rate": 1e-3},
        loss="sparse_categorical_crossentropy",
        metrics=["acc"],
        mode="synchronous",
        frequency="epoch",
        epochs=epochs,
        batch_size=32,
        num_workers=n_workers,
        categorical=False,
        nb_classes=2,
    )
    t0 = time.perf_counter()
    transformer = est.fit(df)
    secs = time.perf_counter() - t0
    out = transformer.transform(
        to_data_frame(None, xte.astype(np.float32), yte.astype(np.float32))
    )
    preds = np.asarray(out["prediction"])
    val_acc = float((preds.argmax(-1) == yte).mean())
    history = {"val_acc": [val_acc]}
    return _record(
        "imdb_lstm_estimator", "estimator", history, len(xtr), epochs, secs, real,
        timer,
    )


def cifar10_resnet18_hogwild(quick: bool):
    """BASELINE config 4 (the flagship): CIFAR-10 ResNet-18, hogwild."""
    from elephas_tpu import SparkModel, compile_model, to_simple_rdd
    from elephas_tpu.data.datasets import load_cifar10, one_hot
    from elephas_tpu.models import get_model

    (xtr, ytr), (xte, yte), real = load_cifar10()
    if quick:
        xtr, ytr = xtr[:2048], ytr[:2048]
        xte, yte = xte[:512], yte[:512]
    mean = np.array([0.4914, 0.4822, 0.4465], np.float32) * 255.0
    std = np.array([0.247, 0.243, 0.261], np.float32) * 255.0
    x = (xtr.astype(np.float32) - mean) / std
    y = one_hot(ytr, 10)
    xv = (xte.astype(np.float32) - mean) / std
    yv = one_hot(yte, 10)
    import jax

    # bf16 compute/norm-output on TPU (the framework's native config —
    # PROFILE.md §1; f32 stats per flax semantics), f32 on CPU CI.
    dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    net = compile_model(
        get_model("resnet18", num_classes=10, width=16 if quick else 64,
                  dtype=dtype),
        optimizer={"name": "momentum", "learning_rate": 0.05},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=x.shape[1:],
    )
    epochs = 2 if quick else 4
    n_workers = len(jax.devices())
    # Per-workload compile autotune (VERDICT r4 #5): the flagship fit
    # picks its own compile options from a 2-batch A/B; the choice is
    # recorded in the emitted row (``compile_autotune``).
    model = SparkModel(net, mode="hogwild", frequency="epoch",
                       num_workers=n_workers, autotune=True)
    timer = EpochTimer()
    t0 = time.perf_counter()
    history = model.fit(
        to_simple_rdd(None, x, y, n_workers), epochs=epochs, batch_size=512,
        validation_data=(xv, yv), callbacks=[timer],
    )
    secs = time.perf_counter() - t0
    if getattr(model, "last_epoch_end_times", None):
        timer.times = model.last_epoch_end_times  # true worker cadence
    return _record(
        "cifar10_resnet18_hogwild", "hogwild", history, len(x), epochs, secs, real,
        timer,
        extra={"compile_autotune": history.get("compile_autotune")},
    )


def hyperparam_search(quick: bool):
    """BASELINE config 5: distributed random search (hyperas analogue)."""
    from elephas_tpu import compile_model
    from elephas_tpu.data.datasets import load_mnist, one_hot
    from elephas_tpu.engine.sync import SyncTrainer
    from elephas_tpu.hyperparam import HyperParamModel, current_trial_device, hp
    from elephas_tpu.models import get_model
    from elephas_tpu.data.rdd import ShardedDataset
    from elephas_tpu.parallel.mesh import build_mesh

    (xtr, ytr), (xte, yte), real = load_mnist()
    n = 2048 if quick else 4096
    x = xtr[:n].astype(np.float32) / 255.0
    y = one_hot(ytr[:n], 10)
    xv = xte[:1024].astype(np.float32) / 255.0
    yv = one_hot(yte[:1024], 10)

    from elephas_tpu.hyperparam import width_bucket

    # Executable sharing (VERDICT r4 #6): widths are PADDED to bucket
    # shapes with the true width masked (models.mlp.MaskedMLP) and the
    # lr rides opt_state ("injected"), so the whole search compiles
    # len(BUCKETS) executables instead of one per fresh (width) —
    # ~12s per fresh shape on this chip (r4 parity_results.jsonl).
    BUCKETS = (128, 256)

    def objective(sample, data):
        x, y, xv, yv = data
        w = int(sample["width"])
        net = compile_model(
            get_model(
                "mlp_masked",
                features=(width_bucket(w, BUCKETS),),
                active=(w,),
                num_classes=10,
            ),
            optimizer={"name": "adam", "learning_rate": sample["lr"],
                       "injected": True},
            loss="categorical_crossentropy",
            metrics=["acc"],
            input_shape=x.shape[1:],
        )
        # respect the trial worker's pinned device (published thread-local
        # by HyperParamModel's worker threads)
        mesh = build_mesh(num_data=1, devices=[current_trial_device()])
        trainer = SyncTrainer(net, mesh, frequency="batch")
        state, history = trainer.fit(
            ShardedDataset(x, y, 1), epochs=1 if quick else 2, batch_size=64
        )
        val = trainer.evaluate_state(state, xv, yv)
        return {"loss": float(val["loss"]), "val_acc": float(val["acc"])}

    model = HyperParamModel(None)
    # 16 full-run trials over 2 bucket shapes: >= 14 land on warm
    # executables, giving the steady-state window a real sample
    # (VERDICT r4 #6 asks >= 12 steady trials).
    max_evals = 2 if quick else 16
    t0 = time.perf_counter()
    best = model.minimize(
        objective,
        lambda: (x, y, xv, yv),
        max_evals=max_evals,
        space={"lr": hp.loguniform(np.log(1e-4), np.log(1e-2)), "width": hp.choice([64, 128, 256])},
    )
    secs = time.perf_counter() - t0
    history = {"val_acc": [best["val_acc"]]}
    epochs_per_trial = 1 if quick else 2
    rec = _record(
        "hyperparam_search", "trial-parallel", history, n * max_evals,
        epochs_per_trial, secs, real,
        extra={"best_sample": best["sample"], "trials": max_evals},
    )
    # Steady-state trial throughput (VERDICT r3 #5, closing r2 weak #1's
    # last row): a trial pays full XLA compilation the first time its
    # worker sees a given model SHAPE — now the width BUCKET, since
    # masked widths within a bucket share the executable — so the
    # comparable rate excludes each worker's first occurrence of each
    # bucket (which subsumes the first trial). Per-trial timestamps come
    # from HyperParamModel itself.
    seen_shapes = set()
    steady = []
    for t in sorted(model.trials, key=lambda t: (t["worker"], t["trial"])):
        key = (t["worker"], width_bucket(int(t["sample"]["width"]), BUCKETS))
        if key in seen_shapes:
            steady.append(t)
        else:
            seen_shapes.add(key)
    if steady:
        span = max(t["t_end"] for t in steady) - min(t["t_start"] for t in steady)
        rec["samples_per_sec"] = round(
            n * epochs_per_trial * len(steady) / span, 2
        )
        rec["timing"] = "steady_state"
        rec["trials_per_sec_steady"] = round(len(steady) / span, 4)
        rec["steady_trials"] = len(steady)
        rec["warmup_trials"] = len(model.trials) - len(steady)
    return rec


CONFIGS = {
    "mnist_mlp_sync": mnist_mlp_sync,
    "mnist_cnn_async": mnist_cnn_async,
    "imdb_lstm_estimator": imdb_lstm_estimator,
    "cifar10_resnet18_hogwild": cifar10_resnet18_hogwild,
    "hyperparam_search": hyperparam_search,
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true", help="small slices (smoke)")
    parser.add_argument("--configs", default=",".join(CONFIGS))
    parser.add_argument("--out", default="parity_results.jsonl")
    args = parser.parse_args()

    names = [n.strip() for n in args.configs.split(",") if n.strip()]
    unknown = set(names) - set(CONFIGS)
    if unknown:
        raise SystemExit(f"unknown configs: {sorted(unknown)}; known: {sorted(CONFIGS)}")

    control = round(micro_control(), 2)
    print(json.dumps({"control_samples_per_sec": control}), flush=True)

    records = []
    for name in names:
        rec = CONFIGS[name](args.quick)
        rec["control_samples_per_sec"] = control
        if rec.get("samples_per_sec"):
            rec["ratio_to_control"] = round(rec["samples_per_sec"] / control, 4)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    with open(args.out, "a") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    from elephas_tpu.utils.compiler import configure_compile_cache

    configure_compile_cache()
    main()
