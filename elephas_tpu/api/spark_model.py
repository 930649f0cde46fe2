"""SparkModel-compatible driver API (reference L4).

Reference: ``elephas/spark_model.py::{SparkModel, SparkMLlibModel,
load_spark_model}`` (SURVEY.md §2.1, §3.1, §3.2, §3.5). The constructor
signature, mode/frequency semantics, and fit/predict/evaluate/save surface
are preserved; Spark executors are replaced by devices of a
``jax.sharding.Mesh``, and the parameter server by ICI collectives (sync)
or an HBM-resident parameter buffer (async/hogwild).

Mode map (SURVEY.md §2.2):
- ``synchronous``  -> SPMD shard_map training, ``lax.pmean`` coordination.
- ``asynchronous`` -> per-device Downpour loops against a locked buffer.
- ``hogwild``      -> same loops, lock-free buffer.
"""

from __future__ import annotations

import logging
import pickle
from typing import Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from elephas_tpu import obs
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.data.rdd import ShardedDataset, lp_to_simple_rdd
from elephas_tpu.engine.step import init_train_state
from elephas_tpu.engine.sync import SyncTrainer
from elephas_tpu.parallel.mesh import build_mesh

logger = logging.getLogger(__name__)

MODES = ("synchronous", "asynchronous", "hogwild")
FREQUENCIES = ("batch", "epoch", "fit")


class TpuModel:
    """Driver-side distributed model (the reference's ``SparkModel``).

    Parameters mirror the reference constructor
    (``elephas/spark_model.py::SparkModel.__init__``):

    mode: 'synchronous' | 'asynchronous' | 'hogwild'.
    frequency: coordination granularity. 'batch' | 'epoch' (reference
        values; applies to async pull/push cadence and to sync averaging
        granularity) plus 'fit' (sync only: the reference's
        average-once-per-fit parity behavior).
    parameter_server_mode: 'local' (in-process HBM buffer) | 'http' |
        'socket' (cross-host transports, reference parity).
    num_workers: logical shard count; defaults to the number of devices.
        Capped to the device count (one worker == one chip).
    port: parameter-server port for http/socket transports.
    custom_objects: name->builder overrides used when deserializing.
    batch_size: default per-worker batch size for ``fit``.
    mesh: optional pre-built mesh (tests / multi-axis setups).
    """

    def __init__(
        self,
        model: Union[CompiledModel, dict],
        mode: str = "asynchronous",
        frequency: str = "epoch",
        parameter_server_mode: str = "local",
        num_workers: Optional[int] = None,
        port: int = 4000,
        custom_objects: Optional[dict] = None,
        batch_size: int = 32,
        mesh=None,
        hogwild_granularity: str = "tree",
        max_failures: int = 4,
        autotune: bool = False,
        pipelined_comms: Optional[bool] = None,
    ):
        """``hogwild_granularity`` ('tree'|'leaf'): lock-free apply
        isolation for mode='hogwild' — 'leaf' drops at most racing
        leaves instead of whole deltas (closer to the reference's
        per-element Hogwild races; measured ≈0.80 applied fraction vs
        the whole-tree default's 0.3–0.9) at one dispatch per leaf per
        push. See ``parameter.buffer.ParameterBuffer``.

        ``max_failures``: async/hogwild worker-fault retry budget — the
        analogue of Spark's ``spark.task.maxFailures`` (same default, 4)
        that the reference leaned on (SURVEY.md §5.3). A transient
        exception in a worker's epoch/batch unit retries from a fresh
        PS pull up to this many total attempts before failing the fit;
        retry counts appear in history as ``worker_retries``.

        ``autotune``: one-shot per-workload compile-option A/B at fit
        start (VERDICT r4 #5): a 2-batch run of this model is timed
        under each candidate option set (today: backend defaults vs the
        measured scoped-VMEM knob, utils/compiler.py) and the winner
        compiles the fit's hot programs. The choice lands in history as
        ``compile_autotune``. Off-TPU (or with $ELEPHAS_SCOPED_VMEM_KIB
        forcing a choice) this is a no-op.

        ``pipelined_comms``: async/hogwild only — move each worker's
        parameter-server traffic onto a background comms thread (pushes
        fire-and-forget with bounded backpressure, next pull prefetched
        while the unit trains). Default None = on for the http/socket
        transports, off for 'local'; see ``AsyncTrainer``."""
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if frequency not in FREQUENCIES:
            raise ValueError(f"frequency must be one of {FREQUENCIES}, got {frequency!r}")
        if hogwild_granularity not in ("tree", "leaf"):
            raise ValueError(
                f"hogwild_granularity must be tree|leaf, got {hogwild_granularity!r}"
            )
        if max_failures < 1:
            raise ValueError(f"max_failures must be >= 1, got {max_failures}")
        if isinstance(model, dict):
            from elephas_tpu.serialize.serialization import dict_to_model

            model = dict_to_model(model, custom_objects)
        elif not isinstance(model, CompiledModel) and (
            type(model).__module__.split(".")[0] == "keras"
            or hasattr(model, "stateless_call")
        ):
            # Reference drop-in: ``SparkModel(compiled_keras_model, ...)``
            # — ingest through the Keras-3 bridge, reading the model's own
            # compile() configuration (elephas/spark_model.py::SparkModel
            # takes the user's compiled Keras model directly).
            from elephas_tpu.serialize.keras_bridge import from_keras

            model = from_keras(model)
        if not isinstance(model, CompiledModel):
            raise TypeError(
                "model must be a CompiledModel, a compiled Keras-3 model, "
                "or a model_to_dict payload; wrap flax modules with "
                "elephas_tpu.compile_model"
            )
        self._master = model
        self.mode = mode
        self.frequency = frequency
        self.parameter_server_mode = parameter_server_mode
        self.port = port
        self.custom_objects = custom_objects or {}
        self.batch_size = batch_size
        self.pipelined_comms = pipelined_comms

        n_devices = len(jax.devices())
        if num_workers is None:
            num_workers = n_devices
        if num_workers > n_devices:
            logger.warning(
                "num_workers=%d exceeds device count %d; capping (one worker per chip)",
                num_workers,
                n_devices,
            )
            num_workers = n_devices
        self.num_workers = num_workers
        self.hogwild_granularity = hogwild_granularity
        self.max_failures = max_failures
        self.autotune = autotune
        self._mesh = mesh
        self._state = None  # latest TrainState (post-fit)
        self.training_histories: List[Dict[str, List[float]]] = []

    # -- reference surface -----------------------------------------------------

    @property
    def master_network(self) -> CompiledModel:
        return self._master

    @master_network.setter
    def master_network(self, model: CompiledModel) -> None:
        self._master = model
        self._state = None

    def get_weights(self):
        return self._master.get_weights()

    def set_weights(self, params) -> None:
        self._master.set_weights(params)
        self._state = None

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = build_mesh(num_data=self.num_workers)
        return self._mesh

    def _as_dataset(self, data, batch_size: int) -> ShardedDataset:
        if isinstance(data, ShardedDataset):
            if data.num_partitions != self.num_workers:
                data = data.repartition(self.num_workers)
            return data
        if isinstance(data, tuple) and len(data) == 2:
            return ShardedDataset(data[0], data[1], self.num_workers)
        if isinstance(data, np.ndarray):
            return ShardedDataset(data, None, self.num_workers)
        raise TypeError(f"cannot interpret training data of type {type(data)}")

    def fit(
        self,
        rdd,
        epochs: int = 10,
        batch_size: Optional[int] = None,
        verbose: int = 0,
        validation_split: float = 0.0,
        validation_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        callbacks=(),
        stream_batches: Optional[int] = None,
        initial_state=None,
    ) -> Dict[str, List[float]]:
        """Train on a ShardedDataset (or ``(x, y)``), reference §3.1/§3.2.

        ``stream_batches``: cap HBM data residency at ~2×N batches with
        a double-buffered host→device pipeline — for datasets larger
        than device memory. Sync mode streams N GLOBAL batches through
        the SPMD epoch; async/hogwild stream N batches per WORKER
        through its Downpour loop (a host-side shuffle + partition
        re-upload per epoch — prefer the default resident path when the
        partition fits).

        ``initial_state``: a restored ``TrainState`` (e.g. from
        ``elephas_tpu.checkpoint.CheckpointManager.restore``) to resume
        from. Sync mode resumes weights, optimizer slots, and step;
        async/hogwild seed the parameter server with the restored
        weights/stats (workers re-init local optimizers — Downpour never
        shares optimizer slots, SURVEY.md §3.2).
        """
        # A traced fit is one tree: `fit` roots a trace context (unless
        # the caller activated one), so its children and the `compile/*`
        # spans they cause carry parent ids besides lying inside them.
        tracer = obs.default_tracer()
        ctx = obs.current_context() or (
            obs.new_context() if tracer.enabled else None)
        with obs.activate(ctx), tracer.span(
                "fit", mode=self.mode, epochs=epochs,
                workers=self.num_workers):
            return self._fit(
                rdd, epochs, batch_size or self.batch_size, verbose,
                validation_split, validation_data, callbacks,
                stream_batches, initial_state,
            )

    def _fit(self, rdd, epochs, batch_size, verbose, validation_split,
             validation_data, callbacks, stream_batches, initial_state):
        tracer = obs.default_tracer()
        if initial_state is not None:
            # Fold restored weights into the master so every mode (and the
            # PS store, which reads compiled.params) starts from them.
            self._master.params = jax.device_get(initial_state.params)
            self._master.batch_stats = jax.device_get(initial_state.batch_stats)
            self._state = initial_state
        with tracer.span("fit/prepare"):
            dataset = self._as_dataset(rdd, batch_size)
            if dataset.labels is None:
                raise ValueError("fit needs labels")

            if validation_data is not None:
                # Normalize ONCE: downstream per-epoch validation caches the
                # device copy keyed by object identity, so the same array
                # objects must flow through the whole fit (and lists must not
                # reach nbytes-based size checks).
                validation_data = (
                    np.asarray(validation_data[0]),
                    np.asarray(validation_data[1]),
                )
            if validation_data is None and validation_split > 0:
                n_val = int(len(dataset) * validation_split)
                if n_val:
                    validation_data = (
                        dataset.features[-n_val:],
                        dataset.labels[-n_val:],
                    )
                    dataset = ShardedDataset(
                        dataset.features[:-n_val],
                        dataset.labels[:-n_val],
                        dataset.num_partitions,
                    )

        if self.mode == "synchronous":
            with tracer.span("fit/trainer"):
                trainer = SyncTrainer(
                    self._master, self.mesh, frequency=self.frequency,
                    autotune=self.autotune,
                )
            state, history = trainer.fit(
                dataset,
                epochs=epochs,
                batch_size=batch_size,
                validation_data=validation_data,
                verbose=verbose,
                callbacks=callbacks,
                stream_batches=stream_batches,
                initial_state=initial_state,
            )
            self._sync_trainer = trainer
        else:
            from elephas_tpu.engine.async_engine import AsyncTrainer

            with tracer.span("fit/trainer"):
                trainer = AsyncTrainer(
                    self._master,
                    self.mesh,
                    frequency=self.frequency,
                    lock=(self.mode == "asynchronous"),
                    parameter_server_mode=self.parameter_server_mode,
                    port=self.port,
                    granularity=(
                        self.hogwild_granularity if self.mode == "hogwild" else "tree"
                    ),
                    max_failures=self.max_failures,
                    autotune=self.autotune,
                    stream_batches=stream_batches,
                    pipelined_comms=self.pipelined_comms,
                )
            state, history = trainer.fit(
                dataset,
                epochs=epochs,
                batch_size=batch_size,
                validation_data=validation_data,
                verbose=verbose,
                callbacks=callbacks,
                initial_step=(
                    int(initial_state.step) if initial_state is not None else 0
                ),
            )
            self._sync_trainer = None

        # Epoch-end timestamps on `time.monotonic`, one per epoch in every
        # mode and with or without callbacks: the true training cadence
        # for throughput harnesses. Async/hogwild stamp the slowest
        # worker's barrier (epoch callbacks run in an overlapped drainer
        # thread there and lag by the in-flight fire); sync stamps when
        # the epoch's metrics reach the host.
        self.last_epoch_end_times = trainer.epoch_end_times
        # Compile-autotune outcome (VERDICT r4 #5): surfaced both on the
        # model and in the returned history so parity/bench tables can
        # quote which option set actually trained.
        self.last_autotune = getattr(trainer, "autotune_choice", None)
        if self.last_autotune is not None:
            history["compile_autotune"] = self.last_autotune["winner"]

        # Checkpoint saves run async during training; barrier before fit
        # returns so snapshots are durable when the caller sees the result.
        with tracer.span("fit/on_fit_end"):
            for cb in callbacks:
                hook = getattr(cb, "on_fit_end", None)
                if hook is not None:
                    hook()

        # Fold the trained weights back into the master network
        # (reference: master_network.set_weights after collect/PS stop).
        self._state = state
        # Async/hogwild leave state leaves COMMITTED to the PS/worker
        # devices; the SPMD evaluator must be free to re-place them
        # (predict after an async fit would otherwise fail on mixed
        # device commitments). Stripped lazily on first predict/evaluate.
        self._state_committed = self.mode != "synchronous"
        with tracer.span("fit/fold_back"):
            self._master.params = jax.device_get(state.params)
            self._master.batch_stats = jax.device_get(state.batch_stats)
        self.training_histories.append(history)
        return history

    def _eval_trainer(self) -> SyncTrainer:
        # Evaluation/prediction always uses the SPMD path regardless of
        # training mode (reference predict/evaluate broadcast+mapPartitions).
        trainer = getattr(self, "_sync_trainer", None)
        if trainer is None:
            trainer = SyncTrainer(self._master, self.mesh, frequency="batch")
            self._sync_trainer = trainer
        return trainer

    def _current_state(self):
        if self._state is None:
            self._state = init_train_state(self._master)
        elif getattr(self, "_state_committed", False):
            # One host fetch, then cached: uncommitted numpy leaves let
            # the jitted SPMD evaluator shard/replicate freely.
            self._state = jax.device_get(self._state)
            self._state_committed = False
        return self._state

    def predict(self, data, batch_size: int = 256) -> np.ndarray:
        """Distributed inference (reference §3.5)."""
        if isinstance(data, ShardedDataset):
            features = data.features
        else:
            features = np.asarray(data)
        return self._eval_trainer().predict_state(
            self._current_state(), features, batch_size=batch_size
        )

    def evaluate(self, x, y=None, batch_size: int = 256) -> Dict[str, float]:
        """Distributed evaluation; returns a metrics dict (loss + compiled
        metrics), the reference's weighted-average semantics (§3.5)."""
        if isinstance(x, ShardedDataset):
            features, labels = x.features, x.labels
        else:
            features, labels = np.asarray(x), np.asarray(y)
        return self._eval_trainer().evaluate_state(
            self._current_state(), features, labels, batch_size=batch_size
        )

    def save(self, path: str) -> None:
        """Persist the master network (arch + weights + optimizer config).

        The reference writes Keras HDF5; the rebuild writes a pickled
        ``model_to_dict`` payload (portable, dependency-free). Use
        ``elephas_tpu.checkpoint`` for mid-training snapshots with
        optimizer state.
        """
        from elephas_tpu.serialize.serialization import model_to_dict

        payload = {
            "model": model_to_dict(self._master),
            "mode": self.mode,
            "frequency": self.frequency,
            "parameter_server_mode": self.parameter_server_mode,
            "num_workers": self.num_workers,
            "batch_size": self.batch_size,
            "port": self.port,
            "hogwild_granularity": self.hogwild_granularity,
            "max_failures": self.max_failures,
        }
        with open(path, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)


# Reference alias: user code says ``SparkModel``.
SparkModel = TpuModel


def load_spark_model(path: str, custom_objects: Optional[dict] = None) -> TpuModel:
    """Inverse of ``SparkModel.save`` (reference ``load_spark_model``)."""
    with open(path, "rb") as f:
        payload = pickle.load(f)
    from elephas_tpu.serialize.serialization import dict_to_model

    model = dict_to_model(payload["model"], custom_objects)
    return TpuModel(
        model,
        mode=payload["mode"],
        frequency=payload["frequency"],
        parameter_server_mode=payload["parameter_server_mode"],
        num_workers=payload["num_workers"],
        batch_size=payload["batch_size"],
        port=payload["port"],
        hogwild_granularity=payload.get("hogwild_granularity", "tree"),
        max_failures=payload.get("max_failures", 4),
    )


class SparkMLlibModel(TpuModel):
    """LabeledPoint-RDD façade (reference ``SparkMLlibModel``, SURVEY.md §0)."""

    def fit(
        self,
        labeled_points,
        epochs: int = 10,
        batch_size: Optional[int] = None,
        verbose: int = 0,
        validation_split: float = 0.0,
        categorical: bool = False,
        nb_classes: Optional[int] = None,
        validation_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        callbacks=(),
    ):
        dataset = lp_to_simple_rdd(
            labeled_points,
            categorical=categorical,
            nb_classes=nb_classes,
            num_partitions=self.num_workers,
        )
        return super().fit(
            dataset,
            epochs=epochs,
            batch_size=batch_size,
            verbose=verbose,
            validation_split=validation_split,
            validation_data=validation_data,
            callbacks=callbacks,
        )
