"""Synchronous data-parallel trainer (mode='synchronous').

Reference semantics (SURVEY.md §3.1): each ``SparkWorker`` trains on its
whole partition locally, the driver ``collect()``s weight deltas and
averages them — one sync point per ``fit``. TPU-native redesign: the whole
epoch is ONE compiled SPMD program per device set — a ``shard_map`` over
the mesh's ``'data'`` axis whose body scans the worker's local batches;
weight coordination is an explicit ICI collective instead of a driver
``collect``:

- ``frequency='batch'``  — ``lax.pmean`` of *gradients* every step
  (lockstep DP; the idiomatic, best-converging TPU path),
- ``frequency='epoch'``  — workers train an epoch independently, then
  ``lax.pmean`` of *weights* (parameter averaging per epoch),
- ``frequency='fit'``    — parameter averaging once after all epochs:
  bit-faithful to the reference's coarsest granularity, kept for parity
  experiments (SURVEY.md §7 hard part 3).

In every case the Python driver does one dispatch per epoch (or per fit) —
there is no per-batch host round-trip, let alone the reference's
2-network-hops-per-batch.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu import obs
from elephas_tpu.engine.state import TrainState
from elephas_tpu.engine.step import (
    DeviceEvalCache,
    init_train_state,
    make_eval_step,
    make_predict_step,
    make_train_step,
    weighted_mean_over_chunks,
)
from elephas_tpu.parallel.mesh import DATA_AXIS, replicated_sharding

_PER_FIT = "fit"
_PER_EPOCH = "epoch"
_PER_BATCH = "batch"

logger = logging.getLogger("elephas_tpu")

_AUTOTUNE_SKIPPED = {"winner": "skipped", "ms_per_2batch": {}}


def decide_autotune(local, multi_host: bool):
    """Adopt ONE autotune outcome job-wide.

    ``local``: this rank's ``(winner, opts, table)``, or None when it
    could not time anything. Multi-host, host 0's outcome is broadcast
    and every rank adopts it — per-rank timings straddle noise, and
    ranks compiling one shared SPMD program with DIFFERENT compiler
    options (or recording divergent histories) would break the
    job-wide-identical invariant the engines maintain everywhere else.
    EVERY rank must call this when multi_host (the broadcast is a
    collective). Returns the adopted (winner, opts, table) or None.
    """
    if not multi_host:
        return local
    import json as _json

    from elephas_tpu.parallel import distributed

    payload = b""
    if distributed.is_host0():
        payload = _json.dumps(
            {"winner": local[0], "opts": local[1], "table": local[2]}
            if local is not None
            else None
        ).encode()
    shipped = _json.loads(distributed.broadcast_bytes_from_host0(payload).decode())
    if shipped is None:
        return None
    return shipped["winner"], shipped["opts"], shipped["table"]


def stack_epoch(features, labels, n_shards: int, batch_size: int):
    """Lay out an epoch as (num_batches, n_shards*batch_size, ...) so that
    column block ``d`` of every batch holds rows from partition ``d`` —
    partition-faithful to the reference's "one RDD partition per worker".
    """
    global_bs = n_shards * batch_size
    usable = (len(features) // global_bs) * global_bs
    if usable == 0:
        raise ValueError(
            f"dataset of {len(features)} rows too small for "
            f"{n_shards} shards × batch_size {batch_size}"
        )
    nb = usable // global_bs

    def lay_out(arr):
        arr = arr[:usable]
        # (n, nb, bs, ...): partition-major, then interleave to (nb, n*bs, ...).
        arr = arr.reshape(n_shards, nb, batch_size, *arr.shape[1:])
        arr = np.swapaxes(arr, 0, 1)
        return arr.reshape(nb, global_bs, *arr.shape[3:])

    return lay_out(np.asarray(features)), lay_out(np.asarray(labels)), nb


@jax.jit
def _dynamics_norms(prev_params, params):
    """(delta_norm, param_norm) of one epoch as two device scalars: the
    global L2 norms of ``prev_params - params`` and of ``prev_params``,
    accumulated in float32. Fetched with the epoch's metrics, so the
    dynamics gauges move no parameter to the host."""
    def sq(tree):
        return sum(
            jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            for leaf in jax.tree_util.tree_leaves(tree)
        )

    delta = jax.tree_util.tree_map(lambda a, b: a - b, prev_params, params)
    return jnp.sqrt(sq(delta)), jnp.sqrt(sq(prev_params))


class SyncTrainer:
    def __init__(
        self, compiled, mesh, frequency: str = _PER_EPOCH,
        autotune: bool = False,
    ):
        """``autotune``: one-shot per-workload compile-option A/B at fit
        start (VERDICT r4 #5) — the measured scoped-VMEM knob is
        workload-separable (+4–5% conv, −43% scan-heavy LSTM;
        utils/compiler.py table), so a 2-batch timing run on THIS
        model picks the epoch program's options instead of a default.
        The choice is recorded in ``self.autotune_choice`` and the
        fit history (``compile_autotune``)."""
        if frequency not in (_PER_BATCH, _PER_EPOCH, _PER_FIT):
            raise ValueError(f"sync frequency must be batch|epoch|fit, got {frequency!r}")
        self.compiled = compiled
        self.mesh = mesh
        self.frequency = frequency
        self.autotune = autotune
        self.autotune_choice = None
        self.ops = None
        self._ops_history = None
        self.n_shards = mesh.shape[DATA_AXIS]
        self._train_step = make_train_step(compiled)
        self._eval_step = make_eval_step(compiled)
        self._predict_step = make_predict_step(compiled)
        from elephas_tpu.utils.compiler import tpu_compiler_options

        opts = tpu_compiler_options()
        self._epoch_fn = self._build_epoch_fn(opts)
        # Jitted once here: wrapping per call would discard the trace cache
        # and retrace every epoch under validation_data (VERDICT r1 weak#1).
        self._eval_fn = jax.jit(self._eval_step, compiler_options=opts)
        # Replicated predictions: the output would otherwise inherit the
        # input's DATA sharding, and fetching it on any one host would
        # touch non-addressable shards under multi-host (r3 #7).
        self._predict_fn = jax.jit(
            self._predict_step, out_shardings=replicated_sharding(mesh),
            compiler_options=opts,
        )
        # Monotonic stamp of each epoch's end (metrics on the host, before
        # validation and callbacks), filled by every ``fit``.
        self.epoch_end_times: List[float] = []
        # ``program_report``'s: the epoch program's argument shapes as a
        # traced ``fit`` last saw them, and the report built from shapes
        self._epoch_shapes = None
        self._program_report = None

    # -- observability ---------------------------------------------------------

    def mount_ops(self, port: int = 0, host: Optional[str] = None,
                  store_dir: Optional[str] = None):
        """Mount a live introspection endpoint for this (single-process,
        SPMD) trainer — role ``worker``: ``/metrics`` serves the process
        registry the compiled-step counters feed, ``/history`` its
        sampled rings, ``/profile`` device capture + per-device memory
        watermarks (the hook the ROADMAP's real-chip runs need).
        Loopback by default; idempotent. ``store_dir`` additionally
        journals flight notes and sampler ticks into a durable telemetry
        store (``obs.store``) for post-mortem reconstruction."""
        if self.ops is not None:
            return self.ops
        from elephas_tpu import obs
        from elephas_tpu.obs.devprof import DeviceProfiler, record_device_memory
        from elephas_tpu.obs.history import HistorySampler
        from elephas_tpu.obs.opsd import OpsServer

        try:
            worker_id = f"w{jax.process_index()}"
        except Exception:
            worker_id = "w0"
        self._ops_history = HistorySampler(
            extra_fn=record_device_memory).start()
        self.store = None
        if store_dir is not None:
            self.store = obs.TelemetryStore(
                store_dir, role="worker",
                flight=obs.default_flight_recorder())
            obs.default_flight_recorder().attach_store(self.store)
            self._ops_history.attach_store(self.store)
        self.ops = OpsServer(
            port=port, host=host, role="worker", worker_id=worker_id,
            history=self._ops_history,
            vars_fn=lambda: {
                "role": "worker",
                "worker_id": worker_id,
                "frequency": self.frequency,
                "n_shards": self.n_shards,
            },
            incidents_fn=(self.store.doc if self.store is not None
                          else None),
            # ``/profile?action=stop`` answers the capture by model part
            profiler=DeviceProfiler(reports=lambda: [self.program_report()]),
        ).start()
        return self.ops

    def unmount_ops(self) -> None:
        if self.ops is not None:
            self.ops.stop()
            self.ops = None
        if self._ops_history is not None:
            self._ops_history.stop()
            self._ops_history = None
        store = getattr(self, "store", None)
        if store is not None:
            from elephas_tpu import obs
            obs.default_flight_recorder().detach_store(store)
            store.close()
            self.store = None

    # -- compiled bodies -------------------------------------------------------

    def program_report(self, state=None, xs=None, ys=None):
        """``obs.programs.ProgramReport`` of the epoch program
        (``jit_epoch_fn``): which part of the step (``forward``,
        ``backward``, ``update`` and the model's own scopes inside them)
        issued each of its instructions. The SAME jitted function is
        lowered over the shapes of ``state``, ``xs`` and ``ys`` as ``fit``
        hands them to it (arrays or ``jax.ShapeDtypeStruct``s; left out:
        those of the last ``fit`` that ran under an enabled tracer) and
        compiled once more, a load where the persistent compile cache is
        on. Built when asked for and kept until the shapes change."""
        from elephas_tpu.obs.programs import ProgramReport, shapes_of

        given = (state, xs, ys)
        if any(a is None for a in given):
            if self._epoch_shapes is None:
                raise ValueError(
                    "program_report() needs state, xs and ys, or a fit under "
                    "obs.enable_tracing() before it")
            shapes = self._epoch_shapes
        else:
            shapes = shapes_of(given)
        if self._program_report is None or self._program_report[0] != shapes:
            epoch = jax.ShapeDtypeStruct((), jnp.int32)
            self._program_report = (shapes, ProgramReport.from_compiled(
                self._epoch_fn.lower(*shapes, epoch).compile()))
        return self._program_report[1]

    def _local_shuffle(self, rng, xs, ys):
        """Per-shard reshuffle of local rows across batches (the reference's
        per-worker ``model.fit`` shuffle)."""
        nb, lbs = xs.shape[0], xs.shape[1]
        perm = jax.random.permutation(rng, nb * lbs)
        flat_x = xs.reshape(nb * lbs, *xs.shape[2:])[perm]
        flat_y = ys.reshape(nb * lbs, *ys.shape[2:])[perm]
        return flat_x.reshape(xs.shape), flat_y.reshape(ys.shape)

    def _build_epoch_fn(self, compiler_options=None):
        sync_every_step = self.frequency == _PER_BATCH
        compiled_model = self.compiled

        def body(state: TrainState, xs, ys, epoch_idx):
            # Local blocks: xs (nb, local_bs, ...), ys (nb, local_bs, ...).
            shard = jax.lax.axis_index(DATA_AXIS)
            base_rng = state.rng
            shard_rng = jax.random.fold_in(jax.random.fold_in(base_rng, epoch_idx), shard)
            data_rng, dropout_rng = jax.random.split(shard_rng)
            xs, ys = self._local_shuffle(data_rng, xs, ys)
            state = state.replace(rng=dropout_rng)

            step_fn = make_train_step(
                compiled_model, pmean_axis=DATA_AXIS if sync_every_step else None
            )

            def scan_body(carry, batch):
                x, y = batch
                new_state, metrics = step_fn(carry, x, y)
                return new_state, metrics

            state, metrics = jax.lax.scan(scan_body, state, (xs, ys))

            # Re-replicate weights/stats across shards.
            if not sync_every_step:
                state = state.replace(
                    params=jax.lax.pmean(state.params, DATA_AXIS),
                    opt_state=_pmean_float_leaves(state.opt_state),
                )
                metrics = jax.tree_util.tree_map(
                    lambda m: jax.lax.pmean(m, DATA_AXIS), metrics
                )
            state = state.replace(
                batch_stats=_pmean_float_leaves(state.batch_stats),
                rng=jax.random.fold_in(base_rng, epoch_idx + 1),
            )
            epoch_metrics = jax.tree_util.tree_map(lambda m: m.mean(), metrics)
            return state, epoch_metrics

        mesh = self.mesh
        data_spec = P(None, DATA_AXIS)  # (num_batches, global_batch, ...) axis 1

        @functools.partial(jax.jit, compiler_options=compiler_options)
        def epoch_fn(state, xs, ys, epoch_idx):
            return jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), data_spec, data_spec, P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(state, xs, ys, epoch_idx)

        return epoch_fn

    def _run_autotune(self, state, xs, ys) -> None:
        """One-shot A/B of the epoch program's compile options on a
        2-batch slice of the real stacks (same model, same shapes but
        nb=2 — scan + pmean included, so the scan-heavy regressions the
        knob can cause show up here). Winner rebuilds ``_epoch_fn``.

        Multi-host: the epoch program is GLOBAL SPMD, so every rank runs
        the same candidate sequence in lockstep (collectives line up);
        host 0's timings then decide for the job (``decide_autotune``).
        """
        from elephas_tpu.utils.compiler import autotune_compile_options

        mini_x, mini_y = xs[:2], ys[:2]

        local = autotune_compile_options(
            self._build_epoch_fn,
            lambda fn: fn(state, mini_x, mini_y, jnp.int32(0)),
            lambda out: float(out[1]["loss"]),  # a fetch forces the chain
        )
        decided = decide_autotune(local, jax.process_count() > 1)
        winner, opts, table = decided
        self.autotune_choice = {"winner": winner, "ms_per_2batch": table}
        if table:  # more than one candidate was actually timed
            self._epoch_fn = self._build_epoch_fn(opts)

    # -- host-side driver ------------------------------------------------------

    def fit(
        self,
        dataset,
        epochs: int = 10,
        batch_size: int = 32,
        validation_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        verbose: int = 0,
        initial_state: Optional[TrainState] = None,
        rng: Optional[jax.Array] = None,
        callbacks=(),
        stream_batches: Optional[int] = None,
    ) -> Tuple[TrainState, Dict[str, List[float]]]:
        """``stream_batches``: when set, at most ~2×``stream_batches``
        global batches are resident in HBM at a time (double-buffered
        host→device pipeline) instead of the whole epoch — for datasets
        larger than device memory. See ``_fit_streaming``."""
        if stream_batches is not None:
            if self.frequency == _PER_FIT:
                raise ValueError(
                    "streaming is not supported with frequency='fit' (the "
                    "parity mode scans all epochs in one resident program)"
                )
            if self.autotune and self.autotune_choice is None:
                # Not silently: the user asked for the A/B and must see
                # from history that the streamed program kept defaults.
                self.autotune_choice = dict(_AUTOTUNE_SKIPPED)
                logger.warning(
                    "autotune=True is not supported with stream_batches; "
                    "compiling the streamed epoch program with defaults "
                    "(compile_autotune='skipped')"
                )
            return self._fit_streaming(
                dataset, epochs, batch_size, stream_batches,
                validation_data, verbose, initial_state, rng, callbacks,
            )
        mesh = self.mesh
        tracer = obs.default_tracer()
        self.epoch_end_times = []
        with tracer.span("fit/state"):
            state = initial_state or init_train_state(
                self.compiled, rng=rng if rng is not None else jax.random.PRNGKey(0)
            )
            state = jax.device_put(state, replicated_sharding(mesh))

        with tracer.span("fit/stack"):
            xs, ys, nb = stack_epoch(
                dataset.features, dataset.labels, self.n_shards, batch_size
            )
        with tracer.span("fit/upload"):
            xs = jax.device_put(xs, NamedSharding(mesh, P(None, DATA_AXIS, *([None] * (xs.ndim - 2)))))
            ys = jax.device_put(ys, NamedSharding(mesh, P(None, DATA_AXIS, *([None] * (ys.ndim - 2)))))
            if tracer.enabled:
                # Only a traced run waits here, so that the span is the
                # upload's own time; untraced, the transfer overlaps the
                # first epoch's trace and lowering as it always did.
                jax.block_until_ready((xs, ys))
                from elephas_tpu.obs.programs import shapes_of

                self._epoch_shapes = shapes_of((state, xs, ys))

        if self.autotune and self.autotune_choice is None:
            if self.frequency == _PER_FIT:
                # The parity mode compiles its own all-epochs program;
                # autotuning the per-epoch proxy would record options the
                # fit doesn't use (a measurement-compat mode keeps
                # defaults, visibly).
                self.autotune_choice = dict(_AUTOTUNE_SKIPPED)
                logger.warning(
                    "autotune=True is not supported with frequency='fit'; "
                    "compiling with defaults (compile_autotune='skipped')"
                )
            else:
                self._run_autotune(state, xs, ys)

        if self.frequency == _PER_FIT:
            return self._fit_parity(state, xs, ys, epochs, validation_data, verbose)

        epoch_hist = obs.default_registry().histogram(
            "train_epoch_seconds",
            help="wall seconds per dispatched training epoch",
        )
        history: Dict[str, List[float]] = {}
        for epoch in range(epochs):
            t_ep = time.perf_counter()
            # The span covers dispatch AND the metrics fetch — the fetch
            # (`train/epoch/wait`) is where the host actually blocks on
            # the epoch program; validation and callbacks follow it
            # inside the span.
            with tracer.span("train/epoch", mode="sync", epoch=epoch) as esp:
                with tracer.span("train/epoch/dispatch"):
                    prev_params = state.params
                    state, metrics = self._epoch_fn(state, xs, ys, jnp.int32(epoch))
                    # Epoch dynamics as two device scalars, queued behind
                    # the epoch program and fetched with its metrics: no
                    # parameter goes to the host for a gauge.
                    norms = _dynamics_norms(prev_params, state.params)
                with tracer.span("train/epoch/wait"):
                    metrics, (delta_norm, param_norm) = jax.device_get(
                        (metrics, norms)
                    )
                    metrics = {k: float(v) for k, v in metrics.items()}
                self.epoch_end_times.append(time.monotonic())
                with tracer.span("train/epoch/dynamics"):
                    # Sync mode has one logical worker → the "driver"
                    # gauge row.
                    obs.record_unit_dynamics(
                        obs.default_registry(),
                        loss=metrics.get("loss"),
                        delta_norm=float(delta_norm),
                        param_norm=float(param_norm),
                        span=esp,
                    )
                epoch_hist.observe(time.perf_counter() - t_ep)
                if validation_data is not None:
                    # Eval in chunks of >=512 regardless of the (often tiny)
                    # training batch: each chunk is a host->device round-trip,
                    # and 64 tiny chunks cost more in round-trips than in eval
                    # compute. Weighted mean is exact either way.
                    with tracer.span("train/eval", epoch=epoch):
                        val = self.evaluate_state(
                            state, *validation_data, batch_size=max(batch_size, 512)
                        )
                    metrics.update({f"val_{k}": v for k, v in val.items()})
                for key, value in metrics.items():
                    history.setdefault(key, []).append(value)
                with tracer.span("train/epoch/callbacks"):
                    for cb in callbacks:
                        cb(epoch, state, metrics)
            if verbose:
                desc = " ".join(f"{k}={v:.4f}" for k, v in metrics.items())
                print(f"[sync] epoch {epoch + 1}/{epochs} {desc}")
        return state, history

    # -- streaming (datasets beyond HBM) ---------------------------------------

    def _build_stream_fns(self):
        """Chunk-scan + epoch-end programs over a *stacked* per-shard state.

        Streaming breaks the epoch into separately-dispatched chunks, so
        shard-local training state must survive shard_map boundaries
        between chunks. Representation: every state leaf gains a leading
        ``n_shards`` axis sharded on ``'data'`` — shard d's slice is its
        private state (params diverge legitimately mid-epoch under
        frequency='epoch'). The epoch-end program pmean-averages across
        shards, restoring the replicated-DP invariant.
        """
        mesh = self.mesh
        sync_every_step = self.frequency == _PER_BATCH
        step_fn = make_train_step(
            self.compiled, pmean_axis=DATA_AXIS if sync_every_step else None
        )

        def chunk_body(state_block, xs, ys):
            state = jax.tree_util.tree_map(lambda a: a[0], state_block)

            def scan_body(carry, batch):
                x, y = batch
                return step_fn(carry, x, y)

            state, metrics = jax.lax.scan(scan_body, state, (xs, ys))
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m.mean(), DATA_AXIS), metrics
            )
            return jax.tree_util.tree_map(lambda a: a[None], state), metrics

        data_spec = P(None, DATA_AXIS)
        state_spec = P(DATA_AXIS)

        chunk_fn = jax.jit(
            jax.shard_map(
                chunk_body,
                mesh=mesh,
                in_specs=(state_spec, data_spec, data_spec),
                out_specs=(state_spec, P()),
                check_vma=False,
            )
        )

        def epoch_end_body(state_block):
            state = jax.tree_util.tree_map(lambda a: a[0], state_block)
            if not sync_every_step:
                state = state.replace(
                    params=jax.lax.pmean(state.params, DATA_AXIS),
                    opt_state=_pmean_float_leaves(state.opt_state),
                )
            state = state.replace(batch_stats=_pmean_float_leaves(state.batch_stats))
            return jax.tree_util.tree_map(lambda a: a[None], state)

        epoch_end_fn = jax.jit(
            jax.shard_map(
                epoch_end_body,
                mesh=mesh,
                in_specs=(state_spec,),
                out_specs=state_spec,
                check_vma=False,
            )
        )
        return chunk_fn, epoch_end_fn

    def _fit_streaming(
        self, dataset, epochs, batch_size, stream_batches,
        validation_data, verbose, initial_state, rng, callbacks,
    ):
        """Double-buffered epoch streaming: host assembles chunk c+1 (shuffle
        gather + async device_put) while the device trains chunk c, so HBM
        holds at most ~2 chunks of ``stream_batches`` global batches — the
        TPU translation of the reference's partition *iterators*
        (``rdd.mapPartitions`` pulls batches lazily; SURVEY.md §2.1
        rdd-utils row), where the resident set is bounded no matter the
        dataset size."""
        from elephas_tpu.native import gather_rows

        mesh = self.mesh
        n_shards = self.n_shards
        state = initial_state or init_train_state(
            self.compiled, rng=rng if rng is not None else jax.random.PRNGKey(0)
        )

        features = np.asarray(dataset.features)
        labels = np.asarray(dataset.labels)
        global_bs = n_shards * batch_size
        usable = (len(features) // global_bs) * global_bs
        if usable == 0:
            raise ValueError(
                f"dataset of {len(features)} rows too small for "
                f"{n_shards} shards × batch_size {batch_size}"
            )
        nb = usable // global_bs
        rows_per_shard = nb * batch_size
        # Partition-major blocks (same layout as stack_epoch): shard d owns
        # rows [d*rows_per_shard, (d+1)*rows_per_shard).
        fparts = [
            features[d * rows_per_shard:(d + 1) * rows_per_shard]
            for d in range(n_shards)
        ]
        lparts = [
            labels[d * rows_per_shard:(d + 1) * rows_per_shard]
            for d in range(n_shards)
        ]

        chunk_fn, epoch_end_fn = self._build_stream_fns()
        data_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
        state_sharding = NamedSharding(mesh, P(DATA_AXIS))
        # Shard-0 extraction as a jitted collective with REPLICATED
        # output: every host then holds (and may fetch) the full value.
        # A plain device_get of the DATA-sharded block would touch
        # non-addressable shards and fail on multi-host (r3 #7 coverage).
        extract_fn = jax.jit(
            lambda sb: jax.tree_util.tree_map(lambda a: a[0], sb),
            out_shardings=replicated_sharding(mesh),
        )

        # Stacked state: leading shard axis; per-shard dropout streams.
        base_rng = state.rng
        shard_rngs = jax.random.split(base_rng, n_shards)
        # The rng leaf is replaced by shard_rngs below; broadcast a dummy in
        # its place — np.asarray on a typed PRNG key (jax.random.key)
        # raises TypeError, so it must not go through the numpy broadcast.
        state_block = jax.device_put(
            jax.tree_util.tree_map(
                lambda l: np.broadcast_to(np.asarray(l), (n_shards,) + np.shape(l)),
                state.replace(rng=np.zeros((), np.uint32)),
            ),
            state_sharding,
        )
        state_block = state_block.replace(rng=jax.device_put(shard_rngs, state_sharding))

        try:  # legacy uint32 keys are plain arrays; typed keys need key_data
            seed_bits = np.asarray(base_rng)
        except TypeError:
            seed_bits = np.asarray(jax.random.key_data(base_rng))
        host_rng = np.random.default_rng(int(seed_bits.ravel()[-1]) & 0x7FFFFFFF)

        def assemble(b0: int, b1: int, perms):
            """Chunk of global batches [b0, b1): (k, global_bs, ...) arrays
            with column block d holding shard d's rows (stack_epoch layout)."""
            k = b1 - b0
            fx = np.empty((k, global_bs) + features.shape[1:], features.dtype)
            fy = np.empty((k, global_bs) + labels.shape[1:], labels.dtype)
            for d in range(n_shards):
                idx = perms[d][b0 * batch_size:b1 * batch_size]
                gx, gy = gather_rows(fparts[d], lparts[d], idx, n_threads=1)
                fx[:, d * batch_size:(d + 1) * batch_size] = gx.reshape(
                    k, batch_size, *features.shape[1:]
                )
                fy[:, d * batch_size:(d + 1) * batch_size] = gy.reshape(
                    k, batch_size, *labels.shape[1:]
                )
            return (
                jax.device_put(fx, data_sharding),
                jax.device_put(fy, data_sharding),
            )

        tracer = obs.default_tracer()
        self.epoch_end_times = []
        # Every shard's slice of the stacked block is the same tree at an
        # epoch boundary, so the block's norm is sqrt(n_shards) times it.
        per_shard = 1.0 / np.sqrt(n_shards)
        history: Dict[str, List[float]] = {}
        for epoch in range(epochs):
            perms = [host_rng.permutation(rows_per_shard) for _ in range(n_shards)]
            bounds = list(range(0, nb, stream_batches)) + [nb]
            spans = list(zip(bounds[:-1], bounds[1:]))
            with tracer.span("train/epoch", mode="sync-stream", epoch=epoch) as esp:
                with tracer.span("train/epoch/dispatch"):
                    prev_params = state_block.params
                    nxt = assemble(*spans[0], perms)
                    chunk_metrics = []
                    for i, (b0, b1) in enumerate(spans):
                        cur = nxt
                        state_block, metrics = chunk_fn(state_block, *cur)  # async dispatch
                        if i + 1 < len(spans):  # overlap host assembly with device compute
                            nxt = assemble(*spans[i + 1], perms)
                        chunk_metrics.append((b1 - b0, metrics))
                    state_block = epoch_end_fn(state_block)
                    norms = _dynamics_norms(prev_params, state_block.params)

                with tracer.span("train/epoch/wait"):
                    total = sum(w for w, _ in chunk_metrics)
                    fetched, (delta_norm, param_norm) = jax.device_get(
                        ([m for _, m in chunk_metrics], norms)
                    )
                metrics = {
                    k: float(sum(w * d[k] for (w, _), d in zip(chunk_metrics, fetched)) / total)
                    for k in fetched[0]
                }
                self.epoch_end_times.append(time.monotonic())
                with tracer.span("train/epoch/dynamics"):
                    obs.record_unit_dynamics(
                        obs.default_registry(),
                        loss=metrics.get("loss"),
                        delta_norm=float(delta_norm) * per_shard,
                        param_norm=float(param_norm) * per_shard,
                        span=esp,
                    )
                snap = (
                    extract_fn(state_block)
                    if (validation_data is not None or callbacks)
                    else None
                )
                if validation_data is not None:
                    with tracer.span("train/eval", epoch=epoch):
                        val = self.evaluate_state(
                            snap, *validation_data, batch_size=max(batch_size, 512)
                        )
                    metrics.update({f"val_{k}": v for k, v in val.items()})
                for key, value in metrics.items():
                    history.setdefault(key, []).append(value)
                with tracer.span("train/epoch/callbacks"):
                    for cb in callbacks:
                        cb(epoch, snap, metrics)
            if verbose:
                desc = " ".join(f"{k}={v:.4f}" for k, v in metrics.items())
                print(f"[sync/stream] epoch {epoch + 1}/{epochs} {desc}")

        final = extract_fn(state_block)
        return final, history

    def _fit_parity(self, state, xs, ys, epochs, validation_data, verbose):
        """frequency='fit': independent local training, one final average."""
        compiled_model = self.compiled
        mesh = self.mesh

        def body(state: TrainState, xs, ys):
            shard = jax.lax.axis_index(DATA_AXIS)
            base_rng = state.rng
            step_fn = make_train_step(compiled_model)

            def epoch_body(carry, epoch_idx):
                st = carry
                rng = jax.random.fold_in(jax.random.fold_in(base_rng, epoch_idx), shard)
                data_rng, dropout_rng = jax.random.split(rng)
                exs, eys = self._local_shuffle(data_rng, xs, ys)
                st = st.replace(rng=dropout_rng)

                def scan_body(c, batch):
                    x, y = batch
                    ns, m = step_fn(c, x, y)
                    return ns, m

                st, metrics = jax.lax.scan(scan_body, st, (exs, eys))
                return st, jax.tree_util.tree_map(lambda m: m.mean(), metrics)

            state, per_epoch = jax.lax.scan(epoch_body, state, jnp.arange(epochs))
            state = state.replace(
                params=jax.lax.pmean(state.params, DATA_AXIS),
                opt_state=_pmean_float_leaves(state.opt_state),
                batch_stats=_pmean_float_leaves(state.batch_stats),
                rng=jax.random.fold_in(base_rng, epochs),
            )
            per_epoch = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, DATA_AXIS), per_epoch
            )
            return state, per_epoch

        from elephas_tpu.utils.compiler import tpu_compiler_options

        data_spec = P(None, DATA_AXIS)
        fit_fn = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), data_spec, data_spec),
                out_specs=(P(), P()),
                check_vma=False,
            ),
            compiler_options=tpu_compiler_options(),
        )
        state, per_epoch = fit_fn(state, xs, ys)
        per_epoch = jax.device_get(per_epoch)
        history = {k: [float(x) for x in v] for k, v in per_epoch.items()}
        if validation_data is not None:
            val = self.evaluate_state(state, *validation_data)
            for k, v in val.items():
                history.setdefault(f"val_{k}", []).append(v)
        if verbose:
            print(f"[sync/fit-parity] {epochs} epochs done")
        return state, history

    # -- eval / predict --------------------------------------------------------

    def _global_chunks(self, n: int, batch_size: int):
        """Yield (start, stop) chunks: equal-shard sized global batches of at
        most ``batch_size * n_shards`` rows, then a final host-remainder."""
        global_bs = batch_size * self.n_shards
        usable = (n // self.n_shards) * self.n_shards
        start = 0
        while start < usable:
            stop = min(start + global_bs, usable)
            # keep the chunk divisible by n_shards
            stop = start + ((stop - start) // self.n_shards) * self.n_shards
            yield start, stop, True
            start = stop
        if usable < n:
            yield usable, n, False

    def evaluate_state(self, state, features, labels, batch_size: int = 256) -> Dict[str, float]:
        """Sharded evaluation in chunks of ``batch_size * n_shards``; exact
        weighted mean over ALL rows (ragged remainder evaluated on one
        device, matching the reference's weighted-average evaluate).

        Sets up to the ``DeviceEvalCache`` bound are sharded onto the
        mesh once and sliced on device across repeated calls (per-epoch
        validation); larger sets stream chunk-at-a-time as always.
        """
        eval_fn = self._eval_fn
        # No-op for ndarray (identity preserved for the cache); converts
        # list inputs so the size check below can't crash. List callers
        # miss the cache (fresh object per call) but stay correct.
        features = np.asarray(features)
        labels = np.asarray(labels)
        n = len(features)
        usable = (n // self.n_shards) * self.n_shards
        if not hasattr(self, "_eval_cache"):
            self._eval_cache = DeviceEvalCache()
        cached = self._eval_cache.get(
            (features, labels, usable),
            features.nbytes + labels.nbytes,
            lambda: _put_batch(self.mesh, features[:usable], labels[:usable]),
        )

        # Dispatch every chunk, then ONE device_get for all metric dicts
        # (a fetch per chunk is a host sync stall each). Uncached
        # sets keep streaming: the trailing fetch bounds in-flight
        # uploads to ~2 chunks.
        spans = list(self._global_chunks(n, batch_size))
        device_metrics = []
        for idx, (start, stop, sharded) in enumerate(spans):
            if sharded and cached is not None:
                # start/stop are n_shards-aligned: slices stay sharded
                x, y = cached[0][start:stop], cached[1][start:stop]
            elif sharded:
                x, y = _put_batch(self.mesh, features[start:stop], labels[start:stop])
            else:
                x, y = jnp.asarray(features[start:stop]), jnp.asarray(labels[start:stop])
            device_metrics.append(eval_fn(state, x, y))
            if cached is None and idx >= 1:
                device_metrics[idx - 1] = jax.device_get(device_metrics[idx - 1])
        fetched = jax.device_get(device_metrics)
        return weighted_mean_over_chunks(
            [(s, e, i) for i, (s, e, _) in enumerate(spans)],
            lambda start, stop, i: fetched[i],
            n,
        )

    def predict_state(self, state, features, batch_size: int = 256) -> np.ndarray:
        predict_fn = self._predict_fn
        outs = []
        for start, stop, sharded in self._global_chunks(len(features), batch_size):
            if sharded:
                (x,) = _put_batch(self.mesh, features[start:stop])
            else:
                x = jnp.asarray(features[start:stop])
            outs.append(jax.device_get(predict_fn(state, x)))
        return np.concatenate(outs, axis=0)


def _pmean_float_leaves(tree):
    """Re-replicate a pytree across the data axis: float leaves are
    pmean'd; integer leaves (step counters, Keras seed-generator state)
    are pmax'd — pmean would silently promote them to float32, and a
    plain passthrough would leave shard-diverged values unreplicated."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pmean(x, DATA_AXIS)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else jax.lax.pmax(x, DATA_AXIS),
        tree,
    )


def _put_batch(mesh, *arrays):
    out = []
    for arr in arrays:
        spec = P(DATA_AXIS, *([None] * (np.ndim(arr) - 1)))
        out.append(jax.device_put(np.asarray(arr), NamedSharding(mesh, spec)))
    return tuple(out)
