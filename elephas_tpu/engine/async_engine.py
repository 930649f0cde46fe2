"""Asynchronous / hogwild trainer (Downpour SGD on chips).

Reference semantics (SURVEY.md §3.2): each ``AsynchronousSparkWorker``
loops pull -> train one ``frequency`` unit ('epoch' or 'batch') -> push
delta against the driver's parameter server; ``asynchronous`` locks the
server state, ``hogwild`` doesn't.

TPU-native redesign (SURVEY.md §7 hard part 1): XLA wants lockstep SPMD,
Downpour wants divergent per-chip programs — so each worker is a *host
thread* driving independently-jitted steps on its own chip, and the
parameter server is an HBM-resident ``ParameterBuffer``. A pull is a
device-to-device copy, a push is an on-device subtract; with the
``http``/``socket`` transports the same loop spans hosts. Host work per
round is a dispatch + two small transfers, so the GIL stays out of the
hot path and chip queues run ahead.

Worker-local optimizer state persists across rounds (Downpour keeps
worker optimizers; only weights flow through the server — matching the
reference, where the driver averages weights, never optimizer slots).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu import obs
from elephas_tpu.engine.state import TrainState
from elephas_tpu.engine.step import make_epoch_scanner, make_train_step
from elephas_tpu.parallel.mesh import DATA_AXIS
from elephas_tpu.parameter.client import (
    ParameterServerUnavailable,
    StaleDeltaRejected,
)
from elephas_tpu.parameter.server import make_server
from elephas_tpu.utils.functional_utils import subtract_params

_FREQUENCIES = ("batch", "epoch")

logger = logging.getLogger("elephas_tpu")


@jax.jit
def _probe_sum(leaves):
    """Scalar depending on every leaf — fetching it forces them all with
    a single device round-trip (phase-profiling helper)."""
    return sum(
        jnp.reshape(leaf, (-1,))[0].astype(jnp.float32) for leaf in leaves
    )


class _PullBox:
    """One in-flight prefetched pull: the comms thread fills exactly one
    of value/error, then sets the event."""

    __slots__ = ("event", "value", "error")

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error = None


class _CommsPipeline:
    """Per-worker background comms thread: pushes become bounded
    fire-and-forget, pulls become prefetches.

    One FIFO queue, one thread — so deltas are applied in the order the
    worker produced them, and a prefetched pull is ordered exactly where
    the worker enqueued it relative to its pushes. The queue is bounded
    (``maxsize=3``): a worker outrunning the wire blocks in ``push()``
    (backpressure) instead of growing an unbounded backlog of
    model-sized deltas.

    Failure contract (mirrors ``run_unit``'s, shifted off-thread):

    - ``ParameterServerUnavailable`` is infrastructure death — recorded
      as fatal, never retried; the worker's NEXT pipeline op re-raises
      it, preserving the fail-fast bound (pull waiters get it
      immediately via their box).
    - A transient push failure retries the SAME delta up to
      ``max_failures`` total attempts (counted in ``ps_push_retry_total``).
      This is the engine layer's documented at-least-once: the wire
      client never re-sends an in-flight write, but the failed attempt
      may have applied server-side, so the re-push can double-apply —
      benign for SGD, same noise class as ``run_unit``'s unit-level
      re-push (see its docstring).
    - Pull failures are NOT retried here — they surface to the waiting
      worker, whose ``run_unit`` owns unit-level retry exactly as on
      the serial path.
    - ``StaleDeltaRejected`` is the PS admission policy's DEFINITIVE
      answer, not a fault: the delta is dropped (re-sending it would be
      MORE stale), the next ``pull()`` is forced onto fresh params even
      if a prefetch is pending, and the push cadence tightens — see the
      ratchet below. Never fatal, never retried.
    - After a fatal, the thread short-circuits the remaining queue
      (pushes complete without wire ops, pull boxes get the fatal) so
      ``flush``/``close`` never deadlock behind a dead server.

    Adaptive sync-interval ratchet (bounded-staleness client half):
    ``sync_interval`` is the worker's train-units-per-push target.
    ``push()`` coalesces deltas (tree-sum — the exact delta the units
    would have pushed one at a time, modulo apply interleaving, which
    is Downpour's standard noise) and enqueues one wire push per
    ``round(interval)`` units. A ``StaleDeltaRejected`` HALVES the
    interval (floor 1.0 — push every unit) so consecutive rejections
    converge on the tightest cadence; each accepted push ADDS 0.25
    back, capped at the configured baseline (AIMD). The live value is
    exported as the ``worker_sync_interval`` gauge and stamped onto the
    client (``client.sync_interval``) so every push frame carries it to
    the PS staleness ledger / fleet SYNC column. The default baseline
    of 1.0 is a no-op ratchet: one push per unit, exactly the
    pre-ratchet behavior, until a rejection proves the PS is enforcing
    bounds (the interval can't drop below 1.0, so only the counters
    move).

    ``flush()`` waits for every enqueued push to complete — called at
    each epoch boundary BEFORE ``on_epoch_done`` so the barrier snapshot
    (validation/checkpoint) sees all of this worker's epoch pushes; it
    deliberately does not wait on a pending prefetch.

    Trace carriage: contextvars don't cross the queue hop, so every
    enqueue captures the worker's active trace context (and the enqueue
    timestamp) into the item; the comms thread re-activates it around
    the wire op — the client's ``ps/push``/``ps/pull`` spans, and the
    PS-side handle spans they propagate to, land in the unit's causal
    tree even though they ran on this thread. The enqueue→dequeue wait
    is recorded as a ``comms/queued`` span: the "queue" phase of the
    per-unit critical-path table.
    """

    # Backoff between same-delta push retries: a transient server hiccup
    # (GC pause, contended accept queue) usually clears in well under a
    # second; retrying instantly just burns the attempt budget into the
    # same hiccup.
    _PUSH_RETRY_DELAYS = (0.05, 0.1, 0.2)

    def __init__(self, client, worker_index: int, max_push_attempts: int,
                 sleep=time.sleep, sync_interval: float = 1.0):
        """``sleep`` is injectable so retry/backoff tests assert the
        schedule without real waits (tier-1 must not sleep).
        ``sync_interval``: baseline train-units-per-push (>= 1.0); the
        AIMD ratchet moves the live value between 1.0 and this cap."""
        if sync_interval < 1.0:
            raise ValueError(
                f"sync_interval must be >= 1.0, got {sync_interval}"
            )
        self._client = client
        self._sleep = sleep
        self._max_push_attempts = max(1, max_push_attempts)
        self._worker_label = f"w{worker_index}"
        self._queue: queue.Queue = queue.Queue(maxsize=3)
        self._fatal: Optional[BaseException] = None
        self._pending: Optional[_PullBox] = None
        self._push_cond = threading.Condition()
        self._pushes_enqueued = 0
        self._pushes_done = 0
        # Ratchet state. _acc/_acc_units are touched only by the worker
        # thread; _interval is written by the comms thread (reject /
        # accept) and read by the worker thread — a float slot under the
        # GIL, no lock needed. rejections is the test/ops-visible count.
        self._baseline = float(sync_interval)
        self._interval = float(sync_interval)
        self._acc = None
        self._acc_units = 0
        self._repull = threading.Event()
        self.rejections = 0
        self._set_interval(self._interval)
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"worker{worker_index}-comms"
        )
        self._thread.start()

    @property
    def sync_interval(self) -> float:
        """The live train-units-per-push interval (AIMD-adjusted)."""
        return self._interval

    def _set_interval(self, value: float) -> None:
        """Move the ratchet: stamp the client (every subsequent push
        frame carries the value to the PS ledger) and export the gauge."""
        value = float(value)
        self._interval = value
        try:
            self._client.sync_interval = value
        except Exception:
            pass  # a client that refuses the stamp just goes unlabeled
        obs.default_registry().gauge(
            "worker_sync_interval",
            help="adaptive train-units-per-push interval (AIMD: halved "
                 "on a stale-delta rejection, +0.25 per accept up to "
                 "the configured baseline)",
            labelnames=("worker",),
        ).labels(worker=self._worker_label).set(value)

    # -- worker-side API ------------------------------------------------

    def prefetch(self) -> None:
        """Schedule the next pull now so it rides the wire while the
        worker trains; no-op if one is already pending or we're dead."""
        if self._fatal is not None or self._pending is not None:
            return
        box = _PullBox()
        self._pending = box
        self._put(self._item("pull", box))

    def pull(self):
        """Consume the pending prefetch (or issue a synchronous pull),
        blocking until the params arrive. After a stale-delta rejection
        a pending prefetch is DISCARDED — its params predate the
        rejection, and the whole point of the re-pull is to train the
        next unit from the version line that refused us."""
        self._raise_if_fatal()
        box, self._pending = self._pending, None
        if box is not None and self._repull.is_set():
            box.event.wait()  # let the in-flight wire op finish cleanly
            box = None
        if box is None:
            self._repull.clear()
            box = _PullBox()
            self._put(self._item("pull", box))
        box.event.wait()
        if box.error is not None:
            raise box.error
        return box.value

    def push(self, delta) -> None:
        """Record one unit's delta; enqueues a WIRE push only when
        ``round(interval)`` units have coalesced (tree-sum). Blocks only
        when the bounded queue is full (backpressure) or re-raises a
        recorded fatal."""
        self._raise_if_fatal()
        if self._acc is None:
            self._acc = delta
        else:
            self._acc = jax.tree_util.tree_map(
                lambda a, b: a + b, self._acc, delta
            )
        self._acc_units += 1
        if self._acc_units >= max(1, int(round(self._interval))):
            self._enqueue_acc()

    def _enqueue_acc(self) -> None:
        delta, self._acc = self._acc, None
        self._acc_units = 0
        with self._push_cond:
            self._pushes_enqueued += 1
        self._put(self._item("push", delta))

    def flush(self) -> None:
        """Push any coalesced remainder, then wait for every enqueued
        push to complete."""
        if self._acc is not None:
            self._enqueue_acc()
        with self._push_cond:
            while self._pushes_done < self._pushes_enqueued:
                self._push_cond.wait(0.05)
        self._raise_if_fatal()

    def close(self) -> None:
        """Stop and join the comms thread (idempotent). Call BEFORE
        closing the client — a stray prefetch otherwise races the close."""
        if self._thread is None:
            return
        self._put(("stop", None, None, None))
        self._thread.join()
        self._thread = None

    # -- comms thread ---------------------------------------------------

    @staticmethod
    def _item(kind, payload):
        # Snapshot the worker's trace context + enqueue time: contextvars
        # don't cross the queue hop, and the wait itself is the unit's
        # "queue" phase.
        tracer = obs.default_tracer()
        return (kind, payload, obs.current_context(),
                tracer.clock() if tracer.enabled else None)

    def _raise_if_fatal(self) -> None:
        if self._fatal is not None:
            raise self._fatal

    def _put(self, item) -> None:
        # Bounded put that can't wedge: after a fatal the thread drains
        # the queue without wire ops, so the timeout loop always exits.
        while True:
            try:
                self._queue.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    def _loop(self) -> None:
        while True:
            kind, payload, ctx, enqueue_t = self._queue.get()
            if kind == "stop":
                return
            with obs.activate(ctx):
                tracer = obs.default_tracer()
                if enqueue_t is not None and tracer.enabled:
                    tracer.record("comms/queued", enqueue_t, tracer.clock(),
                                  op=kind, worker=self._worker_label)
                if kind == "pull":
                    box = payload
                    if self._fatal is not None:
                        box.error = self._fatal
                        box.event.set()
                        continue
                    try:
                        box.value = self._client.get_parameters()
                    except BaseException as exc:
                        box.error = exc
                        if isinstance(exc, ParameterServerUnavailable):
                            self._fatal = exc
                    box.event.set()
                else:  # push
                    try:
                        if self._fatal is None:
                            self._push_with_retry(payload)
                    finally:
                        with self._push_cond:
                            self._pushes_done += 1
                            self._push_cond.notify_all()

    def _push_with_retry(self, delta) -> None:
        for attempt in range(self._max_push_attempts):
            try:
                self._client.update_parameters(delta)
                if self._interval < self._baseline:
                    # Additive recovery: each accepted push relaxes the
                    # cadence back toward the configured baseline.
                    self._set_interval(
                        min(self._baseline, self._interval + 0.25)
                    )
                return
            except ParameterServerUnavailable as exc:
                self._fatal = exc  # fail-fast contract: never retried
                return
            except StaleDeltaRejected:
                # The admission policy's definitive answer: this delta
                # is too stale and a re-send would be MORE stale. Drop
                # it, force the next pull onto fresh params, and halve
                # the units-per-push interval (multiplicative half of
                # the AIMD ratchet) so the worker syncs more often.
                self.rejections += 1
                self._repull.set()
                self._set_interval(max(1.0, self._interval / 2.0))
                return
            except Exception as exc:
                if attempt + 1 >= self._max_push_attempts:
                    self._fatal = exc
                    return
                obs.default_registry().counter(
                    "ps_push_retry_total",
                    help="background same-delta push retries (pipelined comms)",
                    labelnames=("worker",),
                ).labels(worker=self._worker_label).inc()
                self._sleep(self._PUSH_RETRY_DELAYS[
                    min(attempt, len(self._PUSH_RETRY_DELAYS) - 1)
                ])


class AsyncTrainer:
    def __init__(
        self,
        compiled,
        mesh,
        frequency: str = "epoch",
        lock: bool = True,
        parameter_server_mode: str = "local",
        port: int = 4000,
        granularity: str = "tree",
        max_failures: int = 4,
        autotune: bool = False,
        stream_batches: Optional[int] = None,
        pipelined_comms: Optional[bool] = None,
        elastic: bool = False,
        fault_plan=None,
        ps_wal_dir: Optional[str] = None,
        wal_every: int = 1,
        ps_recovery_grace: float = 15.0,
        ps_ops_port: Optional[int] = None,
        ps_shards: Optional[int] = None,
        standby: Optional[int] = None,
        sync_interval: float = 1.0,
        batches_per_unit: Optional[int] = None,
    ):
        """``pipelined_comms``: run each worker's PS traffic on a
        background comms thread (``_CommsPipeline``) — pushes become
        bounded fire-and-forget, and the next unit's pull prefetches
        while the current one trains ('batch' frequency; 'epoch'
        prefetches after the push so an epoch pull always sees the
        worker's own epoch). Default (None) enables it for the wire
        transports (http/socket), where a round-trip costs real wall
        time, and disables it for 'local', where a pull is a device
        handle copy and the extra thread is pure overhead. At 'batch'
        frequency the prefetched pull can miss the worker's own
        just-pushed delta (one unit of self-staleness — standard
        Downpour staleness, traded for full wire/compute overlap).

        ``granularity`` ('tree'|'leaf'): hogwild apply isolation —
        'leaf' drops at most racing leaves instead of whole deltas at the
        cost of one dispatch per leaf per push (ParameterBuffer note).

        ``stream_batches``: cap each worker's HBM data residency at
        ~2×N batches with a double-buffered chunk pipeline instead of
        holding the whole partition device-resident — for partitions
        beyond per-chip HBM (the async analogue of the sync trainer's
        streaming). Costs a host-side shuffle + partition re-upload per
        epoch, so leave unset when the partition fits.

        ``autotune``: one-shot per-workload compile-option A/B at fit
        start (VERDICT r4 #5): the scoped-VMEM knob is workload-
        separable (+4–5% conv step, −43% scan-heavy LSTM —
        utils/compiler.py table), so a 2-batch scan of THIS model is
        timed under each candidate and the winner compiles the worker
        programs. Recorded in ``self.autotune_choice`` and the history
        (``compile_autotune``).

        ``max_failures``: attempts per frequency-unit before a worker
        fault fails the fit — the analogue of Spark's task retry
        (``spark.task.maxFailures``, default 4, SURVEY.md §5.3), which
        the reference delegated to Spark wholesale. A transient worker
        exception (one bad batch, a flaky dispatch) retries its current
        epoch/batch unit from a FRESH parameter-server pull with a
        re-seeded RNG/shuffle stream; ``ParameterServerUnavailable`` is
        infrastructure death, not a task fault, and is never retried.

        ``elastic``: run ``fit`` on the resilience layer's self-healing
        pool (``elephas_tpu.resilience``) instead of the fixed
        thread-per-partition loop: frequency units become ``(epoch,
        partition)`` ledger entries leased to whichever worker is alive,
        a dead worker's units are re-queued to survivors, late joiners
        enter mid-epoch, and a parameter-server crash is ridden out for
        ``ps_recovery_grace`` seconds (warm restart) instead of failing
        the fit. Single-host, ``frequency='epoch'`` only.

        ``fault_plan``: a ``resilience.FaultPlan`` — deterministic,
        seeded chaos (dropped/delayed/duplicated wire frames, worker
        kills/stalls at chosen unit indices) installed for the duration
        of the fit; identical plans replay identical failure schedules.

        ``ps_wal_dir``/``wal_every``: write-ahead snapshot directory for
        the PS (wire transports): accepted pushes become durable before
        they are acked (at most ``wal_every - 1`` versions of lag) and a
        server constructed over the same directory warm-restarts from
        the newest durable version.

        ``ps_shards``: shard the parameter tree across K wire-server
        processes (``parameter.group.ShardGroup``) — workers scatter
        pushes / gather pulls concurrently, so aggregate PS bandwidth
        scales with K. Wire transports, single-host fits only (a
        multi-host fit broadcasts ONE address; the group directory is
        in-process). Default ``$ELEPHAS_PS_SHARDS`` or unsharded.
        ``standby``: with ``ps_shards``, keep one WAL-streamed warm
        spare per shard and promote it when the group's failure
        detector declares a primary dead (requires ``ps_wal_dir``).
        Default ``$ELEPHAS_PS_STANDBY`` or 0.

        ``sync_interval``: baseline train-units-per-push for the
        pipelined comms ratchet (>= 1.0; default 1.0 = push every
        unit, the pre-ratchet cadence). Values > 1 coalesce that many
        units' deltas per wire push — fewer round-trips, more
        staleness; a PS enforcing bounded-staleness admission pushes
        back with rejections, which HALVE the live interval (floor
        1.0), while accepts relax it +0.25 back toward this baseline.

        ``batches_per_unit``: with ``elastic=True``, cut each
        ``(epoch, partition)`` ledger unit into batch ranges of this
        many batches — a worker death mid-epoch re-leases only the
        unfinished ranges, not whole partitions. Default None keeps
        whole-partition units."""
        if frequency not in _FREQUENCIES:
            raise ValueError(
                f"async frequency must be batch|epoch, got {frequency!r} "
                "(the reference's AsynchronousSparkWorker supports the same two)"
            )
        if max_failures < 1:
            raise ValueError(f"max_failures must be >= 1, got {max_failures}")
        self.compiled = compiled
        self.mesh = mesh
        self.frequency = frequency
        self.lock = lock
        self.parameter_server_mode = parameter_server_mode
        self.port = port
        self.granularity = granularity
        self.max_failures = max_failures
        if stream_batches is not None and stream_batches < 1:
            raise ValueError(f"stream_batches must be >= 1, got {stream_batches}")
        self.stream_batches = stream_batches
        self.pipelined_comms = pipelined_comms
        if elastic and frequency != "epoch":
            raise ValueError(
                "elastic=True schedules (epoch, partition) ledger units, "
                "which are epoch-granular — use frequency='epoch'"
            )
        self.elastic = elastic
        if sync_interval < 1.0:
            raise ValueError(
                f"sync_interval must be >= 1.0, got {sync_interval}"
            )
        self.sync_interval = float(sync_interval)
        if batches_per_unit is not None:
            if batches_per_unit < 1:
                raise ValueError(
                    f"batches_per_unit must be >= 1, got {batches_per_unit}"
                )
            if not elastic:
                raise ValueError(
                    "batches_per_unit cuts ELASTIC ledger units into "
                    "batch ranges — set elastic=True to use it"
                )
        self.batches_per_unit = batches_per_unit
        self.fault_plan = fault_plan
        self.ps_wal_dir = ps_wal_dir
        self.wal_every = wal_every
        self.ps_recovery_grace = ps_recovery_grace
        # ops_port for any wire PS this fit spawns (0 = free port; read
        # server.ops.port off the elastic chaos handle), plus this
        # worker process's own mountable ops endpoint (mount_ops()).
        self.ps_ops_port = ps_ops_port
        import os

        if ps_shards is None:
            ps_shards = int(os.environ.get("ELEPHAS_PS_SHARDS", "0")) or None
        if standby is None:
            standby = int(os.environ.get("ELEPHAS_PS_STANDBY", "0"))
        if ps_shards is not None:
            if ps_shards < 1:
                raise ValueError(f"ps_shards must be >= 1, got {ps_shards}")
            if parameter_server_mode == "local":
                raise ValueError(
                    "ps_shards requires a wire transport (http|socket): "
                    "shards are separate server processes"
                )
        if standby:
            if not ps_shards:
                raise ValueError(
                    "standby is the shard group's hot-spare tier — set "
                    "ps_shards (ps_shards=1 shards trivially) to use it"
                )
            if ps_wal_dir is None:
                raise ValueError(
                    "standby streams each shard's WAL to its spare — "
                    "set ps_wal_dir"
                )
        self.ps_shards = ps_shards
        self.standby = standby
        self._elastic_group = None
        self.ops = None
        self._ops_history = None
        self._ops_alerts = None
        # Chaos-harness handles, live during an elastic fit: the current
        # server object (tests kill/replace it) and the worker pool
        # (tests join late workers / inspect membership).
        self._elastic_server = None
        self._elastic_pool = None
        # Phase profiling (scripts/flagship_phases.py): when True, the
        # 'epoch'-frequency worker loop and the epoch fire force device
        # results at phase boundaries and append per-phase wall seconds
        # to phase_times. Forcing breaks the dispatch pipeline, so this
        # measures PHASE COSTS, not end-to-end throughput — leave False
        # for real runs.
        self.profile_phases = False
        self.phase_times: Dict[str, List[float]] = {}
        # One worker per device along the data axis. Under multi-host SPMD
        # every process constructs the same global mesh but drives only its
        # *addressable* devices; the partition index stays global so shard g
        # of the dataset is trained by exactly one worker in the job
        # (reference: one RDD partition per executor, SURVEY.md §3.2).
        n_data = mesh.shape[DATA_AXIS]
        data_devices = list(
            np.asarray(mesh.devices).reshape(mesh.devices.shape[0], -1)[:, 0][:n_data]
        )
        pid = jax.process_index()
        self.workers = [
            (g, dev) for g, dev in enumerate(data_devices) if dev.process_index == pid
        ]
        self.devices = [dev for _, dev in self.workers]
        self.n_workers = len(self.workers)  # local worker count
        self.n_global_workers = len(data_devices)
        from elephas_tpu.utils.compiler import tpu_compiler_options

        self.autotune = autotune
        self.autotune_choice = None
        self._train_step = make_train_step(compiled)
        self._subtract = jax.jit(subtract_params)
        self._build_worker_programs(tpu_compiler_options())
        self._local_eval_fn = None  # lazily-jitted single-device evaluator
        # Distinct, collision-free per-worker/per-step dropout streams.
        self._base_rng = jax.random.PRNGKey(977)

    def _build_worker_programs(self, compiler_options) -> None:
        self._epoch_fn = jax.jit(
            make_epoch_scanner(self._train_step),
            compiler_options=compiler_options,
        )
        self._step_fn = jax.jit(
            self._train_step, compiler_options=compiler_options
        )

    def _run_autotune(self, dataset, batch_size: int) -> None:
        """One-shot compile-option A/B on a 2-batch epoch scan of this
        model (worker 0's device, real rows): the same per-batch compute
        both frequencies dispatch, so scan-heavy regressions the knob
        can cause show up before any worker compiles. The winner
        rebuilds the worker programs.

        Multi-host: the A/B program here is LOCAL (one device), but the
        decision must be job-wide — host 0's outcome is broadcast and
        every rank adopts it (``decide_autotune``), so every rank must
        reach this call even if it cannot time anything locally."""
        from elephas_tpu.engine.state import TrainState
        from elephas_tpu.engine.sync import _AUTOTUNE_SKIPPED, decide_autotune
        from elephas_tpu.utils.compiler import autotune_compile_options

        multi_host = jax.process_count() > 1
        if multi_host:
            from elephas_tpu.parallel import distributed

        local = None
        # Unlike the sync A/B (a global SPMD program every rank must run
        # in lockstep), this one is LOCAL to one device — and host 0's
        # table decides for the job, so timing it anywhere else would be
        # two discarded compiles + 50 dispatches per rank per fit.
        times_here = not multi_host or distributed.is_host0()
        if times_here and self.workers:
            g, device = self.workers[0]
            x, y = dataset.partition(g)
            nb = min(2, len(x) // batch_size)
            if nb > 0:
                usable = nb * batch_size
                xs = jax.device_put(
                    np.asarray(x[:usable]).reshape(nb, batch_size, *x.shape[1:]),
                    device,
                )
                ys = jax.device_put(
                    np.asarray(y[:usable]).reshape(nb, batch_size, *y.shape[1:]),
                    device,
                )
                compiled = self.compiled
                state = TrainState.create(
                    params=jax.device_put(compiled.params, device),
                    opt_state=jax.device_put(compiled.init_opt_state(), device),
                    batch_stats=jax.device_put(compiled.batch_stats, device),
                    rng=jax.device_put(jax.random.PRNGKey(0), device),
                )

                def build(opts):
                    return jax.jit(
                        make_epoch_scanner(self._train_step),
                        compiler_options=opts,
                    )

                local = autotune_compile_options(
                    build,
                    lambda fn: fn(state, xs, ys),
                    # a scalar fetch forces every step before it
                    lambda out: float(out[1]["loss"]),
                )
        decided = decide_autotune(local, multi_host)
        if decided is None:
            # Nowhere (that matters) could time: visible, not silent.
            self.autotune_choice = dict(_AUTOTUNE_SKIPPED)
            logger.warning(
                "autotune=True could not time this workload (partition "
                "smaller than 2 batches); compiling with defaults "
                "(compile_autotune='skipped')"
            )
            return
        winner, opts, table = decided
        self.autotune_choice = {"winner": winner, "ms_per_2batch": table}
        if table:  # more than one candidate was actually timed
            self._build_worker_programs(opts)

    def _local_evaluate(
        self, state: TrainState, features, labels, batch_size: int = 2048
    ) -> Dict[str, float]:
        """Single-device exact weighted-mean evaluation — used where a
        global-mesh SPMD evaluate can't run (host-0 epoch barriers in
        multi-host async are local, so a collective would desync peers)."""
        if self._local_eval_fn is None:
            from elephas_tpu.engine.step import DeviceEvalCache, make_eval_step

            from elephas_tpu.utils.compiler import tpu_compiler_options

            self._local_eval_fn = jax.jit(
                make_eval_step(self.compiled),
                compiler_options=tpu_compiler_options(),
            )
            self._val_cache = DeviceEvalCache()
        from elephas_tpu.engine.step import weighted_mean_over_chunks

        # The validation set is constant across a fit's epoch fires:
        # sets within the cache bound are uploaded ONCE and sliced on
        # device (a ~100MB host->device re-upload per epoch is still
        # worth avoiding on an attached chip); larger sets stream per
        # chunk.
        features = np.asarray(features)
        labels = np.asarray(labels)
        cached = self._val_cache.get(
            (features, labels),
            features.nbytes + labels.nbytes,
            lambda: (jnp.asarray(features), jnp.asarray(labels)),
        )

        n = len(features)
        usable = (n // batch_size) * batch_size
        spans = [(s, s + batch_size) for s in range(0, usable, batch_size)]
        if usable < n:
            spans.append((usable, n))

        # Dispatch chunks, then ONE device_get for all their metric
        # dicts: a fetch per chunk is a host sync stall each, which made
        # the overlapped epoch fire bound by round-trips, not by eval.
        # UNCACHED sets (> the cache byte bound) must still stream: the
        # trailing fetch keeps at most ~2 chunk uploads in flight so a
        # huge validation set never sits fully device-resident.
        device_metrics = []
        for idx, (start, stop) in enumerate(spans):
            if cached is not None:
                x, y = cached[0][start:stop], cached[1][start:stop]
            else:
                x, y = jnp.asarray(features[start:stop]), jnp.asarray(labels[start:stop])
            device_metrics.append(self._local_eval_fn(state, x, y))
            if cached is None and idx >= 1:
                device_metrics[idx - 1] = jax.device_get(device_metrics[idx - 1])
        fetched = jax.device_get(device_metrics)
        return weighted_mean_over_chunks(
            [(s, e, i) for i, (s, e) in enumerate(spans)],
            lambda start, stop, i: fetched[i],
            n,
        )

    # -------------------------------------------------------------------------

    def mount_ops(self, port: int = 0, host: Optional[str] = None,
                  store_dir: Optional[str] = None):
        """Mount a live introspection endpoint for THIS worker process
        (role ``worker``): ``/metrics`` serves the process registry the
        training loop already feeds, ``/history`` its sampled rings,
        ``/profile`` device capture + memory watermarks. A fleet
        aggregator polls this next to the PS's own endpoint so trainer
        and server sides of an outage are visible together. Loopback by
        default; idempotent; ``unmount_ops()`` tears it down.
        ``store_dir`` additionally journals this worker's flight notes,
        alert transitions, and sampler ticks into a durable telemetry
        store (``obs.store``) for post-mortem reconstruction."""
        if self.ops is not None:
            return self.ops
        from elephas_tpu import obs
        from elephas_tpu.obs.devprof import record_device_memory
        from elephas_tpu.obs.opsd import OpsServer

        try:
            worker_id = f"w{jax.process_index()}"
        except Exception:
            worker_id = "w0"
        self._ops_history = obs.HistorySampler(
            extra_fn=record_device_memory).start()
        self._ops_alerts = obs.AlertEngine()
        self.store = None
        if store_dir is not None:
            self.store = obs.TelemetryStore(
                store_dir, role="worker",
                flight=obs.default_flight_recorder())
            obs.default_flight_recorder().attach_store(self.store)
            self._ops_alerts.attach_store(self.store)
            self._ops_history.attach_store(self.store)
        self.ops = OpsServer(
            port=port, host=host, role="worker", worker_id=worker_id,
            alerts_fn=self._ops_alerts.scrape,
            history=self._ops_history,
            vars_fn=lambda: {
                "role": "worker",
                "worker_id": worker_id,
                "parameter_server_mode": self.parameter_server_mode,
                "frequency": self.frequency,
                "elastic": self.elastic,
            },
            incidents_fn=(self.store.doc if self.store is not None
                          else None),
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
            alerts = getattr(self, "_ops_alerts", None)
            if alerts is not None:
                alerts.detach_store(store)
            store.close()
            self.store = None

    def _build_ps_group(self, store0, auth_key):
        """Start the K-shard PS group (plus its standby tier and
        failure monitor) this fit's workers will scatter/gather
        against. Exposed on ``self._elastic_group`` for chaos tests."""
        from elephas_tpu.parameter.group import ShardGroup

        group = ShardGroup(
            store0,
            self.ps_shards,
            mode=self.parameter_server_mode,
            standby=self.standby,
            wal_root=self.ps_wal_dir,
            lock=self.lock,
            device=jax.local_devices()[0],
            granularity=self.granularity,
            auth_key=auth_key,
            wal_every=self.wal_every,
            ops_port=self.ps_ops_port,
        )
        group.start()
        if self.standby:
            group.start_monitor()
        self._elastic_group = group
        return group

    def fit(
        self,
        dataset,
        epochs: int = 10,
        batch_size: int = 32,
        validation_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        verbose: int = 0,
        rng: Optional[jax.Array] = None,
        callbacks=(),
        initial_step: int = 0,
    ) -> Tuple[TrainState, Dict[str, List[float]]]:
        """``initial_step``: step of a restored checkpoint this fit resumes
        from — epoch snapshot steps continue from it, so rotating
        checkpointers (which no-op on an already-saved step) keep saving
        after a resume."""
        compiled = self.compiled
        # True training cadence: `time.monotonic` stamp when the SLOWEST
        # worker finishes each epoch, with or without callbacks (the fire
        # timestamps lag by the in-flight fire, so throughput harnesses
        # should read these). The elastic ledger has no epoch barrier
        # and leaves the list empty.
        self.epoch_end_times: List[float] = []
        if self.elastic:
            return self._fit_elastic(
                dataset, epochs, batch_size, validation_data, verbose,
                rng, callbacks, initial_step,
            )
        if self.autotune and self.autotune_choice is None:
            # No `self.workers` gate: multi-host, the decision broadcast
            # inside is a collective every rank must reach.
            self._run_autotune(dataset, batch_size)
        store0 = {"params": compiled.params, "batch_stats": compiled.batch_stats}
        multi_host = jax.process_count() > 1
        if multi_host and self.parameter_server_mode == "local":
            raise ValueError(
                "multi-host async/hogwild needs parameter_server_mode='http' "
                "or 'socket' — the in-process buffer spans one host"
            )
        if multi_host and self.ps_shards:
            raise ValueError(
                "ps_shards is single-host for now: the shard directory "
                "lives in the driver process and multi-host fits "
                "broadcast one PS address"
            )

        # Reference topology (SURVEY.md §3.2): ONE parameter server on the
        # driver (host 0); every worker on every host dials it. Host 0
        # binds all interfaces (cross-host must be reachable), broadcasts
        # its routable address over the DCN control plane, and the
        # broadcast doubles as the "server is up" barrier.
        server = None
        remote_client_factory = None
        if not multi_host:
            import os

            # Single-host default is loopback + no auth, but a user who
            # binds beyond loopback (ELEPHAS_PS_BIND) and configures a
            # key must get an AUTHENTICATED server — silently ignoring
            # the key would leave an open pickle endpoint.
            env_key = os.environ.get("ELEPHAS_PS_AUTH_KEY")
            if self.ps_shards:
                # ShardGroup quacks like a server here: start/stop/
                # client()/get_parameters() — each worker's client()
                # scatters/gathers across the K shard processes.
                server = self._build_ps_group(
                    store0, bytes.fromhex(env_key) if env_key else None)
            else:
                server = make_server(
                    self.parameter_server_mode,
                    store0,
                    lock=self.lock,
                    port=self.port,
                    device=jax.local_devices()[0],
                    granularity=self.granularity,
                    auth_key=bytes.fromhex(env_key) if env_key else None,
                    wal_dir=self.ps_wal_dir,
                    wal_every=self.wal_every,
                    ops_port=self.ps_ops_port,
                )
                server.start()
        else:
            import os

            from elephas_tpu.parallel import distributed
            from elephas_tpu.parameter.client import make_client
            from elephas_tpu.utils.sockets import determine_master

            # Wire auth, ON by default across hosts: the PS binds beyond
            # loopback and speaks pickle, so every http/socket message
            # carries an HMAC-SHA256 tag under a per-fit secret that host
            # 0 generates and broadcasts over the DCN control plane (the
            # same trusted channel that carries the PS address). Override
            # the key with $ELEPHAS_PS_AUTH_KEY (hex) for an external PS;
            # opt out with ELEPHAS_PS_AUTH=off.
            auth_key = None
            auth_on = os.environ.get("ELEPHAS_PS_AUTH", "on").lower() not in (
                "off", "0", "false",
            )
            if auth_on and distributed.is_host0():
                env_key = os.environ.get("ELEPHAS_PS_AUTH_KEY")
                auth_key = bytes.fromhex(env_key) if env_key else os.urandom(32)

            if distributed.is_host0():
                server = make_server(
                    self.parameter_server_mode,
                    store0,
                    lock=self.lock,
                    port=self.port,
                    device=jax.local_devices()[0],
                    host=os.environ.get("ELEPHAS_PS_BIND", "0.0.0.0"),
                    granularity=self.granularity,
                    auth_key=auth_key,
                    wal_dir=self.ps_wal_dir,
                    wal_every=self.wal_every,
                    ops_port=self.ps_ops_port,
                )
                server.start()
            if server is not None:
                # Advertise what peers can actually dial: a pinned bind
                # interface verbatim; for wildcard binds, this host's
                # routable IP.
                if server.host not in ("0.0.0.0", "::", ""):
                    advertised = f"{server.host}:{server.port}"
                else:
                    advertised = determine_master(server.port)
            else:
                advertised = ""
            address = os.environ.get(
                "ELEPHAS_PS_ADDRESS"
            ) or distributed.broadcast_from_host0(advertised)
            if auth_on:
                auth_key = (
                    distributed.broadcast_bytes_from_host0(auth_key or b"") or None
                )
            remote_client_factory = lambda: make_client(  # noqa: E731
                self.parameter_server_mode, address, auth_key=auth_key
            )

        per_worker_metrics: List[List[Dict[str, float]]] = [None] * self.n_workers
        errors: List[BaseException] = []
        # Epoch-barrier bookkeeping: once the *slowest* worker has finished
        # epoch e (workers never block on each other — the barrier is
        # observational only), fire callbacks and evaluate validation on a
        # snapshot of the server's current weights, so val_* history has one
        # entry per epoch like SyncTrainer's.
        #
        # Multi-host: barrier work runs on HOST 0 ONLY — its barrier is
        # local, so the snapshot samples whatever global progress the PS
        # holds when host 0's workers finish epoch e (honest per-epoch
        # sampling; exact global barriers would reintroduce the lockstep
        # async mode exists to avoid). State-persisting callbacks
        # (checkpointing) are therefore host-0-only under async multi-host:
        # Orbax saves are collective when jax.distributed is live, and
        # unsynchronized per-host fires would deadlock or collide.
        is_driver = not multi_host or jax.process_index() == 0
        if multi_host:
            # Fail fast on a guaranteed deadlock: a COLLECTIVE Orbax
            # manager saves via a global barrier, but only host 0 fires
            # callbacks here — host 0 would block forever waiting for
            # peers that never enter save.
            from elephas_tpu.checkpoint.checkpoint import _CheckpointCallback

            for cb in callbacks:
                if isinstance(cb, _CheckpointCallback) and not cb._manager.host0_only:
                    raise ValueError(
                        "multi-host async/hogwild checkpointing needs "
                        "CheckpointManager(host0_only=True): epoch barriers "
                        "are host-local, so collective saves deadlock"
                    )
        run_callbacks = tuple(callbacks) if is_driver else ()
        do_val = validation_data is not None and is_driver
        epoch_done_counts = [0] * epochs
        epochs_fired = 0
        fire_cond = threading.Condition()
        fire_queue: deque = deque()
        fire_stop = [False]
        fire_errors: List[BaseException] = []
        saturated_warned = [False]
        val_records: List[Optional[Dict[str, float]]] = [None] * epochs

        def pull_snapshot():
            if server is not None:
                # Device arrays, NOT device_get: the snapshot feeds
                # validation (device-side) and Orbax (which copies device
                # buffers itself) — a host round-trip of the full model
                # per epoch is a transfer of every weight both ways.
                return server.get_parameters()
            return remote_client_factory().get_parameters()

        snap_opt_state = [None]  # built once; identical zeros every fire

        mark_phase = self._mark_phase

        def do_fire(fire: int, snapshot=None) -> None:
            t0 = time.perf_counter()
            stale = snapshot is None
            if stale:
                # The drainer fell behind and this epoch's boundary
                # snapshot was never pinned: validation/callbacks see the
                # PS as of NOW, not as of the epoch boundary.
                snapshot = pull_snapshot()
            mark_phase("fire_snapshot", t0, snapshot["params"])
            if snap_opt_state[0] is None:
                snap_opt_state[0] = compiled.init_opt_state(snapshot["params"])
            # step must advance per epoch or rotating checkpointers
            # (keyed on state.step) silently drop every save after the
            # first — Orbax no-ops on an already-saved step.
            snap_state = TrainState.create(
                params=snapshot["params"],
                opt_state=snap_opt_state[0],
                batch_stats=snapshot["batch_stats"],
                step=initial_step + fire + 1,
            )
            if do_val:
                # Single-device eval on the buffer device in BOTH
                # topologies: multi-host because the barrier is host-local
                # (a global-mesh collective would desync peers), and
                # single-host because the snapshot's arrays are committed
                # to the PS device — feeding them to the SPMD evaluator
                # would mix committed placements and fail under jit.
                t0 = time.perf_counter()
                rec = dict(self._local_evaluate(snap_state, *validation_data))
                # Honest metrics (SURVEY.md §5.5): a user must be able to
                # tell from history whether this epoch's val row sampled
                # the epoch boundary or a later (stale-fire) PS state.
                rec["stale"] = 1.0 if stale else 0.0
                val_records[fire] = rec
                mark_phase("fire_val", t0)
            t0 = time.perf_counter()
            for cb in run_callbacks:
                cb(fire, snap_state, {})
            mark_phase("fire_callbacks", t0)

        def on_epoch_done(epoch: int) -> None:
            nonlocal epochs_fired
            barrier_work = bool(run_callbacks) or do_val
            if barrier_work and fire_errors:
                # Surface a failed fire (checkpoint/eval) at the next
                # epoch boundary instead of training to completion first.
                raise RuntimeError(
                    "epoch-barrier work failed; aborting fit"
                ) from fire_errors[0]
            with fire_cond:
                epoch_done_counts[epoch] += 1
                while (
                    epochs_fired < epochs
                    and epoch_done_counts[epochs_fired] == self.n_workers
                ):
                    # The stamp needs no callback: count the barrier and
                    # stamp first; without barrier work nothing else
                    # happens here (no snapshot pulled, no fire queued).
                    self.epoch_end_times.append(time.monotonic())
                    if not barrier_work:
                        epochs_fired += 1
                        continue
                    # Snapshot AT THE EPOCH BOUNDARY (a device-to-device
                    # copy, ~10ms) so per-epoch validation samples the PS
                    # as of this epoch even though the eval itself runs
                    # later in the drainer. If the drainer falls behind
                    # (slow user callback), stop pinning snapshots and
                    # let those fires pull at fire time — bounded HBM
                    # over honesty in the already-degenerate case. The
                    # degradation is SURFACED: warn once, and each
                    # affected epoch's val row carries val_stale=1.
                    saturated = len(fire_queue) >= 3
                    if saturated and not saturated_warned[0]:
                        saturated_warned[0] = True
                        logger.warning(
                            "epoch-fire queue saturated at epoch %d (slow "
                            "callback/validation?): snapshots are no longer "
                            "pinned at epoch boundaries — affected epochs' "
                            "validations sample a LATER parameter-server "
                            "state and are marked val_stale=1 in history",
                            epochs_fired,
                        )
                    snapshot = None if saturated else pull_snapshot()
                    fire_queue.append((epochs_fired, snapshot))
                    epochs_fired += 1
                fire_cond.notify_all()

        def fire_drainer() -> None:
            # Dedicated serial-FIFO consumer: at most one epoch's barrier
            # work runs at a time, in epoch order — concurrent fires raced
            # evaluator creation and Orbax saves are not thread-safe
            # (advisor r2). Running it OFF the worker threads means an
            # in-flight fire (snapshot + validation + checkpoint) overlaps
            # the next epoch's training instead of blocking a worker's
            # dispatch between epochs — measured 23.6k -> ~30k samples/sec
            # steady on the flagship hogwild CIFAR config (PROFILE.md §5:
            # the fire was the dominant per-epoch overhead phase).
            while True:
                with fire_cond:
                    while not fire_queue and not fire_stop[0]:
                        fire_cond.wait()
                    if not fire_queue:
                        return  # stopped and drained
                    fire, snapshot = fire_queue.popleft()
                try:
                    do_fire(fire, snapshot)
                except BaseException as exc:  # checked at epoch boundaries
                    fire_errors.append(exc)
                    return

        drainer = None
        if run_callbacks or do_val:
            if do_val:
                # Pre-compile the epoch evaluator (and upload the val set
                # to its device cache) BEFORE training starts: the first
                # fire otherwise stalls the drainer for the eval jit
                # (~20s on this chip), queueing epochs' fires — pinned
                # snapshots and a burst of stale validations.
                warm = pull_snapshot()
                # Seed the fires' shared opt_state here (they'd build the
                # identical zeros on first fire anyway) and drop the warm
                # snapshot right after — holding it in fit()'s locals
                # would pin a model-sized copy in HBM for the whole run.
                snap_opt_state[0] = compiled.init_opt_state(warm["params"])
                self._local_evaluate(
                    TrainState.create(
                        params=warm["params"],
                        opt_state=snap_opt_state[0],
                        batch_stats=warm["batch_stats"],
                        step=0,
                    ),
                    *validation_data,
                )
                del warm
            drainer = threading.Thread(target=fire_drainer, daemon=True)
            drainer.start()

        def stop_drainer() -> None:
            if drainer is None:
                return
            with fire_cond:
                fire_stop[0] = True
                fire_cond.notify_all()
            drainer.join()

        def worker(slot: int, global_index: int, device: jax.Device) -> None:
            try:
                client = (
                    server.client()
                    if server is not None
                    else remote_client_factory()
                )
                if hasattr(client, "worker_id"):
                    # Wire clients stamp pushes with the worker id so
                    # the PS staleness ledger can attribute lag; the
                    # in-process client has no wire frame to stamp.
                    client.worker_id = f"w{global_index}"
                per_worker_metrics[slot] = self._run_worker(
                    global_index, device, client, dataset, epochs, batch_size,
                    on_epoch_done=on_epoch_done,
                )
            except BaseException as exc:  # surfaced after join
                errors.append(exc)

        threads = [
            threading.Thread(target=worker, args=(slot, g, dev), daemon=True)
            for slot, (g, dev) in enumerate(self.workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop_drainer()  # drains any queued fires, then returns

        if errors or fire_errors:
            # Multi-host: raising here (instead of entering the global
            # barrier) fails this process fast; peers' barriers abort via
            # the launcher's job-level restart (SURVEY.md §5.3 delegation).
            # A failed fire outranks the derived worker abort it caused.
            if server is not None:
                server.stop()
            raise (fire_errors or errors)[0]

        if multi_host:
            # PS-backed host barriers (not device collectives): async hosts
            # can drift by minutes, far past collective-rendezvous deadlines.
            # A dead peer surfaces as wait_barrier's TimeoutError (bounded
            # by $ELEPHAS_BARRIER_TIMEOUT); the finally stops the PS so a
            # failed teardown never leaks the server thread.
            ctl = None
            try:
                n_hosts = jax.process_count()
                ctl = server.client() if server is not None else remote_client_factory()
                ctl.wait_barrier("elephas:pushes_done", n_hosts)
                final = pull_snapshot()
                if server is not None:
                    # Host 0 keeps the PS alive until every peer has announced
                    # its final read, then tears it down.
                    ctl.wait_barrier("elephas:final_read", n_hosts)
                else:
                    # Peers only announce — waiting here would race the
                    # server shutdown (host 0 stops the PS once the count
                    # completes, possibly mid-poll).
                    ctl.barrier_arrive("elephas:final_read")
            finally:
                if ctl is not None and hasattr(ctl, "close"):
                    ctl.close()
                if server is not None:
                    server.stop()
        else:
            final = jax.device_get(server.get_parameters())
            server.stop()
            self._elastic_group = None

        # Master state from the server's final weights; metrics averaged
        # across workers per epoch.
        state = TrainState.create(
            params=final["params"],
            opt_state=compiled.init_opt_state(final["params"]),
            batch_stats=final["batch_stats"],
            rng=rng if rng is not None else jax.random.PRNGKey(0),
            step=initial_step + epochs,
        )
        # Train-metric history: mean over ALL workers job-wide. Multi-host:
        # allgather each host's per-epoch means weighted by its local worker
        # count, so every host reports the identical history a single-host
        # run of the same job would (hosts are already re-synchronized by
        # the PS teardown barriers above, so the collective is safe).
        worker_histories = [m for m in per_worker_metrics if m is not None]
        keys = sorted(worker_histories[0][0].keys())
        local_means = np.array(
            [[np.mean([m[e][k] for m in worker_histories]) for k in keys]
             for e in range(epochs)],
            dtype=np.float64,
        )  # (epochs, nkeys)
        if multi_host:
            from jax.experimental import multihost_utils

            counts = np.asarray(
                multihost_utils.process_allgather(
                    np.array([len(worker_histories)], dtype=np.float64)
                )
            ).reshape(-1)  # (nhosts,)
            all_means = np.asarray(
                multihost_utils.process_allgather(local_means)
            ).reshape(-1, epochs, len(keys))
            local_means = (
                all_means * counts[:, None, None]
            ).sum(axis=0) / counts.sum()
        history: Dict[str, List[float]] = {
            k: [float(local_means[e, i]) for e in range(epochs)]
            for i, k in enumerate(keys)
        }
        # Retry bookkeeping rides the metric aggregation as a per-worker
        # mean; surface it as the job-wide COUNT per epoch (mean × global
        # worker count — exact because the multi-host gather weights by
        # worker count).
        if "_retries" in history:
            total_workers = float(
                counts.sum() if multi_host else len(worker_histories)
            )
            history["worker_retries"] = [
                int(round(v * total_workers)) for v in history.pop("_retries")
            ]
        def fill_val_gaps(records):
            """Defensive: every barrier fires when no worker errored, but a
            None entry must not ship — evaluate the final state ONCE.
            Single-device eval: multi-host, this runs on host 0 while
            peers are already parked in the broadcast collective, so an
            SPMD evaluate here would desync the job."""
            fallback = None
            for epoch, val in enumerate(records):
                if val is None:
                    if fallback is None:
                        fallback = dict(
                            self._local_evaluate(state, *validation_data)
                        )
                        fallback["stale"] = 1.0  # final state, not the epoch's
                    records[epoch] = fallback
            return records

        if multi_host:
            # EVERY host must reach this collective regardless of its own
            # validation_data — gating it locally would deadlock host 0
            # (the only evaluator) against peers launched without val
            # data. Host 0 decides whether val history exists; peers
            # receive the records verbatim, so val_* history is identical
            # job-wide (one PS-snapshot eval per epoch, like single-host).
            import json as _json

            from elephas_tpu.parallel import distributed

            if distributed.is_host0() and validation_data is not None:
                payload = _json.dumps(fill_val_gaps(val_records)).encode()
            else:
                payload = b"null"
            shipped = _json.loads(
                distributed.broadcast_bytes_from_host0(payload).decode()
            )
            if shipped is not None:
                for val in shipped:
                    for k, v in val.items():
                        history.setdefault(f"val_{k}", []).append(v)
        elif validation_data is not None:
            for val in fill_val_gaps(val_records):
                for k, v in val.items():
                    history.setdefault(f"val_{k}", []).append(v)
        if verbose:
            last = {k: round(v[-1], 4) for k, v in history.items()}
            print(f"[{'async' if self.lock else 'hogwild'}] done: {last}")
        return state, history

    # -------------------------------------------------------------------------

    def _fit_elastic(
        self,
        dataset,
        epochs: int,
        batch_size: int,
        validation_data,
        verbose: int,
        rng,
        callbacks,
        initial_step: int,
    ) -> Tuple[TrainState, Dict[str, List[float]]]:
        """Elastic fit: the ledger/pool replaces the fixed worker loop.

        Every ``(epoch, partition)`` unit — or, with
        ``batches_per_unit`` set, every ``(epoch, partition, (lo, hi))``
        batch range — is leased from a
        ``resilience.UnitLedger`` to whichever worker thread is alive;
        dead workers' in-flight units are re-queued to survivors, the
        per-epoch fire runs when the LEDGER says the epoch is complete
        (not when a fixed set of threads report in), and a PS crash is
        ridden out against a warm restart on the same address. Unit
        determinism is keyed on ``(partition, epoch)`` — NOT the worker —
        so a re-run of a re-queued unit trains the identical shuffle and
        dropout streams the dead worker would have.

        Chaos harness surface: ``self._elastic_server`` (kill it, warm
        restart on the same port + WAL dir, reassign the handle) and
        ``self._elastic_pool`` (``join_worker`` for late joins,
        ``membership`` for the published liveness table).
        """
        import os

        from elephas_tpu.parameter.client import make_client
        from elephas_tpu.parameter.server import _dial_host
        from elephas_tpu.resilience import (
            ElasticWorkerPool,
            FaultInjector,
            UnitLedger,
            install,
        )

        compiled = self.compiled
        if jax.process_count() > 1:
            raise ValueError(
                "elastic fit is single-host for now: one process leases "
                "units for all of its chips (multi-host elasticity needs "
                "a cross-host ledger)"
            )
        store0 = {"params": compiled.params, "batch_stats": compiled.batch_stats}
        env_key = os.environ.get("ELEPHAS_PS_AUTH_KEY")
        auth_key = bytes.fromhex(env_key) if env_key else None
        if self.ps_shards:
            server = self._build_ps_group(store0, auth_key)
        else:
            server = make_server(
                self.parameter_server_mode,
                store0,
                lock=self.lock,
                port=self.port,
                device=jax.local_devices()[0],
                granularity=self.granularity,
                auth_key=auth_key,
                wal_dir=self.ps_wal_dir,
                wal_every=self.wal_every,
                ops_port=self.ps_ops_port,
            )
            server.start()
        self._elastic_server = server

        mode = self.parameter_server_mode
        if self.ps_shards:
            def client_factory(worker_id):
                # The group directory (not a fixed address) is the
                # re-resolution point: after a shard failover the
                # generation bump re-dials the promoted primary.
                client = self._elastic_group.client()
                client.worker_id = str(worker_id)
                return client
        elif mode == "local":
            def client_factory(worker_id):
                # In-process: a PS "restart" is impossible (the buffer
                # dies with this process), so always the live handle.
                return self._elastic_server.client()
        else:
            # Dial the ADDRESS, not the server object: after a kill +
            # warm restart a NEW server owns the same port, and fresh
            # clients must reach it for recovery to complete.
            address = f"{_dial_host(server.host)}:{server.port}"

            def client_factory(worker_id):
                client = make_client(mode, address, auth_key=auth_key)
                # Stamp the wire identity: pushes then carry the
                # worker id + trained-against version, which is what
                # the PS staleness ledger keys its rows on.
                client.worker_id = str(worker_id)
                return client

        injector = None
        if self.fault_plan is not None:
            injector = FaultInjector(self.fault_plan)
            install(injector)
        self._fault_injector = injector

        partitions = list(range(self.n_global_workers))
        worker_ids = [f"w{slot}" for slot in range(self.n_workers)]
        devices = self.devices

        def device_for(worker_id: str) -> jax.Device:
            # Late joiners ("w<k>" beyond the initial pool, or any name)
            # share the chip ring round-robin.
            try:
                i = int(str(worker_id).lstrip("w"))
            except ValueError:
                i = abs(hash(worker_id))
            return devices[i % len(devices)]

        data_lock = threading.Lock()
        host_rows: Dict[int, tuple] = {}       # partition -> (x, y, nb, usable)
        device_rows: Dict[tuple, tuple] = {}   # (worker, partition) -> arrays
        opt_states: Dict[str, object] = {}     # worker -> local optimizer state

        def partition_rows(part: int):
            with data_lock:
                if part not in host_rows:
                    x, y = dataset.partition(part)
                    nb = len(x) // batch_size
                    if nb == 0:
                        raise ValueError(
                            f"partition {part}: {len(x)} rows < "
                            f"batch_size {batch_size}"
                        )
                    usable = nb * batch_size
                    host_rows[part] = (
                        np.asarray(x[:usable]), np.asarray(y[:usable]),
                        nb, usable,
                    )
                return host_rows[part]

        if self.batches_per_unit is not None:
            # Batch-range units need every partition's batch count up
            # front (the driver holds the dataset in-process here, so
            # this just moves the lazy load earlier).
            ledger = UnitLedger(
                epochs, partitions,
                n_batches={p: partition_rows(p)[2] for p in partitions},
                batches_per_unit=self.batches_per_unit,
            )
        else:
            ledger = UnitLedger(epochs, partitions)

        def run_unit(worker_id: str, client, unit):
            # Each ledger unit roots its own trace: the
            # pull→train→push→PS-apply chain below — including a push
            # retried against a warm-restarted server — is one causal
            # tree (PS-side spans carry the boot id of the incarnation
            # that served them).
            epoch, part = unit[0], unit[1]
            span_args = {}
            if len(unit) > 2:
                span_args["batches"] = f"{unit[2][0]}:{unit[2][1]}"
            tracer = obs.default_tracer()
            ctx = obs.new_context() if tracer.enabled else None
            with obs.activate(ctx), tracer.span(
                    "async/unit", epoch=epoch, partition=part,
                    worker=worker_id, **span_args) as usp:
                return unit_body(worker_id, client, unit, usp)

        def unit_body(worker_id: str, client, unit, usp=None):
            epoch, part = unit[0], unit[1]
            batch_range = unit[2] if len(unit) > 2 else None
            device = device_for(worker_id)
            x, y, nb, usable = partition_rows(part)
            cache_key = (worker_id, part)
            if cache_key not in device_rows:
                device_rows[cache_key] = (
                    jax.device_put(x, device), jax.device_put(y, device)
                )
            x_d, y_d = device_rows[cache_key]
            # Unit-keyed determinism: shuffle and dropout depend only on
            # (partition, epoch), so a survivor re-running a dead
            # worker's unit reproduces it exactly.
            perm = np.random.default_rng([1234, part, epoch]).permutation(usable)
            perm_d = jax.device_put(perm, device)
            ex = jnp.take(x_d, perm_d, axis=0).reshape(
                nb, batch_size, *x_d.shape[1:]
            )
            ey = jnp.take(y_d, perm_d, axis=0).reshape(
                nb, batch_size, *y_d.shape[1:]
            )
            # Batch-range unit: train only batches [lo, hi) of the
            # SHARED (partition, epoch)-keyed shuffle, so the ranges of
            # one epoch partition the identical batch stream a
            # whole-partition unit would have trained — a survivor
            # re-running a dead worker's range reproduces it exactly.
            lo, hi = (0, nb) if batch_range is None else batch_range
            if batch_range is not None:
                ex, ey = ex[lo:hi], ey[lo:hi]
            pulled = client.get_parameters()
            params = jax.device_put(pulled["params"], device)
            batch_stats = jax.device_put(pulled["batch_stats"], device)
            opt_state = opt_states.get(worker_id)
            if opt_state is None:
                opt_state = jax.device_put(
                    compiled.init_opt_state(params), device
                )
            unit_rng = jax.random.fold_in(
                jax.random.fold_in(self._base_rng, part), epoch
            )
            if batch_range is not None:
                # Distinct dropout stream per range (keyed on the range
                # start, so it too is worker-independent).
                unit_rng = jax.random.fold_in(unit_rng, lo)
            state0 = TrainState.create(
                params=params,
                opt_state=opt_state,
                batch_stats=batch_stats,
                rng=jax.device_put(unit_rng, device),
                step=epoch * nb + lo,
            )
            with obs.default_tracer().span("async/train", worker=worker_id,
                                           epoch=epoch):
                new_state, metrics = self._epoch_fn(state0, ex, ey)
                # Force the scan BEFORE pushing — a device fault must
                # kill this unit (re-queued by the pool), never poison
                # the buffer.
                fetched = {
                    k: float(v) for k, v in jax.device_get(metrics).items()
                }
            delta_params = self._subtract(state0.params, new_state.params)
            try:
                client.update_parameters({
                    "params": delta_params,
                    "batch_stats": self._subtract(
                        state0.batch_stats, new_state.batch_stats
                    ),
                })
            except StaleDeltaRejected as exc:
                # The admission policy's definitive answer, NOT a worker
                # fault: re-running this unit would train the identical
                # batches against an even older base and push an even
                # staler delta. Drop the delta, count the unit done —
                # the next unit's pull refreshes this worker's base,
                # which is exactly the re-pull the rejection demands.
                if usp is not None:
                    usp.note(admission="reject", lag=exc.lag)
            opt_states[worker_id] = new_state.opt_state
            # Unit dynamics: the scan is already forced (metrics fetch
            # above), so these host norms add one small transfer, not a
            # pipeline stall. ``pulled`` is the host tree the unit
            # trained FROM — the right denominator for effective step.
            obs.record_unit_dynamics(
                obs.default_registry(), worker_id,
                loss=fetched.get("loss"),
                delta_norm=obs.tree_norm(jax.device_get(delta_params)),
                param_norm=obs.tree_norm(pulled["params"]),
                span=usp,
            )
            return fetched

        val_records: List[Optional[Dict[str, float]]] = [None] * epochs
        snap_opt_state = [None]
        run_callbacks = tuple(callbacks)
        do_val = validation_data is not None

        def on_epoch_complete(epoch: int) -> None:
            if not run_callbacks and not do_val:
                return
            # Fresh client per fire: the server object may have been
            # killed and warm-restarted since the last epoch.
            fire_client = client_factory("fire")
            try:
                snapshot = fire_client.get_parameters()
            finally:
                fire_client.close()
            if snap_opt_state[0] is None:
                snap_opt_state[0] = compiled.init_opt_state(snapshot["params"])
            snap_state = TrainState.create(
                params=snapshot["params"],
                opt_state=snap_opt_state[0],
                batch_stats=snapshot["batch_stats"],
                step=initial_step + epoch + 1,
            )
            if do_val:
                val_records[epoch] = dict(
                    self._local_evaluate(snap_state, *validation_data)
                )
            for cb in run_callbacks:
                cb(epoch, snap_state, {})

        pool = ElasticWorkerPool(
            ledger,
            run_unit,
            client_factory,
            worker_ids,
            on_epoch_complete=on_epoch_complete,
            injector=injector,
            ps_recovery_grace=self.ps_recovery_grace,
        )
        self._elastic_pool = pool
        pool.start()
        try:
            stats = pool.wait()
            # Final weights through the ADDRESS (the original server
            # handle may be a corpse the chaos harness replaced). Rides
            # an in-flight warm restart under the same grace budget the
            # workers get: a fast fit can drain the ledger BEFORE a
            # chaos kill lands, leaving this pull — the last wire op of
            # the fit — to face the outage alone with only the client's
            # ~3 s connect-retry budget.
            deadline = time.monotonic() + self.ps_recovery_grace
            while True:
                final_client = client_factory("final")
                try:
                    final = jax.device_get(final_client.get_parameters())
                    break
                except ParameterServerUnavailable:
                    if time.monotonic() >= deadline:
                        raise
                    time.sleep(0.1)
                finally:
                    final_client.close()
        finally:
            if injector is not None:
                install(None)
            self._elastic_pool = None
            live = self._elastic_server
            self._elastic_server = None
            self._elastic_group = None
            if live is not None:
                try:
                    live.stop()  # a ShardGroup handle stops every member
                except Exception:
                    pass

        self.elastic_stats = stats
        em = pool.epoch_metrics()
        keys = sorted(next(iter(em[0].values())).keys())
        history: Dict[str, List[float]] = {
            k: [
                float(np.mean([em[e][p][k] for p in sorted(em[e])]))
                for e in range(epochs)
            ]
            for k in keys
        }
        if do_val:
            for epoch, val in enumerate(val_records):
                if val is None:  # defensive; every epoch completion fires
                    val = val_records[epoch] = dict(
                        self._local_evaluate(
                            TrainState.create(
                                params=final["params"],
                                opt_state=compiled.init_opt_state(
                                    final["params"]
                                ),
                                batch_stats=final["batch_stats"],
                                step=initial_step + epochs,
                            ),
                            *validation_data,
                        )
                    )
                for k, v in val.items():
                    history.setdefault(f"val_{k}", []).append(v)
        state = TrainState.create(
            params=final["params"],
            opt_state=compiled.init_opt_state(final["params"]),
            batch_stats=final["batch_stats"],
            rng=rng if rng is not None else jax.random.PRNGKey(0),
            step=initial_step + epochs,
        )
        if verbose:
            last = {k: round(v[-1], 4) for k, v in history.items()}
            print(
                f"[elastic] done: {last} "
                f"(requeued={stats['requeued_units']}, "
                f"deaths={len(stats['worker_deaths'])}, "
                f"late_joins={len(stats['late_joins'])})"
            )
        return state, history

    # -------------------------------------------------------------------------

    def _mark_phase(self, phase: str, t0: float, *force) -> None:
        """Profiling hook: record wall seconds for one phase, forcing the
        given device values first so async dispatch can't hide the cost.
        Forcing is ONE scalar fetch of a jitted first-element reduction
        over all leaves: the scalar depends on every leaf, and a fetch
        per leaf would bill ~60 host<->device round-trips to the phase.
        No-op unless ``profile_phases``."""
        if not self.profile_phases:
            return
        for obj in force:
            leaves = tuple(
                leaf
                for leaf in jax.tree_util.tree_leaves(obj)
                if hasattr(leaf, "ndim") and getattr(leaf, "size", 0)
            )
            if leaves:
                jax.device_get(_probe_sum(leaves))
        self.phase_times.setdefault(phase, []).append(time.perf_counter() - t0)

    def _run_worker(
        self,
        index: int,
        device: jax.Device,
        client,
        dataset,
        epochs: int,
        batch_size: int,
        on_epoch_done=None,
    ) -> List[Dict[str, float]]:
        """``index`` is the worker's GLOBAL slot along the data axis —
        it selects the dataset partition and seeds the RNG streams, so
        each shard is trained by exactly one worker job-wide."""
        compiled = self.compiled
        x, y = dataset.partition(index)
        nb = len(x) // batch_size
        if nb == 0:
            raise ValueError(
                f"worker {index}: partition of {len(x)} rows < batch_size {batch_size}"
            )
        usable = nb * batch_size
        x, y = np.asarray(x[:usable]), np.asarray(y[:usable])

        # Pipelined comms (wire transports by default): PS traffic moves
        # to a background thread so the worker never blocks on the wire
        # in steady state. The finally joins the thread on EVERY exit —
        # including a failed unit — so a dying worker can't leak a comms
        # thread still holding its client.
        pipelined = (
            self.pipelined_comms
            if self.pipelined_comms is not None
            else self.parameter_server_mode != "local"
        )
        comms = _CommsPipeline(
            client, index, self.max_failures,
            sync_interval=self.sync_interval,
        ) if pipelined else None
        try:
            return self._run_worker_units(
                index, device, client, comms, x, y, nb, usable,
                epochs, batch_size, on_epoch_done,
            )
        finally:
            if comms is not None:
                comms.close()

    def _run_worker_units(
        self,
        index: int,
        device: jax.Device,
        client,
        comms: Optional[_CommsPipeline],
        x,
        y,
        nb: int,
        usable: int,
        epochs: int,
        batch_size: int,
        on_epoch_done=None,
    ) -> List[Dict[str, float]]:
        compiled = self.compiled
        opt_state = None
        epoch_metrics: List[Dict[str, float]] = []
        # Worker threads each get their own tid row in the trace (events
        # without an explicit track land on the recording thread's name),
        # so per-worker pull/train/push phases read as parallel lanes.
        tracer = obs.default_tracer()

        def pull_state(step: int, attempt: int = 0) -> TrainState:
            nonlocal opt_state
            # Pipelined: async/pull now measures how long the worker
            # WAITED for params (near zero once the prefetch is warm);
            # the wire time itself lands on the comms thread's ps/pull
            # lane in the trace.
            with tracer.span("async/pull", worker=index, step=step):
                pulled = comms.pull() if comms is not None else client.get_parameters()
                if comms is not None and self.frequency == "batch":
                    # Double-buffered: the NEXT unit's pull rides the
                    # wire while this unit trains. It can miss this
                    # unit's own push (one unit of self-staleness — see
                    # the pipelined_comms docstring).
                    comms.prefetch()
                params = jax.device_put(pulled["params"], device)
                batch_stats = jax.device_put(pulled["batch_stats"], device)
                if opt_state is None:
                    opt_state = jax.device_put(
                        compiled.init_opt_state(params), device
                    )
                rng = jax.random.fold_in(
                    jax.random.fold_in(self._base_rng, index), step
                )
                if attempt:  # retry of this unit: a distinct dropout stream
                    rng = jax.random.fold_in(rng, 10_000 + attempt)
                return TrainState.create(
                    params=params,
                    opt_state=opt_state,
                    batch_stats=batch_stats,
                    rng=jax.device_put(rng, device),
                    step=step,
                )

        def push_delta(before: TrainState, after: TrainState) -> None:
            with tracer.span("async/push", worker=index) as psp:
                delta_params = self._subtract(before.params, after.params)
                delta = {
                    "params": delta_params,
                    "batch_stats": self._subtract(
                        before.batch_stats, after.batch_stats
                    ),
                }
                if self.frequency == "epoch":
                    # Dynamics only at epoch granularity: the norms
                    # force a device fetch, and a per-step force would
                    # serialize the batch pipeline (see run_unit's
                    # device-fault note). Epoch units already forced
                    # their scan before pushing, so this is one small
                    # transfer, not a stall.
                    obs.record_unit_dynamics(
                        obs.default_registry(), f"w{index}",
                        delta_norm=obs.tree_norm(
                            jax.device_get(delta_params)),
                        param_norm=obs.tree_norm(
                            jax.device_get(before.params)),
                        span=psp,
                    )
                if comms is None:
                    client.update_parameters(delta)
                    return
                comms.push(delta)  # fire-and-forget, bounded backpressure
                if self.frequency == "epoch":
                    # Epoch pulls prefetch AFTER the push so the next
                    # epoch's base always includes this worker's own
                    # epoch (a whole epoch of self-staleness would be
                    # too costly); the pull then overlaps the metric
                    # fetch + epoch-barrier work instead of training.
                    comms.prefetch()

        def run_unit(unit, **unit_args):
            """Spark's ``spark.task.maxFailures`` analogue (SURVEY.md §5.3):
            ``unit(attempt)`` runs one frequency-unit from a fresh PS pull;
            a transient exception retries it (re-seeded stream) up to
            ``max_failures`` total attempts before failing the worker.
            PS death is not a task fault — it propagates immediately so
            the fail-fast bound of ``ParameterServerUnavailable`` holds.

            Device-fault coverage: 'epoch' units force their results
            (the per-epoch metrics fetch) BEFORE pushing, so async XLA/
            runtime errors surface inside the retry and never reach the
            server. 'batch' units deliberately don't — a per-step force
            would serialize the chip queue the pipeline exists to keep
            full (VERDICT r1 weak#4) — so device faults there surface at
            the epoch-boundary fetch, outside the retry; the per-batch
            retry covers host- and wire-side faults.

            Delivery semantics (advisor r4): this layer is AT-LEAST-ONCE.
            The wire clients never re-send an in-flight write, but if a
            unit fails AFTER its push was applied server-side (e.g. the
            response read errors with something other than
            ParameterServerUnavailable), the retry re-runs the whole
            unit from a fresh pull and pushes a SECOND delta for the
            same batch/epoch. Benign for SGD — the duplicate is one more
            small stochastic step, same class of noise as hogwild's
            racing writers — and the push is the LAST fallible op in
            each unit, so the window is exactly the response handling."""
            nonlocal epoch_retries
            for attempt in range(self.max_failures):
                try:
                    # Each attempt roots its own trace: one causal tree
                    # per pull→train→push chain, spanning the comms-
                    # thread hop and the PS-side handle spans (which tag
                    # the boot id of whichever incarnation served them).
                    ctx = obs.new_context() if tracer.enabled else None
                    with obs.activate(ctx), tracer.span(
                            "async/unit", worker=index, attempt=attempt,
                            **unit_args):
                        return unit(attempt)
                except ParameterServerUnavailable:
                    raise
                except Exception:
                    if attempt + 1 >= self.max_failures:
                        raise
                    epoch_retries += 1
                    obs.default_registry().counter(
                        "worker_retry_total",
                        help="frequency-unit retries across all workers",
                    ).inc()

        epoch_retries = 0

        # Per-epoch bookkeeping + worker exit, SHARED by the streamed and
        # resident paths below — the contract (retry counts, history
        # shape, barrier callback, client close) must never diverge
        # between them.
        def finish_epoch(entry: Dict[str, float], epoch: int) -> None:
            if comms is not None:
                # All of this worker's epoch pushes must be SERVER-SIDE
                # before the barrier counts the epoch done — the barrier
                # snapshot feeds validation/checkpointing, and an honest
                # per-epoch val row must include the work it reports.
                # Waits on pushes only, never the prefetched pull.
                comms.flush()
            # Per-epoch loss lands next to the push-side norms above so
            # the worker's gauge row reads as one coherent unit.
            obs.record_unit_dynamics(
                obs.default_registry(), f"w{index}", loss=entry.get("loss"))
            entry["_retries"] = float(epoch_retries)
            epoch_metrics.append(entry)
            if on_epoch_done is not None:
                on_epoch_done(epoch)

        def finish_worker() -> List[Dict[str, float]]:
            if comms is not None:
                # Join the comms thread BEFORE closing the client — a
                # stray prefetch (epoch mode enqueues one after the
                # final push) must not race the close. Idempotent; the
                # _run_worker finally covers error exits.
                comms.close()
            if hasattr(client, "close"):
                client.close()
            return epoch_metrics

        if self.stream_batches is not None:
            # Streamed partition (opt-in, ``stream_batches=N``): HBM
            # holds at most ~2×N batches (the training chunk + the next
            # one uploading behind it) instead of the whole partition —
            # for partitions beyond per-chip HBM, the async analogue of
            # the sync trainer's double-buffered pipeline. The price is
            # a host-side shuffle gather + full-partition re-upload per
            # epoch; prefer the resident path when the partition fits.
            chunk_nb = max(1, min(self.stream_batches, nb))
            chunk_rows = chunk_nb * batch_size

            spans = []
            start = 0
            while start < usable:
                rows_count = min(chunk_rows, usable - start)
                spans.append((start, rows_count))
                start += rows_count

            def make_perm(epoch: int, attempt: int) -> np.ndarray:
                seq = [1234, index, 7, epoch]
                if attempt:  # re-seeded order clears data-order faults
                    seq.append(10_000 + attempt)
                return np.random.default_rng(seq).permutation(usable)

            def upload(perm, start_row, rows_count):
                sel = perm[start_row:start_row + rows_count]
                cnb = rows_count // batch_size
                cx = np.ascontiguousarray(x[sel]).reshape(
                    cnb, batch_size, *x.shape[1:]
                )
                cy = np.ascontiguousarray(y[sel]).reshape(
                    cnb, batch_size, *y.shape[1:]
                )
                return jax.device_put(cx, device), jax.device_put(cy, device)

            global_step = 0
            for epoch in range(epochs):
                epoch_retries = 0
                if self.frequency == "epoch":

                    def epoch_unit(attempt, epoch=epoch):
                        nonlocal opt_state
                        perm = make_perm(epoch, attempt)
                        state0 = pull_state(global_step, attempt)
                        state = state0
                        device_metrics = []
                        buf = upload(perm, *spans[0])
                        for ci in range(len(spans)):
                            # BACKPRESSURE: before a third chunk enters
                            # flight, wait for chunk ci-1's scan (its
                            # metrics force it) so its buffers free —
                            # without this the host (whose per-chunk work
                            # is a numpy gather + async dispatch) runs
                            # arbitrarily far ahead and peak residency
                            # approaches the whole partition, the exact
                            # OOM streaming exists to avoid. Cost: one
                            # small fetch per chunk.
                            if ci >= 1:
                                device_metrics[ci - 1] = jax.device_get(
                                    device_metrics[ci - 1]
                                )
                            # Dispatch the NEXT chunk's upload before
                            # scanning this one: host→device transfer
                            # overlaps the chunk's compute.
                            nxt = (
                                upload(perm, *spans[ci + 1])
                                if ci + 1 < len(spans)
                                else None
                            )
                            state, metrics = self._epoch_fn(state, *buf)
                            device_metrics.append(metrics)
                            buf = nxt
                        # Forces every chunk's scan: a device-side fault
                        # raises HERE (retryable) before the delta is
                        # pushed (same contract as the resident path).
                        fetched = jax.device_get(device_metrics)
                        from elephas_tpu.engine.step import (
                            weighted_mean_over_chunks,
                        )

                        out = weighted_mean_over_chunks(
                            [(s, s + rows, i)
                             for i, (s, rows) in enumerate(spans)],
                            lambda start, stop, i: fetched[i],
                            usable,
                        )
                        push_delta(state0, state)
                        opt_state = state.opt_state
                        return out

                    entry = run_unit(epoch_unit, epoch=epoch, partition=index)
                    global_step += nb
                else:  # 'batch': pull/push per step, batches from the chunk
                    perm = make_perm(epoch, 0)
                    device_metrics = []
                    prev_last = None  # previous chunk's final batch metric
                    buf = upload(perm, *spans[0])
                    for si, (start_row, rows_count) in enumerate(spans):
                        cxb, cyb = buf
                        nxt = None
                        if si + 1 < len(spans):
                            # Same bounded pipeline as the epoch path:
                            # wait for the PREVIOUS chunk's work before a
                            # third chunk uploads, then prefetch the next
                            # chunk so its transfer overlaps this chunk's
                            # batch loop.
                            if prev_last is not None:
                                device_metrics[prev_last] = jax.device_get(
                                    device_metrics[prev_last]
                                )
                            nxt = upload(perm, *spans[si + 1])
                        for b in range(rows_count // batch_size):

                            def batch_unit(attempt, b=b, cxb=cxb, cyb=cyb):
                                nonlocal opt_state
                                state = pull_state(global_step, attempt)
                                new_state, metrics = self._step_fn(
                                    state, cxb[b], cyb[b]
                                )
                                push_delta(state, new_state)
                                opt_state = new_state.opt_state
                                return metrics

                            device_metrics.append(run_unit(
                                batch_unit, epoch=epoch, partition=index,
                                step=global_step))
                            global_step += 1
                        prev_last = len(device_metrics) - 1
                        buf = nxt
                    fetched = jax.device_get(device_metrics)
                    entry = {
                        k: float(np.mean([d[k] for d in fetched]))
                        for k in fetched[0]
                    }
                finish_epoch(entry, epoch)
            return finish_worker()

        # The partition is uploaded to the worker's chip ONCE and shuffled
        # ON DEVICE each epoch (mirroring the sync trainer's in-program
        # shuffle). The previous host-side gather + per-epoch re-upload
        # cost a full partition transfer per epoch, on the critical path
        # of the epoch's compute. HBM residency: 1× the partition,
        # plus a second shuffled copy in 'epoch' frequency only (the scan
        # needs the batched stack); 'batch' frequency gathers one batch
        # at a time from the resident flat arrays. Opt-in
        # ``stream_batches`` (above) trades this for a bounded-HBM
        # chunk pipeline.
        x_d = jax.device_put(x, device)
        y_d = jax.device_put(y, device)

        def reshuffle(key, xf, yf):
            perm = jax.random.permutation(key, xf.shape[0])
            return (
                xf[perm].reshape(nb, batch_size, *xf.shape[1:]),
                yf[perm].reshape(nb, batch_size, *yf.shape[1:]),
            )

        reshuffle_fn = jax.jit(reshuffle)

        def take_batch(xf, yf, perm, start):
            idx = jax.lax.dynamic_slice_in_dim(perm, start, batch_size)
            return jnp.take(xf, idx, axis=0), jnp.take(yf, idx, axis=0)

        take_batch_fn = jax.jit(take_batch)  # start is traced: one compile
        shuffle_base = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(1234), index), 7
        )

        global_step = 0
        for epoch in range(epochs):
            epoch_retries = 0
            if self.frequency == "epoch":

                def epoch_unit(attempt, epoch=epoch):
                    nonlocal opt_state
                    key = jax.random.fold_in(shuffle_base, epoch)
                    if attempt:  # re-seeded shuffle clears data-order faults
                        key = jax.random.fold_in(key, 10_000 + attempt)
                    t0 = time.perf_counter()
                    ex_d, ey_d = reshuffle_fn(jax.device_put(key, device), x_d, y_d)
                    self._mark_phase("reshuffle", t0, ex_d)
                    t0 = time.perf_counter()
                    state = pull_state(global_step, attempt)
                    self._mark_phase("pull", t0, state.params)
                    t0 = time.perf_counter()
                    with tracer.span("async/train", worker=index, epoch=epoch):
                        new_state, metrics = self._epoch_fn(state, ex_d, ey_d)
                        # Fetching metrics forces the whole epoch scan, so a
                        # device-side fault raises HERE (retryable) before the
                        # delta is pushed — a poisoned delta must never reach
                        # the shared buffer.
                        fetched = {
                            k: float(v)
                            for k, v in jax.device_get(metrics).items()
                        }
                    self._mark_phase("train", t0, new_state.params)
                    t0 = time.perf_counter()
                    push_delta(state, new_state)
                    self._mark_phase("push", t0)
                    opt_state = new_state.opt_state
                    return fetched

                entry = run_unit(epoch_unit, epoch=epoch, partition=index)
                global_step += nb
            else:  # frequency == 'batch': pull/push every step (reference cadence)
                # Metrics stay on-device per step; one device_get per epoch.
                # A per-step fetch would block the host on every dispatch and
                # serialize the chip queue (VERDICT r1 weak#4). Each batch is
                # a device-side gather from the resident flat partition.
                epoch_key = jax.device_put(
                    jax.random.fold_in(shuffle_base, epoch), device
                )
                perm_d = jax.random.permutation(epoch_key, usable)
                device_metrics = []
                for b in range(nb):

                    def batch_unit(attempt, b=b):
                        nonlocal opt_state
                        xb, yb = take_batch_fn(x_d, y_d, perm_d, b * batch_size)
                        state = pull_state(global_step, attempt)
                        new_state, metrics = self._step_fn(state, xb, yb)
                        push_delta(state, new_state)
                        opt_state = new_state.opt_state
                        return metrics

                    device_metrics.append(run_unit(
                        batch_unit, epoch=epoch, partition=index,
                        step=global_step))
                    global_step += 1
                fetched = jax.device_get(device_metrics)
                entry = {
                    k: float(np.mean([d[k] for d in fetched])) for k in fetched[0]
                }
            finish_epoch(entry, epoch)
        return finish_worker()
