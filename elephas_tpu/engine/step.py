"""Jitted train/eval/predict step builders.

This is the rebuild of the reference's per-worker compute: where
``elephas/worker.py::SparkWorker.train`` calls Keras ``model.fit`` on TF
kernels (SURVEY.md §3.1 [HOT]), here a pure function closes over the
``CompiledModel``'s apply/loss/optimizer and is compiled once by XLA.
The same step function serves every mode:

- sync: jitted over the mesh with the batch sharded on ``'data'`` —
  GSPMD inserts the gradient allreduce (``psum``) automatically since the
  loss is a global-batch mean;
- async/hogwild: jitted per-device, driven by host threads;
- single-chip: plain jit.

Losses are computed in f32 regardless of compute dtype; per-example loss
vectors are meaned so sharded means are exact when shard sizes are equal
(guaranteed by ``ShardedDataset.even_shards``).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from elephas_tpu.engine.state import TrainState


def make_loss_fn(compiled) -> Callable:
    """(params, batch_stats, x, y, rng) -> (loss, (new_batch_stats, outputs))."""

    def loss_fn(params, batch_stats, x, y, rng):
        outputs, new_stats = compiled.apply_train(params, batch_stats, x, rng)
        per_example = compiled.loss_fn(outputs.astype(jnp.float32), y)
        return per_example.mean(), (new_stats, outputs)

    return loss_fn


def _metrics_dict(compiled, loss, outputs, y) -> Dict[str, jax.Array]:
    metrics = {"loss": loss}
    for name, fn in zip(compiled.metric_names, compiled.metric_fns):
        metrics[name] = fn(outputs.astype(jnp.float32), y).mean()
    return metrics


def make_train_step(compiled, pmean_axis: Optional[str] = None) -> Callable:
    """Build ``step(state, x, y) -> (new_state, metrics)`` (uncompiled).

    ``pmean_axis``: if set (one axis name or a tuple of them), gradients
    and metrics are ``lax.pmean``'d over those mesh axes before the
    optimizer update — the per-step allreduce that replaces the
    reference's driver ``collect()`` in lockstep DP, and the combined
    data+seq reduction in sequence-parallel training.
    """
    loss_fn = make_loss_fn(compiled)

    def train_step(state: TrainState, x, y) -> Tuple[TrainState, Dict]:
        rng, step_rng = jax.random.split(state.rng)

        def forward(params):
            return loss_fn(params, state.batch_stats, x, y, step_rng)

        # `value_and_grad` spelled out as its two halves, so that each
        # carries its own name in the device trace (`forward/jvp(forward)`,
        # `backward/transpose(jvp(forward))`, `update`): HLO metadata
        # only, the program is the same.
        with jax.named_scope("forward"):
            loss, vjp_fn, (new_stats, outputs) = jax.vjp(
                forward, state.params, has_aux=True
            )
        with jax.named_scope("backward"):
            (grads,) = vjp_fn(jnp.ones_like(loss))
            if pmean_axis is not None:
                grads = jax.lax.pmean(grads, pmean_axis)
        with jax.named_scope("update"):
            updates, new_opt_state = compiled.optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), state.params, updates
            )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            rng=rng,
        )
        metrics = _metrics_dict(compiled, loss, outputs, y)
        if pmean_axis is not None:
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, pmean_axis), metrics
            )
        return new_state, metrics

    return train_step


def make_eval_step(compiled) -> Callable:
    """Build ``eval_step(state, x, y) -> metrics`` (deterministic)."""

    def eval_step(state: TrainState, x, y) -> Dict[str, jax.Array]:
        outputs = compiled.apply_eval(state.params, state.batch_stats, x)
        loss = compiled.loss_fn(outputs.astype(jnp.float32), y).mean()
        return _metrics_dict(compiled, loss, outputs, y)

    return eval_step


def weighted_mean_over_chunks(spans, eval_chunk, n: int) -> Dict[str, float]:
    """Exact weighted mean of per-chunk metric dicts over ``n`` rows.

    ``spans`` yields tuples whose first two elements are (start, stop);
    ``eval_chunk(*span)`` returns a metrics dict for those rows. Shared
    by the sync sharded evaluator and the async host-local evaluator so
    the weighting/remainder arithmetic cannot diverge between them
    (both implement the reference's weighted-average evaluate, §3.5).
    """
    totals: Dict[str, float] = {}
    for span in spans:
        start, stop = span[0], span[1]
        metrics = eval_chunk(*span)
        for k, v in metrics.items():
            totals[k] = totals.get(k, 0.0) + float(v) * (stop - start)
    return {k: v / n for k, v in totals.items()}


_EVAL_CACHE_MAX_BYTES = 1 << 30  # pin eval sets up to 1 GiB on device


class DeviceEvalCache:
    """Small LRU device cache for arrays evaluated repeatedly (per-epoch
    validation): uploading each set once and slicing on device saves a
    full host->device re-upload per epoch. Holding
    ``slots`` (default 4) entries means alternating validation sets —
    e.g. an estimator's val split plus a manual ``evaluate`` call — don't
    thrash the single slot and silently re-upload ~100MB per call.

    Keyed by object IDENTITY for arrays (host references are retained so
    a recycled ``id`` can never serve a stale copy) and equality for
    scalars. The identity key assumes callers do NOT mutate a cached
    array in place between epochs — ``fit(validation_data=...)`` /
    ``evaluate`` treat their arrays as immutable snapshots; mutate a
    copy (or pass a fresh array) to change the eval set. Sets larger
    than ``_EVAL_CACHE_MAX_BYTES`` are NOT cached — ``get`` returns None
    and the caller streams chunk-at-a-time as before, so huge eval sets
    keep their bounded-memory behavior. Cached entries together are
    bounded by the same byte budget (evicted LRU-first BEFORE the new
    set uploads), so the worst-case pinned HBM equals the old one-slot
    cache's — more slots never cost more memory.
    """

    def __init__(self, slots: int = 4):
        self._slots = max(1, int(slots))
        self._entries: list = []  # [(key, nbytes, device_value)], most recent last

    @staticmethod
    def _same(a, b):
        import numpy as _np

        if isinstance(a, _np.ndarray) or isinstance(b, _np.ndarray):
            return a is b
        return a == b

    def _match(self, key: tuple) -> Optional[int]:
        for i, (k, _, _) in enumerate(self._entries):
            if len(k) == len(key) and all(self._same(a, b) for a, b in zip(k, key)):
                return i
        return None

    def get(self, key: tuple, nbytes: int, make: Callable):
        if nbytes > _EVAL_CACHE_MAX_BYTES:
            return None
        i = self._match(key)
        if i is not None:
            entry = self._entries.pop(i)
            self._entries.append(entry)  # refresh LRU position
            return entry[2]
        # Evict LRU-first until the new set fits BOTH bounds, before the
        # upload — peak pinned memory never exceeds the byte budget.
        while self._entries and (
            len(self._entries) >= self._slots
            or sum(e[1] for e in self._entries) + nbytes > _EVAL_CACHE_MAX_BYTES
        ):
            self._entries.pop(0)
        dev = make()
        self._entries.append((key, nbytes, dev))
        return dev


def make_predict_step(compiled) -> Callable:
    def predict_step(state: TrainState, x):
        return compiled.apply_eval(state.params, state.batch_stats, x)

    return predict_step


def make_epoch_scanner(train_step: Callable) -> Callable:
    """Build ``scan_epoch(state, xs, ys) -> (state, mean_metrics)``.

    xs/ys are (num_batches, batch, ...) stacks; the whole epoch runs as a
    single ``lax.scan`` inside one compiled program — no per-batch Python
    dispatch (the reference pays a network round-trip per batch in async
    mode; we don't even pay a host round-trip).
    """

    def scan_epoch(state: TrainState, xs, ys):
        def body(carry, batch):
            x, y = batch
            new_state, metrics = train_step(carry, x, y)
            return new_state, metrics

        state, metrics = jax.lax.scan(body, state, (xs, ys))
        return state, jax.tree_util.tree_map(lambda m: m.mean(), metrics)

    return scan_epoch


def init_train_state(compiled, rng=None) -> TrainState:
    """Fresh TrainState from a CompiledModel's current weights."""
    return TrainState.create(
        params=compiled.params,
        opt_state=compiled.init_opt_state(),
        batch_stats=compiled.batch_stats,
        rng=rng if rng is not None else jax.random.PRNGKey(0),
    )
