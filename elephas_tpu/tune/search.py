"""Search-space combinators, samplers, and the two search frontends.

This module is the tuner's user surface. It carries two generations of
API:

1. The **reference-parity frontend** — ``hp`` combinators +
   ``HyperParamModel.minimize`` (hyperas/hyperopt analogue, SURVEY.md
   §3.4): embarrassingly-parallel trials with independent per-worker
   streams, one host thread per chip, DCN argmin on pods. Moved here
   verbatim from the original ``elephas_tpu/hyperparam.py`` (which
   remains as a compatibility façade re-exporting these names).
2. The **elastic ASHA frontend** — ``sample_trials`` +
   ``run_search``: the same ``hp`` spaces, but trials run as
   lease-fenced ledger units on the elastic worker pool with successive
   halving, vault checkpoints, and full observability wiring
   (``tune/scheduler.py``, ``tune/runner.py``).

Objective contract (hyperopt-compatible, frontend 1):
    ``model_fn(sample: dict, data) -> {"loss": float, "model":
    CompiledModel, "status": "ok"}`` — extra keys are kept and returned
    with the trial.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from elephas_tpu.tune.trial import TrialSpec

__all__ = [
    "hp", "HyperParamModel", "sample_space", "current_trial_device",
    "width_bucket", "sample_trials", "run_search",
]


def width_bucket(width: int, buckets) -> int:
    """Smallest bucket >= ``width`` — the executable-sharing quantizer.

    XLA compiles one executable per SHAPE, so a width search that builds
    models at every sampled width pays a full compile per fresh width
    (~12s on a v5e chip, parity_results.jsonl, July 2026). Building instead at
    ``width_bucket(w, buckets)`` with the true width masked
    (``models.mlp.MaskedMLP``, or any model taking a bucket+active
    pair) means only bucket boundaries ever compile; combined with an
    ``"injected"`` optimizer (api.compile.resolve_optimizer) the whole
    search shares len(buckets) executables.
    """
    for b in sorted(int(b) for b in buckets):
        if width <= b:
            return b
    raise ValueError(
        f"width {width} exceeds the largest bucket {max(buckets)} — "
        "add a bucket at least as large as the search space's maximum"
    )

_trial_ctx = threading.local()


def current_trial_device():
    """The device the calling trial's worker thread is pinned to.

    For use inside objectives that build their own mesh/trainer (e.g.
    the parity harness): each worker thread publishes its device here
    before running trials. Outside a trial thread, falls back to the
    default device.
    """
    device = getattr(_trial_ctx, "device", None)
    return device if device is not None else jax.devices()[0]


class _Dist:
    def sample(self, rng: np.random.Generator):
        raise NotImplementedError

    # -- numeric-KDE interface (TPE). Choice overrides with categorical logic.
    def warp(self, value) -> float:
        """Map a sampled value into the continuous domain the TPE kernel
        density lives in (log-space for loguniform, identity otherwise)."""
        return float(value)

    @property
    def span(self) -> float:
        """Width of the warped domain (bandwidth floor for the KDE)."""
        raise NotImplementedError


class _Choice(_Dist):
    def __init__(self, options):
        self.options = list(options)

    def sample(self, rng):
        return self.options[rng.integers(len(self.options))]


class _Uniform(_Dist):
    def __init__(self, low, high):
        self.low, self.high = low, high

    def sample(self, rng):
        return float(rng.uniform(self.low, self.high))

    @property
    def span(self):
        return float(self.high - self.low)


class _LogUniform(_Dist):
    def __init__(self, low, high):
        # hyperopt convention: bounds are on log(value).
        self.low, self.high = low, high

    def sample(self, rng):
        return float(np.exp(rng.uniform(self.low, self.high)))

    def warp(self, value):
        return float(np.log(value))

    @property
    def span(self):
        return float(self.high - self.low)


class _QUniform(_Dist):
    def __init__(self, low, high, q):
        self.low, self.high, self.q = low, high, q

    def sample(self, rng):
        return float(np.round(rng.uniform(self.low, self.high) / self.q) * self.q)

    @property
    def span(self):
        return float(self.high - self.low)


class _RandInt(_Dist):
    def __init__(self, upper):
        self.upper = upper

    def sample(self, rng):
        return int(rng.integers(self.upper))

    @property
    def span(self):
        return float(self.upper)


class hp:
    """hyperopt-flavored search-space combinators."""

    choice = _Choice
    uniform = _Uniform
    loguniform = _LogUniform
    quniform = _QUniform
    randint = _RandInt


def sample_space(space: Any, rng: np.random.Generator) -> Any:
    """Recursively sample every ``hp.*`` node in a nested dict/list/tuple."""
    if isinstance(space, _Dist):
        return space.sample(rng)
    if isinstance(space, dict):
        return {k: sample_space(v, rng) for k, v in space.items()}
    if isinstance(space, (list, tuple)):
        return type(space)(sample_space(v, rng) for v in space)
    return space


def _iter_nodes(space: Any, path=()):
    """Yield (path, dist) for every ``hp.*`` node in the nested space."""
    if isinstance(space, _Dist):
        yield path, space
    elif isinstance(space, dict):
        for k, v in space.items():
            yield from _iter_nodes(v, path + (k,))
    elif isinstance(space, (list, tuple)):
        for i, v in enumerate(space):
            yield from _iter_nodes(v, path + (i,))


def _substitute(space: Any, values: Dict, path=()):
    """Rebuild the space structure with ``values[path]`` at each hp node."""
    if isinstance(space, _Dist):
        return values[path]
    if isinstance(space, dict):
        return {k: _substitute(v, values, path + (k,)) for k, v in space.items()}
    if isinstance(space, (list, tuple)):
        return type(space)(
            _substitute(v, values, path + (i,)) for i, v in enumerate(space)
        )
    return space


class _RandomSampler:
    """Pure random search (``algo='random'``) — the r1/r2 behavior."""

    def __init__(self, space: Any, rng: np.random.Generator):
        self.space = space
        self.rng = rng
        self.nodes = list(_iter_nodes(space))

    def suggest(self):
        values = {path: dist.sample(self.rng) for path, dist in self.nodes}
        return values, _substitute(self.space, values)

    def observe(self, values: Dict, loss: float) -> None:
        pass


class _TPESampler(_RandomSampler):
    """TPE-lite: within-worker *adaptive* sampling (``algo='tpe'``).

    The reference runs sequential ``hyperopt.fmin`` (default algo: TPE)
    inside each executor (SURVEY.md §3.4) — adaptive within a worker,
    independent across workers. This is the same shape: after
    ``n_startup`` random trials, observations are split at the ``gamma``
    quantile into good/bad sets; each of ``n_candidates`` prior draws is
    scored by the factorized density ratio l(x)/g(x) (per-node Gaussian
    KDE in the warped domain for numeric nodes, add-one-smoothed
    categorical for ``hp.choice``) and the argmax is evaluated. Like
    hyperopt, nodes are treated independently.
    """

    def __init__(self, space, rng, n_startup: int = 5, n_candidates: int = 24,
                 gamma: float = 0.25):
        super().__init__(space, rng)
        self.n_startup = n_startup
        self.n_candidates = n_candidates
        self.gamma = gamma
        self.history: List[tuple] = []  # (values, loss)

    def observe(self, values: Dict, loss: float) -> None:
        self.history.append((values, float(loss)))

    def _node_log_density(self, path, dist, value, observations) -> float:
        obs = [o[path] for o in observations]
        if isinstance(dist, _Choice):
            try:
                matches = sum(1 for o in obs if o == value)
            except Exception:
                matches = 0
            return float(
                np.log((matches + 1.0) / (len(obs) + len(dist.options)))
            )
        w = dist.warp(value)
        ws = np.array([dist.warp(o) for o in obs], dtype=np.float64)
        sigma = max(float(np.std(ws)), 0.05 * dist.span, 1e-12)
        logps = -0.5 * ((w - ws) / sigma) ** 2 - np.log(sigma)
        return float(np.logaddexp.reduce(logps) - np.log(len(ws)))

    def suggest(self):
        if not self.nodes or len(self.history) < self.n_startup:
            return super().suggest()
        ordered = sorted(self.history, key=lambda t: t[1])
        n_good = max(1, int(np.ceil(self.gamma * len(ordered))))
        good = [v for v, _ in ordered[:n_good]]
        bad = [v for v, _ in ordered[n_good:]] or good
        best_score, best_values = -np.inf, None
        for _ in range(self.n_candidates):
            values = {path: dist.sample(self.rng) for path, dist in self.nodes}
            score = sum(
                self._node_log_density(path, dist, values[path], good)
                - self._node_log_density(path, dist, values[path], bad)
                for path, dist in self.nodes
            )
            if score > best_score:
                best_score, best_values = score, values
        return best_values, _substitute(self.space, best_values)


_SAMPLERS = {"random": _RandomSampler, "tpe": _TPESampler}


class HyperParamModel:
    """Distributed random search with per-worker independent streams.

    Constructor mirrors the reference (``HyperParamModel(sc, num_workers)``);
    ``sc`` is accepted-and-ignored (no Spark driver).
    """

    def __init__(self, sc=None, num_workers: Optional[int] = None):
        del sc
        # LOCAL worker count: one thread per addressable chip. Multi-host,
        # every host runs the same minimize() over its own chips and the
        # job-wide reduction happens over DCN (see minimize).
        n_devices = len(jax.local_devices())
        self.num_workers = min(num_workers or n_devices, n_devices)
        self.best_models: List[Dict] = []  # per-worker bests (reference attr)
        self.trials: List[Dict] = []  # every LOCAL trial of the last minimize
        self._last_best: Optional[Dict] = None  # returned best (global, multi-host)

    def minimize(
        self,
        model: Callable,
        data: Callable,
        max_evals: int = 10,
        space: Optional[Dict] = None,
        seed: int = 0,
        algo: str = "tpe",
    ):
        """Run ``max_evals`` trials split across workers; return the best
        trial dict (``{"loss", "model", "sample", ...}``).

        ``model``: objective ``(sample, data) -> {"loss", "model", ...}``.
        ``data``: zero-arg callable returning the dataset given to every
        trial (the reference's hyperas ``data`` function).
        ``algo``: ``'tpe'`` (default — within-worker adaptive, matching
        the reference's per-executor ``hyperopt.fmin``) or ``'random'``.

        Multi-host (pod): every host calls this with the same arguments
        (SPMD control flow — the allgather below is a collective).
        ``max_evals`` splits across the job's global worker slots so
        exactly ``max_evals`` trials run job-wide; each host's best is
        gathered over DCN and every host returns the identical global
        argmin, the winner's model rebuilt from its serialized payload
        where possible. Per-trial wall times ride each result as
        ``t_start``/``t_end``/``secs`` (``time.perf_counter``) for
        steady-state throughput accounting.
        """
        if space is None:
            space = {}
        if algo not in _SAMPLERS:
            raise ValueError(f"algo must be one of {sorted(_SAMPLERS)}, got {algo!r}")
        dataset = data() if callable(data) else data
        n_hosts = jax.process_count()
        pid = jax.process_index()
        multi_host = n_hosts > 1
        # Global worker slots. Hosts can expose unequal chip counts, so
        # the split is computed over GATHERED local counts — exactly
        # max_evals trials job-wide, the trailing slots absorbing the
        # remainder (idle slots get zero, like the reference's idle
        # executors).
        if multi_host:
            from jax.experimental import multihost_utils

            counts = np.asarray(
                multihost_utils.process_allgather(
                    np.array([self.num_workers], dtype=np.int64)
                )
            ).reshape(-1)
            total_workers = int(counts.sum())
            offset = int(counts[:pid].sum())
        else:
            total_workers = self.num_workers
            offset = 0
        base, extra = divmod(max_evals, total_workers)
        trials_for = [base + (1 if g < extra else 0) for g in range(total_workers)]
        devices = jax.local_devices()[: self.num_workers]
        results: List[List[Dict]] = [[] for _ in range(self.num_workers)]
        errors: List[BaseException] = []

        def worker(index: int, device) -> None:
            # Independent stream per GLOBAL worker slot — the reference's
            # independent Trials() semantics (§3.4 note); the sampler is
            # adaptive only *within* this worker, exactly like
            # per-executor fmin. SeedSequence spawning: collision-free
            # across (seed, slot) pairs — including across hosts —
            # unlike arithmetic seed mixing.
            g = offset + index
            rng = np.random.default_rng([seed, g])
            sampler = _SAMPLERS[algo](space, rng)
            _trial_ctx.device = device  # thread-local; see current_trial_device
            try:
                with jax.default_device(device):
                    for trial in range(trials_for[g]):
                        values, sample = sampler.suggest()
                        t0 = time.perf_counter()
                        out = model(sample, dataset)
                        t1 = time.perf_counter()
                        if not isinstance(out, dict) or "loss" not in out:
                            raise TypeError(
                                "objective must return a dict with a 'loss' key"
                            )
                        out.setdefault("status", "ok")
                        out["sample"] = sample
                        out["worker"] = g
                        out["trial"] = trial
                        out["t_start"] = t0
                        out["t_end"] = t1
                        out["secs"] = t1 - t0
                        results[index].append(out)
                        sampler.observe(values, float(out["loss"]))
            except BaseException as exc:
                errors.append(exc)

        threads = [
            threading.Thread(target=worker, args=(i, dev), daemon=True)
            for i, dev in enumerate(devices)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors and not multi_host:
            raise errors[0]

        self.trials = [t for worker_results in results for t in worker_results]
        self.best_models = [
            min(worker_results, key=lambda r: r["loss"])
            for worker_results in results
            if worker_results
        ]
        local_best = (
            min(self.best_models, key=lambda r: r["loss"])
            if self.best_models
            else None
        )
        if not multi_host:
            if local_best is None:
                raise RuntimeError("no trials completed")
            self._last_best = local_best
            return local_best
        # The allgather is a COLLECTIVE: a host that raised before it
        # would park every peer inside process_allgather with no bounded
        # failure path (the async engine's PS barriers exist for the same
        # reason). So even a host whose workers errored contributes what
        # it has (possibly nothing), completes the collective, and THEN
        # re-raises locally — peers finish with the surviving trials.
        try:
            best = self._global_argmin(local_best, pid)
        except RuntimeError:
            if errors:
                raise errors[0]  # the objective's real failure, not the
            raise                # derived "no trials job-wide"
        if errors:
            raise errors[0]
        self._last_best = best
        return best

    def _global_argmin(self, local_best: Optional[Dict], pid: int) -> Dict:
        """Reference §3.4's driver ``collect()`` + argmin, over DCN: gather
        every host's best (a collective — every host must call this), pick
        the global argmin with a deterministic (loss, host) tie-break, and
        rebuild the winner's model locally where it was serializable."""
        import pickle

        from elephas_tpu.parallel import distributed

        payload = None
        if local_best is not None:
            summary = {k: v for k, v in local_best.items() if k != "model"}
            model_payload = None
            model_obj = local_best.get("model")
            if model_obj is not None:
                try:
                    from elephas_tpu.serialize.serialization import model_to_dict

                    model_payload = model_to_dict(model_obj)
                except Exception:
                    model_payload = None  # winner's host keeps the live object
            try:
                payload = pickle.dumps(
                    {"host": pid, "summary": summary, "model_payload": model_payload}
                )
            except Exception:
                payload = pickle.dumps(
                    {
                        "host": pid,
                        "summary": {
                            "loss": float(local_best["loss"]),
                            "sample": local_best.get("sample"),
                            "worker": local_best.get("worker"),
                            "trial": local_best.get("trial"),
                            "status": local_best.get("status", "ok"),
                        },
                        "model_payload": model_payload,
                    }
                )
        gathered = distributed.allgather_bytes(
            payload if payload is not None else pickle.dumps(None)
        )
        candidates = [c for c in (pickle.loads(b) for b in gathered) if c is not None]
        if not candidates:
            raise RuntimeError("no trials completed job-wide")
        win = min(candidates, key=lambda c: (c["summary"]["loss"], c["host"]))
        if win["host"] == pid and local_best is not None:
            return local_best  # the live trial dict, model object included
        best = dict(win["summary"])
        if win["model_payload"] is not None:
            from elephas_tpu.serialize.serialization import dict_to_model

            best["model"] = dict_to_model(win["model_payload"])
        return best

    def best_model(self):
        """Best model object across workers — job-wide after a multi-host
        ``minimize`` (reference convenience)."""
        best = getattr(self, "_last_best", None)
        if best is None:
            # A rank whose global slots got zero trials still holds the
            # gathered winner in _last_best; best_models alone can't tell
            # "never minimized" from "idle rank".
            if not self.best_models:
                raise RuntimeError("call minimize() first")
            best = min(self.best_models, key=lambda r: r["loss"])
        return best.get("model")


# -- elastic ASHA frontend ----------------------------------------------------


def sample_trials(space: Any, num_trials: int, seed: int = 0) -> List[TrialSpec]:
    """Draw the search's trial population from ONE seeded stream.

    Same ``seed`` ⇒ the identical config stream (the chaos gate's
    precondition); per-trial seeds derive from ``SeedSequence([seed,
    trial])`` so trial workloads are decorrelated but replayable.
    """
    rng = np.random.default_rng([int(seed)])
    sampler = _RandomSampler(space, rng)
    specs: List[TrialSpec] = []
    for i in range(int(num_trials)):
        values, config = sampler.suggest()
        trial_seed = int(np.random.SeedSequence([int(seed), i])
                         .generate_state(1)[0])
        specs.append(TrialSpec(i, config, trial_seed, values=values))
    return specs


def run_search(trial_fn: Callable, space: Any, *, num_trials: int = 9,
               seed: int = 0, eta: int = 3, rungs: int = 3, r0: int = 1,
               workers: int = 2, vault=None, injector=None,
               client_factory=None, plateau_delta_norm: Optional[float] = None,
               stall_after: Optional[float] = None,
               registry=None, tracer=None, flight=None,
               clock: Callable[[], float] = time.monotonic,
               sleep: Callable[[float], None] = time.sleep) -> Dict[str, Any]:
    """One elastic ASHA search, end to end.

    ``trial_fn(config, state, epochs, seed, rung) -> {"loss", "state"}``
    trains ``epochs`` more epochs from ``state`` (None = fresh init).
    Returns the search doc (winner config/digest, search digest, epoch
    accounting, pool resilience stats) — see ``TuneRunner.run``.
    """
    # Lazy import: the elastic/observability stack is only needed when a
    # search actually runs, and this module loads during package init.
    from elephas_tpu.tune.runner import TuneRunner
    from elephas_tpu.tune.scheduler import AshaScheduler

    specs = sample_trials(space, num_trials, seed)
    scheduler = AshaScheduler(
        specs, eta=eta, rungs=rungs, r0=r0,
        plateau_delta_norm=plateau_delta_norm, stall_after=stall_after,
        clock=clock, registry=registry, flight=flight)
    runner = TuneRunner(
        trial_fn, scheduler, vault=vault,
        worker_ids=[f"w{i}" for i in range(int(workers))],
        client_factory=client_factory, injector=injector,
        registry=registry, tracer=tracer, flight=flight,
        clock=clock, sleep=sleep)
    doc = runner.run()
    doc["trials"] = {str(t.spec.trial_id): t.to_doc()
                     for t in scheduler.trials}
    return doc
