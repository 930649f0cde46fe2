"""What the `fit` driver needs: the model and rows from a cell's files,
the comparison with the plain reference, and the free of the program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import numpy as np

from .cells import Cell


def model_cfg(config: dict) -> dict:
    return {k: config[k] for k in ("width", "stage_sizes", "num_classes", "image_size",
                                   "channels", "rows")}


def compile_resnet(config: dict, params, batch_stats):
    """The program's `CompiledModel` around the benchmark's own weights
    (`params=None`: the program's initialiser, for shapes only)."""
    from elephas_tpu import compile_model
    from elephas_tpu.models import get_model

    module = get_model(config["model"], num_classes=config["num_classes"],
                       width=config["width"], dtype=config["dtype"])
    side, ch = config["image_size"], config["channels"]
    kwargs = {} if params is None else {"params": params, "batch_stats": batch_stats}
    return compile_model(module, optimizer=dict(config["optimizer"]), loss=config["loss"],
                         metrics=["acc"], input_shape=(side, side, ch), **kwargs)


@dataclass
class FitRun:
    """One run's state: what the readers read."""
    cell: Cell
    config: dict
    traffic: dict
    cfg: dict
    batch: int
    steps: int                     # steps an epoch, per worker
    rows_per_epoch: int            # rows trained an epoch, over all workers
    epochs: int = 0
    fit_s: float = 0.0
    stamps: List[float] = field(default_factory=list)   # epoch ends of the timed fit
    setup_s: float = 0.0
    window: object = None
    capture: object = None
    trace: object = None
    spans: list = field(default_factory=list)
    peak: dict = None
    extra: dict = field(default_factory=dict)

    @property
    def epoch_seconds(self) -> List[float]:
        return [b - a for a, b in zip(self.stamps, self.stamps[1:])]


def momentum_trace(opt_state):
    """The momentum leaf tree of an optax `sgd(momentum=...)` state."""
    for part in opt_state:
        if hasattr(part, "trace"):
            return part.trace
    raise ValueError("no momentum trace in the optimizer state")


def leaf_norms(tree) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(np.linalg.norm(np.asarray(leaf, np.float64)))
            for path, leaf in flat}


def norm_gaps(program: dict, reference: dict, skip=()) -> tuple:
    """The gap between the program's norm of each leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (widest gap, its leaf, median gap)."""
    median = float(np.median(list(reference.values())))
    gaps = {name: abs(program[name] - ref) / max(ref, median)
            for name, ref in reference.items() if name not in skip}
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def compare_first_epoch(run: FitRun, seed: int, captured: dict, control: bool) -> dict:
    """The reference follows the timed call's first epoch, the finest grain
    at which `fit` commits anything, from the same seeded weights and rows:
    the epoch's mean loss, the momentum the optimizer holds after it (the
    gradients as it got them, summed with decay), and the change of the
    parameters, each by the worst leaf."""
    import jax
    import jax.numpy as jnp

    from . import weights

    ref = run.cell.module("references", run.config["reference"])
    cfg, opt = run.cfg, run.config["optimizer"]
    x, y = weights.separable_rows(seed, cfg)
    params0, stats0 = weights.resnet18_variables(seed, cfg)
    program = {
        "loss": captured["loss"],
        "trace": leaf_norms(captured["trace"]),
        "update": leaf_norms(jax.tree_util.tree_map(
            lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
            captured["params"], jax.device_get(params0))),
    }

    def follow(quant, rows_used=None):
        p0 = jax.tree_util.tree_map(jnp.copy, params0)
        s0 = jax.tree_util.tree_map(jnp.copy, stats0)
        p, _, trace, losses = ref.train_epoch(
            p0, s0, x, y, 0, run.steps, run.batch, tuple(cfg["stage_sizes"]),
            opt["learning_rate"], opt["momentum"], quant=quant, rows_used=rows_used)
        update = jax.tree_util.tree_map(
            lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
            jax.device_get(p), jax.device_get(params0))
        return {"loss": float(losses.mean()), "trace": leaf_norms(trace),
                "update": leaf_norms(update), "losses": np.asarray(losses).tolist()}

    def gaps(a, b):
        median = float(np.median(list(b["trace"].values())))
        skip = {k for k, v in b["trace"].items() if v < 1e-3 * median}
        g, g_leaf, g_med = norm_gaps(a["trace"], b["trace"])
        u, u_leaf, u_med = norm_gaps(a["update"], b["update"], skip)
        return {"loss_gap": abs(a["loss"] - b["loss"]) / abs(b["loss"]),
                "grad_norm_gap": g, "grad_norm_gap_leaf": g_leaf,
                "update_norm_gap": u, "update_norm_gap_leaf": u_leaf,
                "median_grad_norm_gap": g_med, "median_update_norm_gap": u_med,
                "leaves_left_out": sorted(skip)}

    reference = follow(ref.identity)
    out = gaps(program, reference)
    out["reference_losses"] = reference["losses"]
    out["program_loss"], out["reference_loss"] = program["loss"], reference["loss"]
    if control:
        # in the program's place: the reference in float8, and the reference
        # with half of each batch left out
        low = follow(ref.fp8)
        out["float8_reference"] = {k: v for k, v in gaps(low, reference).items()
                                   if not k.startswith("leaves")}
        out["float8_reference"]["loss"] = low["loss"]
        half = follow(ref.identity, rows_used=run.batch // 2)
        out["half_batch"] = {k: v for k, v in gaps(half, reference).items()
                             if not k.startswith("leaves")}
    jax.clear_caches()
    return out
