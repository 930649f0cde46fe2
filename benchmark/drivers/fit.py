"""Training: the window is one call of `SparkModel.fit` with a fixed
number of epochs `E`, from the call to its return. Work is `E` times the
rows an epoch trains, exactly; time is what the call took. `E` is chosen
before the window from the epoch time the warm-up fit showed, so that the
call lasts about `--seconds`; a faster program gets more epochs and the
same window. No epoch is ever counted against a deadline.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from lib import fit as fit_lib
from lib import weights
from lib.cells import CHECKOUT, device_report, log, process_age_s
from lib.stats import check
from lib.xplane import TraceCapture


def drive(cell, args) -> dict:
    import jax

    from elephas_tpu import SparkModel, obs, to_simple_rdd

    config = cell.sized(cell.config, args.rehearse)
    tr = cell.sized(cell.traffic, args.rehearse)
    cfg, workers = fit_lib.model_cfg(config), tr["workers"]
    batch = config["training"]["batch_size"]
    steps = (cfg["rows"] // workers) // batch
    run = fit_lib.FitRun(cell, config, tr, cfg, batch, steps, workers * steps * batch)
    run.extra["rehearse"] = args.rehearse
    if args.trace:
        obs.enable_tracing(capacity=1 << 20)

    x_dev, y_dev = weights.separable_rows(args.seed, cfg)
    x, y = np.asarray(x_dev), np.asarray(y_dev)
    del x_dev, y_dev
    params, stats = weights.resnet18_variables(args.seed, cfg)
    model = SparkModel(fit_lib.compile_resnet(config, params, stats), mode=tr["mode"],
                       frequency=tr["frequency"], parameter_server_mode="local",
                       num_workers=workers)
    del params, stats
    dataset = to_simple_rdd(None, x, y, workers)
    log(f"rows, weights and model after {process_age_s():.1f} s")

    # warm-up: compiles every program and shows the epoch time that sets E.
    # The weights then go back to the seed's, so that the timed call starts
    # where the reference does and `correct` follows the timed call itself.
    warm = []
    t = time.monotonic()
    model.fit(dataset, epochs=tr["warmup_epochs"], batch_size=batch,
              callbacks=[lambda epoch, state, metrics: warm.append(time.monotonic())])
    warm_s = time.monotonic() - t
    epoch_s = warm[-1] - warm[-2]
    run.epochs = max(2, round((args.seconds - tr["fixed_s"]) / epoch_s))
    params, stats = weights.resnet18_variables(args.seed, cfg)
    model.set_weights(params)
    model.master_network.batch_stats = stats
    del params, stats
    log(f"warm-up fit {warm_s:.2f} s, epoch {epoch_s:.3f} s, E = {run.epochs}")

    # the traced part is the fit's last epochs; the profiler is stopped
    # after the call has returned, because stopping stalls the host
    last = run.epochs - 1
    first = max(0, last - tr["trace"]["epochs"])
    if args.trace:
        run.capture = TraceCapture(os.path.join(CHECKOUT, ".bench_trace", cell.name))

    captured = {}

    def on_epoch(epoch, state, metrics):
        run.stamps.append(time.monotonic())
        if epoch == 0:  # what the timed call's first epoch left: kept on the device
            captured.update(params=state.params, batch_stats=state.batch_stats,
                            trace=fit_lib.momentum_trace(state.opt_state),
                            loss=float(metrics["loss"]))
        if run.capture is not None:
            if epoch == first:
                run.capture.start()
            elif epoch == last:
                run.capture.mark_close()

    run.setup_s = process_age_s()
    log(f"window opens after {run.setup_s:.1f} s")
    t_open = time.monotonic()
    history = model.fit(dataset, epochs=run.epochs, batch_size=batch, callbacks=[on_epoch])
    run.fit_s = time.monotonic() - t_open
    if run.capture is not None:
        run.capture.stop()
        run.spans = [(e.name, e.begin_s, e.end_s) for e in obs.default_tracer().events()]
        try:
            run.trace = run.capture.result()
        except ValueError:
            if not args.rehearse:
                raise
    losses = [float(v) for v in history["loss"]]
    run.extra["loss_per_epoch"] = losses

    devices = jax.devices()[: cell.chips]
    run.peak = device_report(devices, run.trace)
    captured = jax.device_get(captured)
    del model, dataset, history
    gc.collect()
    t = time.monotonic()
    found = fit_lib.compare_first_epoch(run, args.seed, captured, bool(args.control))
    run.extra["reference"] = found
    run.extra["reference_s"] = time.monotonic() - t
    log(f"reference over the first epoch took {run.extra['reference_s']:.1f} s")
    limits = config["check"]

    def compared(gaps: dict) -> dict:
        return {name: check(gaps[name], limits[name + "_limit"])
                for name in ("loss_gap", "median_grad_norm_gap", "update_norm_gap")}

    checks = {"epochs_finite": check(int(np.all(np.isfinite(losses))) * len(losses),
                                     run.epochs, "equal"), **compared(found)}
    # what was put in the program's place (`--control 1`), held to the same limits
    controls = {name: compared(found[name]) for name in ("float8_reference", "half_batch")
                if name in found}
    end_to_end = {"setup_s": run.setup_s,
                  "train_samples_per_s_per_chip":
                      run.epochs * run.rows_per_epoch / run.fit_s / cell.chips}
    notes = {k: found[k] for k in ("grad_norm_gap", "grad_norm_gap_leaf", "update_norm_gap_leaf",
                                   "median_update_norm_gap", "leaves_left_out",
                                   "program_loss", "reference_loss")}
    notes["float8_reference"] = found.get("float8_reference")
    notes["half_batch"] = found.get("half_batch")
    notes.update(setup_s=run.setup_s, reference_s=run.extra["reference_s"],
                 epochs=run.epochs, fit_s=run.fit_s, warm_fit_s=warm_s, epoch_s=epoch_s)
    return {"run": run, "attempted": run.epochs, "failed": 0, "end_to_end": end_to_end,
            "checks": checks, "controls": controls, "device": run.peak, "notes": notes}
