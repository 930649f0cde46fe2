"""The benchmark's one command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N --seconds S --trace 0|1

Finds the cell's files by name (`lib/cells.py`), refuses to run without the
TPU and the chips the cell asks for, keeps JAX's compile cache inside the
checkout (or where `JAX_COMPILATION_CACHE_DIR` says), hands the cell to the
driver its traffic file names, and prints one JSON object as the last line
of standard output. With `--trace 0` its metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics.

`--rehearse` walks the same code at the files' `rehearsal` sizes on
whatever backend there is, for the tests; it prints no metric and exits 3.
`--control 1` also puts the controls of `correct` in the program's place
(the reference in the next precision down, a planted fault) and holds them
to the program's limits: each has to come out not correct, and the run
exits 4 if one passes. The driver's runs never set it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

REHEARSAL_EXIT = 3
CONTROL_PASSED_EXIT = 4


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--overlay", default=None)
    parser.add_argument("--set", action="append", default=[], metavar="traffic.key=json",
                        help="override a traffic-file value, for a sweep; never the driver's")
    args = parser.parse_args(argv)

    from lib import cells
    from lib.stats import passes, print_result

    cell = cells.Cell(args.workload, args.overlay)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    for item in args.set:
        path, value = item.split("=", 1)
        node, *keys = path.split(".")
        target = {"traffic": cell.traffic, "config": cell.config}[node]
        for key in keys[:-1]:
            target = target[key]
        target[keys[-1]] = json.loads(value)

    import jax

    from elephas_tpu.utils.compiler import configure_compile_cache

    if not args.rehearse:
        cache_dir = configure_compile_cache()
        # every program, also those that compile in under a second
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cells.log(f"compile cache: {cache_dir}")
    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if not args.rehearse and devices[0].platform != "tpu":
        cells.log(f"benchmark: no TPU — JAX reports {found}")
        return 2
    if len(devices) < cell.chips:
        cells.log(f"benchmark: {cell.name} asks for {cell.chips} chips, JAX reports {found}")
        return 2

    driver = cell.module("drivers", cell.traffic["driver"])
    out = driver.drive(cell, args)
    breakdown = None
    if args.trace:
        metrics = cells.read_per_layer(cell, out["run"])
        trace = out["run"].trace
        if trace is not None:
            breakdown = {"device_ops": trace.top_ops(10),
                         "idle_gaps": trace.gaps_by_span(out["run"].spans, 10)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": float(out["end_to_end"][name]), "unit": unit}
                   for name, unit in units.items() if name in out["end_to_end"]}
        missing = set(units) - set(metrics)
        if missing:
            raise SystemExit(f"{cell.name}: the driver gave no {sorted(missing)}")
    if args.rehearse:  # sizes of a test: the names that were read, never a number
        out["notes"] = {**out.get("notes", {}), "metrics_read": sorted(metrics)}
        metrics, out["device"] = {}, {**out["device"], "rehearsal": True}
    controls = out.get("controls", {})
    print_result(out["attempted"], out["failed"], metrics, out["device"], out["checks"],
                 controls, breakdown, out.get("notes"))
    if any(passes(cs) for cs in controls.values()):
        cells.log("a control came out correct: the comparison does not tell it from the program")
        return CONTROL_PASSED_EXIT
    return REHEARSAL_EXIT if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
