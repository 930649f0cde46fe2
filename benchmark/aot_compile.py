"""Compiles the cells' programs at their real shapes for a described
`v5e:2x2` chip, without the chip, and prints `memory_analysis()` of each:
the reckoning quoted in the two configuration files.

    JAX_PLATFORMS=cpu python3 benchmark/aot_compile.py [<config> ...]

A serving configuration's engine is built through the family its file
names (`models/<family>.py`), as the harness builds it. Nothing runs, so
this says nothing about times. It builds the engine's KV pool on the host
(5 GB of zeros at 16 slots), so it wants that much memory. Under the CPU
backend the engine takes the XLA body of the paged decode; the Pallas
kernel's program is compiled by `tests/test_aot_compile.py`.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))


def report(name, compiled):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": name,
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "generated_code_bytes": m.generated_code_size_in_bytes,
    }), flush=True)


def shapes_on(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def serving(topo, cells):
    """Both serving programs of each of `cells` (one configuration's), the
    engine built through the family its configuration file names, as
    `lib/serve.py` builds it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from elephas_tpu import InferenceEngine, compile_model
    from lib.cells import Cell

    chip = SingleDeviceSharding(topo.devices[0])
    for name in cells:
        cell = Cell(name)
        config = cell.config
        family = cell.module("models", config["model"])
        cfg = family.shape(config)
        s = {**config["serving"], **cell.traffic["engine"]}
        params = jax.eval_shape(lambda: family.params(0, cfg, jnp.dtype(config["dtype"])))
        compiled = compile_model(family.flax_module(cfg, config["dtype"]), params=params,
                                 optimizer="sgd", loss="sparse_categorical_crossentropy",
                                 metrics=[], input_shape=(s["max_prompt_len"],),
                                 input_dtype=jnp.int32)
        engine = InferenceEngine(
            compiled, max_slots=s["max_slots"], max_prompt_len=s["max_prompt_len"],
            max_len=s["max_len"], kv_block_size=s["kv_block_size"],
            prefill_chunk=s["prefill_chunk"])
        p = shapes_on(params, chip)
        cache = shapes_on(engine.pool.cache, chip)

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        table = arg(tuple(engine.pool.device_table().shape), jnp.int32)
        S = s["max_slots"]
        i32 = arg((), jnp.int32)
        rng = arg((2,), jnp.uint32)
        report(f"{name} ({S} slots) jit__chunk_prefill_impl", engine._jit_prefill.lower(
            p, cache, table, arg((1, s["prefill_chunk"]), jnp.int32), i32, i32, i32,
            rng).compile())
        report(f"{name} ({S} slots) jit__paged_decode_impl", engine._jit_decode.lower(
            p, cache, table, arg((S,), jnp.int32), arg((S,), jnp.int32),
            arg((S,), jnp.bool_), arg((S,), jnp.bool_), arg((S,), jnp.int32),
            rng).compile())
        del engine


def resnet18(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.engine.step import init_train_state
    from elephas_tpu.engine.sync import SyncTrainer
    from elephas_tpu.parallel.mesh import DATA_AXIS, build_mesh
    from lib import fit

    with open(os.path.join(BENCH_DIR, "configs", "resnet18-cifar10.json")) as f:
        config = json.load(f)
    compiled = fit.compile_resnet(config, params=None, batch_stats=None)
    mesh = build_mesh(num_data=1, devices=topo.devices[:1])
    trainer = SyncTrainer(compiled, mesh, frequency="epoch")
    batch = config["training"]["batch_size"]
    nb = config["rows"] // batch
    rep = NamedSharding(mesh, P())
    state = shapes_on(jax.eval_shape(lambda: init_train_state(compiled)), rep)
    side, ch = config["image_size"], config["channels"]
    xs = jax.ShapeDtypeStruct((nb, batch, side, side, ch), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, DATA_AXIS)))
    ys = jax.ShapeDtypeStruct((nb, batch, config["num_classes"]), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, DATA_AXIS)))
    epoch = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    report(f"resnet18-cifar10 jit_epoch_fn batch {batch} x {nb} steps",
           trainer._epoch_fn.lower(state, xs, ys, epoch).compile())


def main():
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in sys.argv[1:] or [c["name"] for c in bench["configs"]]:
        if name == "resnet18-cifar10":
            resnet18(topo)
        else:
            serving(topo, [w["name"] for w in bench["workloads"] if w["config"] == name])


if __name__ == "__main__":
    main()
