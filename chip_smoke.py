"""Does the program still start, run and finish on the attached TPU?

One process, phases in sequence, exit non-zero at the first failure:

- *device*: a TPU, its kind known to ``metrics.flops.peak_flops``;
- *train*: ResNet-18 at full width through ``SparkModel.fit`` —
  synchronous per-batch, then hogwild over the HBM-resident buffer;
- *serve*: ``TransformerLM`` at GPT-2 small's widths through
  ``InferenceEngine`` behind ``serve_forever``;
- *kernels*: ``flash_attention`` forward + gradient on the Pallas branch;
- *sync check* (informative): ``block_until_ready`` against a scalar fetch.

Only on success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--chips 4`` runs instead the device check and the data-parallel fits
across four chips, compared with a one-device mesh in the same process.
``--rehearse`` walks the same code at tiny sizes on whatever backend
there is, to find wrong paths before chip time is spent; it never prints
``"ok": true`` and never exits 0.

No benchmark: the seconds printed on earlier lines are for finding
faults, not for comparing commits.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

REHEARSAL_EXIT = 3

# Published widths everywhere; --rehearse cuts them so a CPU finishes.
REAL = dict(
    resnet_width=64, rows=4096, batch=512, epochs=3, predict_rows=1024,
    lm=dict(vocab_size=50257, d_model=768, num_heads=12, num_layers=12,
            max_seq_len=1024),
    max_slots=8, max_prompt_len=256, max_len=512, requests=16,
    prompt_lens=(16, 256), new_tokens=32,
    attn_shape=(1, 8, 4096, 64), sync_steps=10,
)
TINY = dict(
    resnet_width=8, rows=512, batch=64, epochs=3, predict_rows=64,
    lm=dict(vocab_size=211, d_model=32, num_heads=4, num_layers=2,
            max_seq_len=64),
    max_slots=4, max_prompt_len=16, max_len=32, requests=8,
    prompt_lens=(2, 16), new_tokens=8,
    attn_shape=(1, 2, 256, 16), sync_steps=3,
)


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how the
    persistent cache answered, since the last ``take()``."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._totals: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _add(self, key, amount):
        with self._lock:
            self._totals[key] = self._totals.get(key, 0) + amount

    def _on_duration(self, event, seconds, **_):
        if event in self._DURATIONS:
            self._add(self._DURATIONS[event], seconds)
            if event.endswith("backend_compile_duration"):
                self._add("programs", 1)

    def _on_event(self, event, **_):
        if event in self._EVENTS:
            self._add(self._EVENTS[event], 1)

    def take(self) -> dict:
        with self._lock:
            totals, self._totals = self._totals, {}
        return {k: round(v, 3) if isinstance(v, float) else v
                for k, v in sorted(totals.items())}


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, device):
    """Time one phase; any exception ends the run with exit code 1."""
    say(phase=name, status="start")
    clock.take()
    t0 = time.perf_counter()
    try:
        yield
    except Exception:
        traceback.print_exc()
        say(phase=name, ok=False, seconds=round(time.perf_counter() - t0, 3))
        sys.exit(1)
    stats = device.memory_stats() or {}
    say(phase=name, ok=True, seconds=round(time.perf_counter() - t0, 3),
        compile=clock.take(), peak_bytes_in_use=stats.get("peak_bytes_in_use"))


def check(condition: bool, message: str) -> None:
    if not condition:
        raise AssertionError(message)


def leaf_devices(tree) -> set:
    """``(platform, id)`` of every device holding a leaf; ``("host",
    None)`` for a leaf that is not a ``jax.Array`` at all."""
    import jax

    found = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            found.update((d.platform, d.id) for d in leaf.devices())
        else:
            found.add(("host", None))
    return found


# -- train -----------------------------------------------------------------


def separable_images(size: dict, seed: int):
    """Seeded rows made separable — a per-class pattern plus noise — so
    the loss must fall within a few steps."""
    rng = np.random.default_rng(seed)
    patterns = rng.normal(size=(10, 32, 32, 3))
    labels = rng.integers(0, 10, size=size["rows"])
    x = patterns[labels] + 0.5 * rng.normal(size=(size["rows"], 32, 32, 3))
    return x.astype(np.float32), np.eye(10, dtype=np.float32)[labels]


def resnet18(size: dict):
    from elephas_tpu import compile_model
    from elephas_tpu.models import get_model

    return compile_model(
        get_model("resnet18", num_classes=10, width=size["resnet_width"],
                  dtype="bfloat16"),
        optimizer={"name": "momentum", "learning_rate": 0.1},
        loss="categorical_crossentropy",
        metrics=["acc"],
        input_shape=(32, 32, 3),
    )


def fit_resnet(size, x, y, platform, workers, mesh=None,
               loss_must_fall=True, **mode):
    """One ``SparkModel.fit`` + ``predict``; returns the loss history and
    the devices that held the state at the last epoch's end."""
    from elephas_tpu import SparkModel, to_simple_rdd

    seen = []

    def placement(epoch, state, metrics):
        # sync: the live replicated state; hogwild: a snapshot pulled
        # from the parameter buffer at the epoch barrier
        seen.append(leaf_devices(state.params))

    model = SparkModel(resnet18(size), num_workers=workers, mesh=mesh, **mode)
    t0 = time.perf_counter()
    history = model.fit(
        to_simple_rdd(None, x, y, workers), epochs=size["epochs"],
        # fit's batch_size is per worker; size["batch"] is the global batch
        batch_size=size["batch"] // workers, callbacks=[placement],
    )
    fit_s = time.perf_counter() - t0
    losses = history["loss"]
    steps = size["epochs"] * (size["rows"] // size["batch"])
    preds = model.predict(x[: size["predict_rows"]])
    say(fit=mode, workers=workers, steps=steps, fit_seconds=round(fit_s, 3),
        loss_per_epoch=losses, acc_per_epoch=history.get("acc"),
        state_devices=sorted(seen[-1]))
    check(len(losses) == size["epochs"] and np.all(np.isfinite(losses)),
          f"losses not finite: {losses}")
    check(losses[-1] < losses[0] or not loss_must_fall,
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(preds.shape == (size["predict_rows"], 10) and
          np.all(np.isfinite(preds)), f"predict gave {preds.shape}")
    check(len(seen) == size["epochs"] and
          all(kind == platform for devices in seen for kind, _ in devices),
          f"state or buffer leaves off the {platform}: {seen}")
    return losses, seen[-1]


SYNC = dict(mode="synchronous", frequency="batch")
HOGWILD = dict(mode="hogwild", parameter_server_mode="local")


def train_phase(size, seed, platform):
    x, y = separable_images(size, seed)
    fit_resnet(size, x, y, platform, 1, **SYNC)
    fit_resnet(size, x, y, platform, 1, **HOGWILD)


def four_chip_phase(size, seed, platform):
    """Data-parallel fits over four chips against a one-device mesh."""
    import jax

    from elephas_tpu.parallel.mesh import build_mesh

    x, y = separable_images(size, seed)
    four, on_four = fit_resnet(size, x, y, platform, 4, **SYNC)
    one, on_one = fit_resnet(
        size, x, y, platform, 1,
        mesh=build_mesh(num_data=1, devices=jax.devices()[:1]), **SYNC)
    gap = abs(four[-1] - one[-1])
    say(sync_final_loss={"four_chips": four[-1], "one_chip": one[-1]},
        abs_gap=gap)
    check(len(on_four) == 4 and len(on_one) == 1,
          f"sync state on devices {on_four} / {on_one}")
    # per-shard BatchNorm statistics differ between 4×128 and 1×512, so
    # the runs agree in where they end, not bit for bit
    check(gap < 0.05 + 0.5 * max(four[-1], one[-1]),
          f"final losses differ by {gap}")
    batch_devices, worker_devices = four_chip_placement(size, x, y)
    say(batch_shard_devices=batch_devices, hogwild_worker_devices=worker_devices)
    check(len(set(batch_devices)) == 4, "batch shards share a device")
    check(len(set(worker_devices)) == 4, "hogwild workers share a device")
    # Four workers' summed deltas at this learning rate need not descend
    # in three epochs: the smoke tests the path, not the optimizer. The
    # buffer snapshot it reports sits on one device by design
    # (parameter/buffer.py: jax.devices()[0]).
    fit_resnet(size, x, y, platform, 4, loss_must_fall=False, **HOGWILD)


def four_chip_placement(size, x, y):
    """Where a global batch's shards and the hogwild workers land, made
    by the calls ``SparkModel.fit`` makes: ``SyncTrainer.fit``'s
    ``stack_epoch`` + ``device_put``, ``AsyncTrainer``'s worker list."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.engine.async_engine import AsyncTrainer
    from elephas_tpu.engine.sync import stack_epoch
    from elephas_tpu.parallel.mesh import DATA_AXIS, build_mesh

    mesh = build_mesh(num_data=4)
    xs, _, _ = stack_epoch(x, y, 4, size["batch"] // 4)
    xs = jax.device_put(xs, NamedSharding(mesh, P(None, DATA_AXIS)))
    shards = sorted(s.device.id for s in xs.addressable_shards)
    trainer = AsyncTrainer(resnet18(size), mesh, lock=False,
                           parameter_server_mode="local")
    return shards, sorted(d.id for d in trainer.devices)


# -- serve -----------------------------------------------------------------


def serve_phase(size, seed, platform):
    import jax.numpy as jnp

    from elephas_tpu import InferenceEngine, compile_model
    from elephas_tpu.models import get_model
    from elephas_tpu.models.transformer import generate

    lm = size["lm"]
    t0 = time.perf_counter()
    compiled = compile_model(
        get_model("transformer_lm", dtype="bfloat16", **lm),
        optimizer={"name": "adam", "learning_rate": 1e-4},
        loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(size["max_prompt_len"],), input_dtype=jnp.int32,
        seed=seed,
    )
    engine = InferenceEngine(
        compiled, max_slots=size["max_slots"],
        max_prompt_len=size["max_prompt_len"], max_len=size["max_len"],
    )
    say(lm=lm, params=compiled.count_params(),
        build_seconds=round(time.perf_counter() - t0, 3))

    rng = np.random.default_rng(seed)
    lo, hi = size["prompt_lens"]
    lengths = [lo, hi] + rng.integers(lo, hi + 1, size["requests"] - 2).tolist()
    prompts = [rng.integers(0, lm["vocab_size"], n).tolist() for n in lengths]
    new = size["new_tokens"]

    stop = threading.Event()
    server_error = []

    def serve():
        try:
            engine.serve_forever(stop)
        except Exception as exc:  # surfaced by the waiting client below
            server_error.append(exc)

    thread = threading.Thread(target=serve, name="serve_forever")
    thread.start()
    try:
        t0 = time.perf_counter()
        half = len(prompts) // 2
        ids = [engine.submit(p, max_new_tokens=new) for p in prompts[:half]]
        # the second half arrives while the first is decoding: step 1
        # prefills (and compiles), step 2 compiles the decode program
        while engine.stats()["steps"] < 3 and not server_error:
            check(time.perf_counter() - t0 < 900, "no decode step in 900 s")
            time.sleep(0.001)
        first_steps_s = time.perf_counter() - t0
        still_decoding = engine.stats()["pool_active"]
        ids += [engine.submit(p, max_new_tokens=new) for p in prompts[half:]]
        results = []
        for rid in ids:
            while True:
                check(not server_error, f"serve thread died: {server_error}")
                try:
                    results.append(engine.result(rid, timeout_s=5.0))
                    break
                except TimeoutError:
                    check(time.perf_counter() - t0 < 900,
                          f"request {rid} not done in 900 s")
        serve_s = time.perf_counter() - t0
    finally:
        stop.set()
        thread.join(timeout=60)
    check(not thread.is_alive(), "serve thread did not stop")

    stats = engine.stats()
    say(requests=len(results), prompt_tokens=lengths,
        statuses=sorted({r.status for r in results}),
        seconds_to_third_step_compile_included=round(first_steps_s, 3),
        decoding_when_second_half_arrived=still_decoding,
        serve_seconds=round(serve_s, 3),
        **{k: stats[k] for k in (
            "prefill_traces", "decode_traces", "tokens_out", "steps",
            "max_concurrent", "ttft_s_avg", "itl_s_avg", "kv_blocks_total",
            "kv_blocks_free", "prefix_hit_rate")})
    for r in results:
        check(r.status == "completed", f"request {r.req_id}: {r.status}")
        check(len(r.tokens) == new and
              all(0 <= t < lm["vocab_size"] for t in r.tokens),
              f"request {r.req_id}: {len(r.tokens)} tokens")
    check(stats["prefill_traces"] == 1 and stats["decode_traces"] == 1,
          f"retraced: prefill {stats['prefill_traces']}, "
          f"decode {stats['decode_traces']}")
    pool_devices = leaf_devices(engine.pool.cache)
    check({kind for kind, _ in pool_devices} == {platform},
          f"KV pool leaves on {pool_devices}")

    # A finding, not a pass condition: a batched and a per-row bf16
    # matmul may round differently on the TPU and flip a greedy argmax.
    t0 = time.perf_counter()
    reference = np.asarray(generate(compiled, prompts, new))[:, -new:]
    same = [list(ref) == r.tokens for ref, r in zip(reference, results)]
    agree = [int(np.argmin(np.append(np.asarray(r.tokens) == ref, False)))
             for ref, r in zip(reference, results)]
    say(token_identity_with_generate=dict(
        identical_rows=sum(same), rows=len(same),
        share=sum(same) / len(same), tokens_agreeing_before_first_flip=agree),
        generate_seconds=round(time.perf_counter() - t0, 3))


# -- kernels ---------------------------------------------------------------


def kernels_phase(size, seed, platform):
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.attention import _blockwise_reference, flash_attention
    from elephas_tpu.ops.attention_pallas import default_blocks

    shape = size["attn_shape"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16) for key in keys)
    block_q, block_k = default_blocks(shape[2])

    def reference(q, k, v):
        return _blockwise_reference(q, k, v, True, block_q, block_k)

    def weighted(attention):
        def loss(q, k, v):
            out = attention(q, k, v).astype(jnp.float32)
            return (out * w.astype(jnp.float32)).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    flash = weighted(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    ).lower(q, k, v).compile()
    custom_calls = flash.as_text().count("tpu_custom_call")
    (_, out), grads = flash(q, k, v)
    (_, ref_out), ref_grads = weighted(reference)(q, k, v)

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errors = {"out": rel_err(out, ref_out)}
    errors.update({f"d{name}": rel_err(g, r)
                   for name, g, r in zip("qkv", grads, ref_grads)})
    say(flash_attention=dict(shape=shape, dtype="bfloat16", causal=True,
                             blocks=(block_q, block_k)),
        tpu_custom_calls_in_program=custom_calls, relative_l2_error=errors)
    check(all(np.isfinite(e) and e < 2e-2 for e in errors.values()),
          f"flash_attention off its reference: {errors}")
    if platform == "tpu":
        check(custom_calls >= 3,
              "the dispatch took the XLA path: no Pallas forward, dq and "
              f"dk/dv kernels in the program ({custom_calls} custom calls)")


# -- sync check ------------------------------------------------------------


def sync_check_phase(size, seed, platform):
    """Is ``block_until_ready`` honest here? The code anchors timings on
    a scalar fetch because, through an earlier installation's link to
    the chip, it returned before the work had finished."""
    import jax

    from elephas_tpu.engine.step import init_train_state, make_train_step

    compiled = resnet18(size)
    x, y = separable_images(dict(size, rows=size["batch"]), seed)
    device = jax.devices()[0]
    x, y = jax.device_put(x, device), jax.device_put(y, device)
    state = jax.device_put(init_train_state(compiled), device)
    step = jax.jit(make_train_step(compiled), donate_argnums=(0,))
    state, metrics = step(state, x, y)
    float(metrics["loss"])

    steps = size["sync_steps"]
    by_block, late_fetch, by_fetch = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, x, y)
        jax.block_until_ready((state, metrics))
        t1 = time.perf_counter()
        float(metrics["loss"])  # long only if the wait above returned early
        t2 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, x, y)
        float(metrics["loss"])
        t3 = time.perf_counter()
        by_block.append(t1 - t0)
        late_fetch.append(t2 - t1)
        by_fetch.append(t3 - t2)
    block, late, fetch = (float(np.median(t)) for t in
                          (by_block, late_fetch, by_fetch))
    say(sync_check=dict(
        steps=steps, batch=size["batch"], readings=5,
        ended_by_block_until_ready_s=block, fetch_after_block_s=late,
        ended_by_scalar_fetch_s=fetch, block_over_fetch=block / fetch,
        block_until_ready_is_honest=bool(block > 0.9 * fetch
                                         and late < 0.1 * fetch)))


# -- main ------------------------------------------------------------------


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on any backend; never reports ok")
    args = parser.parse_args()
    size = TINY if args.rehearse else REAL

    import jax

    from elephas_tpu import native
    from elephas_tpu.metrics.flops import peak_flops
    from elephas_tpu.utils.compiler import configure_compile_cache

    cache_dir = configure_compile_cache()
    clock = CompileClock()

    devices = jax.devices()
    dev = devices[0]
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    # stdout stays empty when there is no chip: nothing to mistake for a result
    if not args.rehearse:
        if dev.platform != "tpu":
            sys.exit(f"chip_smoke: no TPU — JAX reports {report}")
        if peak_flops() is None:
            sys.exit(f"chip_smoke: metrics.flops.PEAK_FLOPS has no entry "
                     f"matching device_kind {dev.device_kind!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX reports {report}")
    say(phase="device", **report, peak_flops=peak_flops(),
        jax=jax.__version__, compile_cache_dir=cache_dir,
        native_available=native.available())

    if args.chips == 4:
        phases = [("four_chips", four_chip_phase)]
    else:
        phases = [("train", train_phase), ("serve", serve_phase),
                  ("kernels", kernels_phase), ("sync_check", sync_check_phase)]
    t0 = time.perf_counter()
    for name, run in phases:
        with phase(name, clock, dev):
            run(size, args.seed, dev.platform)
    cache_files = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(total_seconds=round(time.perf_counter() - t0, 3),
        compile_cache_files=cache_files)
    if args.rehearse:
        say(ok=False, rehearsal=True, device=report)
        sys.exit(REHEARSAL_EXIT)
    say(ok=True, device=report)


if __name__ == "__main__":
    main()
