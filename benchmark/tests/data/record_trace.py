"""Records the small device trace kept beside this file (`small.xplane.pb`).

Run on the chip: a 2-layer TransformerLM through InferenceEngine, a few
requests, under `jax.profiler`. Prints the trace's planes, lines and first
events, so that a reader of `benchmark/lib/xplane.py` can see what it parses.
"""

from __future__ import annotations

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu import InferenceEngine, compile_model, obs
    from elephas_tpu.models import get_model

    out = os.path.join("chiprun_out", "trace_small")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    tracer = obs.enable_tracing()
    lm = dict(vocab_size=512, d_model=128, num_heads=4, num_layers=2,
              max_seq_len=128)
    compiled = compile_model(
        get_model("transformer_lm", dtype="bfloat16", **lm),
        optimizer="sgd", loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(16,), input_dtype=jnp.int32, seed=0)
    engine = InferenceEngine(compiled, max_slots=4, max_prompt_len=32,
                             max_len=64, kv_block_size=16, prefill_chunk=16,
                             prefill_chunks_per_step=1, tracer=tracer)
    rng = np.random.default_rng(0)

    def burst(n):
        ids = [engine.submit(rng.integers(0, 512, 20).tolist(),
                             max_new_tokens=6) for _ in range(n)]
        return [engine.result(i) for i in ids]

    burst(4)
    jax.block_until_ready(engine.pool.cache)
    print("memory_stats keys:", sorted((jax.devices()[0].memory_stats() or {}).keys()))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    from lib.xplane import TraceCapture, find_xplane

    capture = TraceCapture(out)  # the benchmark's own capture: marks, no Python frames
    capture.start()
    burst(6)
    jax.block_until_ready(engine.pool.cache)
    capture.stop()
    print("marks", capture.marks, "sched_step spans",
          [(e.begin_s, e.end_s) for e in tracer.events() if e.name == "sched_step"][-3:])
    path = find_xplane(out)
    print("trace", path, os.path.getsize(path), "bytes")
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    data = jax.profiler.ProfileData.from_file(os.path.join(out, "small.xplane.pb"))
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), "lines")
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for ev in events[:6]:
                stats = {k: v for k, v in list(ev.stats)[:12]}
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns, stats)


if __name__ == "__main__":
    main()
