"""Megabytes one call of a serving program writes in instructions that only
move data (`copy`, `copy-done`, `slice-done`, a `transpose` or `reshape` that
stands alone), from the program's own report of its compiled instructions
(`InferenceEngine.program_report(program).copy_bytes()`; `program` is
`prefill` for `jit__chunk_prefill_impl`, `decode` for `jit__paged_decode_impl`).
A count made at compile time: the same in every run of one program, whatever
the clock reads.

The run's engine is gone by the time a reader runs (`lib/serve.py::conclude`
frees it before the reference), so the report is asked of a second engine of
the same sizes over parameters that are shapes, as `aot_compile.py` builds
one: its pool is real, its weights are not. With the compile cache on, its two
compiles are loads of what the run compiled. The parts the megabytes fall to
(top ten) go to the line's notes (`needed.copies_by_part`). A program from
before it had reports reads nothing.
"""

import time

from lib.cells import log


def _engine(run):
    import jax
    import jax.numpy as jnp

    from elephas_tpu import InferenceEngine, compile_model

    config, s = run.extra["config"], run.serving
    params = jax.eval_shape(
        lambda: run.family.params(0, run.cfg, jnp.dtype(config["dtype"])))
    compiled = compile_model(
        run.family.flax_module(run.cfg, config["dtype"]), params=params, optimizer="sgd",
        loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(s["max_prompt_len"],), input_dtype=jnp.int32)
    return InferenceEngine(
        compiled, max_slots=s["max_slots"], max_prompt_len=s["max_prompt_len"],
        max_len=s["max_len"], queue_depth=s["queue_depth"],
        kv_block_size=s["kv_block_size"], prefill_chunk=s["prefill_chunk"],
        prefill_chunks_per_step=s.get("prefill_chunks_per_step"))


def read(run, program: str):
    from elephas_tpu import InferenceEngine

    if not hasattr(InferenceEngine, "program_report"):
        return None
    if "report_engine" not in run.extra:
        run.extra["report_engine"] = _engine(run)
    t = time.monotonic()
    report = run.extra["report_engine"].program_report(program)
    log(f"program_report({program!r}) of {report.program}: {time.monotonic() - t:.1f} s, "
        f"{len(report.instructions)} instructions")
    run.extra.setdefault("needed", {}).setdefault("copies_by_part", {})[
        report.program] = report.copies_by_part(10)
    return report.copy_bytes() / 1e6
