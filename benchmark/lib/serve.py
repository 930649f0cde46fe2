"""What the two serving drivers share: the engine built from a cell's
files, its warm-up, what the program's own events and spans say each step
and chunk held, and `correct`. The language model is the family that the
configuration file names (`"model"`), found as `models/<family>.py`: its
sizes, its flax module, its weights from the seed, its plain reference's
name and its needed work. Nothing here names a family.
"""

from __future__ import annotations

import gc
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import traffic as traffic_lib
from .cells import CHECKOUT, Cell, device_report, log, process_age_s
from .stats import check, percentile
from .window import StepSink
from .xplane import TraceCapture


@dataclass
class Sent:
    index: int
    req_id: int
    due: Optional[float]      # monotonic; None in a closed backlog
    submitted: float
    prompt: List[int]
    result: object = None


@dataclass
class Serving:
    """One run's state: what the readers read."""
    cell: Cell
    family: object            # `models/<family>.py`
    cfg: dict                 # the family's `shape` of the sized configuration
    serving: dict
    engine: object
    sink: StepSink
    schedule: Optional[traffic_lib.Schedule] = None
    sent: List[Sent] = field(default_factory=list)
    by_id: Dict[int, Sent] = field(default_factory=dict)
    window: object = None
    setup_s: float = 0.0
    lateness_s: List[float] = field(default_factory=list)
    capture: Optional[TraceCapture] = None
    trace: object = None
    spans: list = field(default_factory=list)
    # (begin, start, valid) of every `step/prefill_chunk` span
    chunk_spans: list = field(default_factory=list)
    peak: dict = None
    extra: dict = field(default_factory=dict)

    def submit(self, i: int, due: Optional[float]) -> None:
        prompt = self.schedule.prompt(i)
        now = time.monotonic()
        rid = self.engine.submit(prompt, max_new_tokens=self.schedule.output_lens[i],
                                 stop_token=None)
        s = Sent(i, rid, due, now, prompt)
        self.sent.append(s)
        self.by_id[rid] = s
        if due is not None:
            self.lateness_s.append(now - due)

    def step(self) -> None:
        for r in self.engine.step():
            if r.req_id in self.by_id:
                self.by_id[r.req_id].result = r

    def drain_device(self) -> None:
        import jax

        jax.block_until_ready(self.engine.pool.cache)


def build(cell: Cell, seed: int, rehearse: bool, trace: bool) -> Serving:
    import jax.numpy as jnp

    from elephas_tpu import InferenceEngine, compile_model, obs

    config = cell.sized(cell.config, rehearse)
    tr = cell.sized(cell.traffic, rehearse)
    family = cell.module("models", config["model"])
    cfg, serving = family.shape(config), {**config["serving"], **tr["engine"]}
    dtype = jnp.dtype(config["dtype"])
    tracer = obs.enable_tracing(capacity=1 << 20) if trace else None
    params = family.params(seed, cfg, dtype)
    module = family.flax_module(cfg, config["dtype"])
    compiled = compile_model(module, params=params, optimizer="sgd",
                             loss="sparse_categorical_crossentropy", metrics=[],
                             input_shape=(serving["max_prompt_len"],), input_dtype=jnp.int32)
    sink = StepSink(time.monotonic)
    engine = InferenceEngine(
        compiled, max_slots=serving["max_slots"], max_prompt_len=serving["max_prompt_len"],
        max_len=serving["max_len"], queue_depth=serving["queue_depth"],
        kv_block_size=serving["kv_block_size"], prefill_chunk=serving["prefill_chunk"],
        prefill_chunks_per_step=serving.get("prefill_chunks_per_step"),
        sink=sink, tracer=tracer)
    run = Serving(cell, family, cfg, serving, engine, sink)
    run.extra["traffic"] = tr
    run.extra["rehearse"] = rehearse
    run.extra["config"] = config
    return run


def plan_requests(run: Serving, seed: int, requests: int) -> None:
    """The run's schedule: at least `requests` requests, in whole cycles."""
    run.schedule = traffic_lib.make_schedule(run.extra["traffic"], seed,
                                             run.cfg["vocab_size"], requests)


def warm_up(run: Serving, seed: int) -> None:
    """Both programs, at the one shape each has: a prompt of a chunk and a
    bit, a few tokens, on every slot at once so that the lane fetch sees
    its widest case."""
    rng = np.random.default_rng([seed, 2])
    plen = min(run.serving["prefill_chunk"] + 3, run.serving["max_prompt_len"])
    ids = [run.engine.submit(rng.integers(0, run.cfg["vocab_size"], plen).tolist(),
                             max_new_tokens=4, stop_token=None)
           for _ in range(run.serving["max_slots"])]
    for rid in ids:
        run.engine.result(rid, timeout_s=1100)
    run.drain_device()
    stats = run.engine.stats()
    if stats["prefill_traces"] != 1 or stats["decode_traces"] != 1:
        raise RuntimeError(f"warm-up traced {stats['prefill_traces']} prefill and "
                           f"{stats['decode_traces']} decode programs, not one each")
    run.sink.steps.clear()
    run.sink.requests.clear()


def open_window(run: Serving) -> float:
    """Set-up ends here. The heap as it stands (a quarter of a million
    objects: the imports, the schedule, the warm-up's) is collected once and
    frozen, so that a full pass of the interpreter's collector inside the
    window scans what the window allocated and no more. Unfrozen, one such
    pass falls in every window at the same count of allocations and holds
    every live request for 90-120 ms (PERF.md, Findings, PR 29): a tail then
    reads where that pass happens to land. The collector stays on."""
    gc.collect()
    gc.freeze()
    run.setup_s = process_age_s()
    return time.monotonic()


def trace_length(run: Serving, trace: bool) -> Optional[float]:
    """Makes the capture of a traced run and returns the length of its
    traced part, a few seconds of the window. The driver says where they
    begin; the profiler is stopped after the window has closed, because
    stopping stalls the host for many seconds."""
    if not trace:
        return None
    run.capture = TraceCapture(os.path.join(CHECKOUT, ".bench_trace", run.cell.name))
    return run.extra["traffic"]["trace"]["seconds"]


def close_trace(run: Serving) -> None:
    """As the window closes: the traced part ends here."""
    if run.capture is not None:
        if not run.capture.active:
            raise RuntimeError("the window ended before the traced part began")
        run.capture.mark_close()


def finish_trace(run: Serving) -> None:
    if run.capture is None:
        return
    run.capture.stop()
    from elephas_tpu import obs

    tracer = obs.default_tracer()
    events = tracer.events()
    if tracer.dropped:
        raise RuntimeError(f"the tracer dropped {tracer.dropped} events: its spans are not whole")
    run.spans = [(e.name, e.begin_s, e.end_s) for e in events]
    run.chunk_spans = [(e.begin_s, e.args["start"], e.args["valid"])
                       for e in events if e.name == "step/prefill_chunk"]
    try:
        run.trace = run.capture.result()
    except ValueError:
        if not run.extra["rehearse"]:  # a CPU rehearsal has no device plane
            raise


def lengths_before_each_step(run: Serving, t0: float, t1: float):
    """For each decode step dispatched in [t0, t1]: the cache columns each
    of its lanes held before it, as the program's `step` event says
    (`lane_lengths`; a step dispatches its decode as it begins, at the
    event's `t - step_seconds`); and (start, valid) of each chunk dispatched
    in [t0, t1], from the `step/prefill_chunk` spans of a traced run."""
    steps = [list(s["lane_lengths"]) for s in run.sink.steps
             if s["lane_lengths"] and t0 <= s["t"] - s["step_seconds"] <= t1]
    chunk_calls = [(start, valid) for t, start, valid in run.chunk_spans if t0 <= t <= t1]
    return steps, chunk_calls


# -- correct ---------------------------------------------------------------


def sample_finished(run: Serving, seed: int, count: int) -> List[Sent]:
    done = [s for s in run.sent if s.result is not None and s.result.status == "completed"
            and s.result.tokens]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.prompt) + len(s.result.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 3])
    picks = [rest[i] for i in rng.permutation(len(rest))[: max(0, count - 1)]]
    return [longest] + picks


def reference_gaps(run: Serving, seed: int, sample: List[Sent], control: bool = False) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, and the mean of those gaps, over the sampled
    requests; with `control`, the same for the token that the reference
    computed in float8 puts first."""
    import jax
    import jax.numpy as jnp

    family = run.family
    ref = run.cell.module("references", family.REFERENCE)
    cfg, T = run.cfg, run.serving["max_len"]
    width = max(len(s.result.tokens) for s in sample)
    tokens = np.zeros((len(sample), T), np.int32)
    rows = np.zeros((len(sample), width), np.int32)
    served = np.zeros((len(sample), width), np.int32)
    mask = np.zeros((len(sample), width), bool)
    for i, s in enumerate(sample):
        out, plen = s.result.tokens, len(s.prompt)
        seq = (s.prompt + out[:-1])[:T]
        tokens[i, : len(seq)] = seq
        rows[i, : len(out)] = np.arange(plen - 1, plen - 1 + len(out))
        served[i, : len(out)], mask[i, : len(out)] = out, True
    dtype = jnp.dtype(run.extra["config"]["dtype"])
    top, layers = family.top_at(seed, cfg, dtype), family.layers(cfg)

    def block_at(layer):
        return family.block_at(seed, layer, cfg, dtype)

    logits = ref.logits_at(jnp.asarray(tokens), jnp.asarray(rows), top, block_at, layers)
    best = logits.max(-1)

    def gap_of(tok):
        at = jnp.take_along_axis(logits, jnp.asarray(tok)[:, :, None], axis=-1)[..., 0]
        return np.where(mask, np.asarray(best - at), 0.0)

    gaps = gap_of(served)
    out = {"logit_gap_max": float(gaps.max()),
           "logit_gap_mean": float(gaps.sum() / mask.sum()), "tokens_compared": int(mask.sum()),
           "requests_compared": len(sample),
           "tokens_off_best": int((gaps > 0).sum())}
    if control:
        low = ref.logits_at(jnp.asarray(tokens), jnp.asarray(rows), top, block_at,
                            layers, quant=ref.fp8)
        low_gaps = gap_of(np.asarray(low.argmax(-1)))
        out["control_logit_gap_max"] = float(low_gaps.max())
        out["control_logit_gap_mean"] = float(low_gaps.sum() / mask.sum())
    jax.clear_caches()
    return out


def free_program(run: Serving) -> None:
    """Drop the engine, its pool and its weights before the reference runs."""
    run.engine = None
    gc.unfreeze()  # `open_window` froze the heap, the engine's cycles with it
    gc.collect()


def conclude(run: Serving, args, attempted: int, failed: int, end_to_end: dict,
             checks: dict) -> dict:
    """What both drivers do once the window is closed: read the memory
    peak, free the program, run the reference over a sample of what was
    served, and hand the readers their run."""
    import jax

    devices = jax.devices()[: run.cell.chips]
    run.peak = device_report(devices, run.trace)
    count = run.extra["traffic"]["check"]["requests"]
    sample = sample_finished(run, args.seed, count)
    free_program(run)
    t, controls = time.monotonic(), {}
    if sample:
        gaps = reference_gaps(run, args.seed, sample, control=bool(args.control))
        # the mean over the compared tokens: the widest gap (in the notes)
        # swings too much to keep the program and the control apart
        limit = run.extra["traffic"]["check"]["logit_gap_mean_limit"]
        checks["logit_gap_mean"] = check(gaps["logit_gap_mean"], limit, "at_most")
        if "control_logit_gap_mean" in gaps:
            # the float8 reference's tokens in the served ones' place, held
            # to the same limit: `run.py` fails a run whose control passes
            controls["float8_reference"] = {"logit_gap_mean": check(
                gaps["control_logit_gap_mean"], limit, "at_most")}
        run.extra["reference"] = gaps
    checks["requests_sampled"] = check(len(sample), 1, "at_least")
    run.extra["reference_s"] = time.monotonic() - t
    log(f"reference over {len(sample)} requests took {run.extra['reference_s']:.1f} s")
    notes = {"setup_s": run.setup_s, "reference_s": run.extra["reference_s"],
             "window_s": run.window.seconds, "prompt_tokens": run.window.prompt_tokens,
             "output_tokens": run.window.output_tokens, "steps": len(run.window.steps),
             "chunk_calls": run.window.chunk_calls,
             "reference": run.extra.get("reference"),
             # filled by the roofline and MFU readers of a traced run: the
             # needed seconds and FLOPs they stood on
             "needed": run.extra.setdefault("needed", {})}
    for key in ("backlog_at_close", "generator_late_ms_p95", "generator_late_ms_max"):
        if key in run.extra:
            notes[key] = run.extra[key]
    if "tpot_ms" in run.extra:
        notes["tpot_ms_p50"] = end_to_end.get("tpot_ms_p50")
        notes["tpot_ms_p95"] = end_to_end.get("tpot_ms_p95")
        notes["ttft_ms_p50"] = percentile(run.extra["ttft_ms"], 50)
        notes["ttft_ms_p95"] = percentile(run.extra["ttft_ms"], 95)
    steps = run.window.steps
    if steps:
        notes["occupancy"] = sum(s["active_slots"] for s in steps) / (
            len(steps) * run.serving["max_slots"])
        notes["queue_depth_max"] = max(s["queue_depth"] for s in steps)
    return {"run": run, "attempted": attempted, "failed": failed, "end_to_end": end_to_end,
            "checks": checks, "controls": controls, "device": run.peak, "notes": notes}
