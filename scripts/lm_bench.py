"""LM decode throughput bench: KV-cache generate vs no-cache re-forward,
plus the serving engine end-to-end (continuous batching over the slot
pool).

Emits one JSON object per measurement so the numbers land as a committed
artifact (``--out BENCH_DECODE.json``):

- ``{"mode": "cache" | "no_cache", "batch": B, ...}`` — tokens/sec of
  batch-B greedy decode. EVERY row carries ``flops_per_token`` (from
  ``metrics.flops.transformer_flops_per_token``) so the achieved-FLOPs
  math is reproducible from the artifact alone, and ``mfu`` when the
  chip's peak FLOPs are known (None on CPU — see
  ``metrics.flops.peak_flops``),
- ``{"mode": "serving", "pipeline": bool, ...}`` — the
  ``InferenceEngine`` driven over a mixed-length workload with
  mid-decode admission, one arm per scheduler mode (unpipelined
  reference vs one-step-lookahead), so the artifact shows the
  before/after of pipelining directly; reports engine tokens/sec, TTFT,
  dispatch→fetch overlap, prefill/decode compile counts. The serving
  arms also land in their own artifact via ``--serve-out
  BENCH_SERVE.json``,
- ``{"mode": "serving_spec", ...}`` (``--spec``) — speculative
  draft-and-verify decode vs the unspeculated oracle on a
  shared-prefix workload: accept rate, realized tokens/step, the
  per-token spec/plain ITL ratio, token identity, and the
  compile-counter pins (one draft + one verify program), with draft
  params delivered by a real 2-shard parameter-server group,
- ``{"mode": "fleet_*", ...}`` (``--fleet`` → ``--fleet-out
  BENCH_FLEET.json``) — the replicated fleet: routed-vs-bare overhead
  with token-identity proof, N-replica session-affinity throughput,
  the kill-a-replica-mid-traffic chaos arm (fleet-plane outage arc,
  blackbox canary outage, goodput dip, requeue recovery), and the
  autoscaler's seeded decision replay. Gated by scripts/bench_gate.py
  ``--fleet``,
- ``{"mode": "fleet_disagg", ...}`` (``--disagg``, appends to the
  fleet artifact) — disaggregated prefill/decode tiers vs a monolithic
  fleet on the same two-tenant interference workload: token identity
  across the KV-block handoff, the decode-tier ITL p99 ratio under
  long-prompt interference, handoff latency p50/p99, the cross-tier
  prefix hit rate, and the per-tenant fair-share goodput floor.
- ``{"mode": "fleet_rollout", ...}`` (``--rollout``, appends to the
  fleet artifact) — live model delivery: mid-stream zero-delta swap
  identity + swap-tax ITL ratio, steady-state subscription wire cost,
  and a full canary arc (live trainer push → promote, then a forced
  rollback with zero non-canary exposure to the poisoned version).

Importable (and runnable with tiny defaults) without a TPU — tier-1
collects it. Only the meta row names the backend: every other row
carries whatever it ran on under device-metric names, and every
committed artifact it wrote is a CPU run. Not an on-chip measurement
until ROADMAP S0 rebuilds it; ``chip_smoke.py`` is what runs on the chip.

Usage: python scripts/lm_bench.py [--batches 1 8 32] [--new 64]
       [--out BENCH_DECODE.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(vocab: int, d_model: int, heads: int, layers: int,
                max_seq: int):
    import jax.numpy as jnp

    from elephas_tpu.api.compile import CompiledModel
    from elephas_tpu.models.transformer import TransformerLM

    module = TransformerLM(
        vocab_size=vocab, d_model=d_model, num_heads=heads,
        num_layers=layers, max_seq_len=max_seq,
    )
    return CompiledModel(
        module,
        optimizer="adam",
        loss="sparse_categorical_crossentropy",
        input_shape=(16,),
        input_dtype=jnp.int32,
    )


def flops_per_decode_token(compiled, context_len: int) -> float:
    from elephas_tpu.metrics import transformer_flops_per_token

    m = compiled.module
    return transformer_flops_per_token(
        compiled.count_params(), m.num_layers, m.d_model, context_len
    )


def bench_generate(compiled, batch: int, prompt_len: int, new_tokens: int,
                   use_cache: bool, reps: int) -> dict:
    """Tokens/sec of batch-B greedy decode, cache vs no-cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.metrics import mfu
    from elephas_tpu.models.transformer import generate

    rng = np.random.default_rng(0)
    vocab = compiled.module.vocab_size
    prompt = rng.integers(1, vocab, (batch, prompt_len)).astype(np.int32)

    if use_cache:
        run = lambda: generate(compiled, prompt, new_tokens)  # noqa: E731
    else:
        # No-cache baseline: re-forward the growing sequence per token
        # (the quadratic loop KV caching exists to remove).
        fwd = jax.jit(
            lambda params, toks: compiled.module.apply(
                {"params": params}, toks
            )
        )

        def run():
            toks = jnp.asarray(prompt)
            for _ in range(new_tokens):
                logits = fwd(compiled.params, toks)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
            return toks

    jax.block_until_ready(run())  # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run()
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    tps = batch * new_tokens / dt
    fpt = flops_per_decode_token(compiled, prompt_len + new_tokens)
    return {
        "mode": "cache" if use_cache else "no_cache",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "sec_per_rep": dt,
        "tokens_per_sec": tps,
        "flops_per_token": fpt,
        "mfu": mfu(tps, fpt),
    }


def bench_serving(compiled, max_slots: int, prompt_len: int,
                  new_tokens: int, requests: int,
                  pipeline: bool = True, tracer=None) -> dict:
    """Drive the InferenceEngine over a mixed-length workload: more
    requests than slots, staggered submits, so admission happens
    mid-decode (continuous batching) and slots get reused.
    ``pipeline=False`` runs the unpipelined reference scheduler — the
    before/after pair is the pipelining speedup. ``tracer``: an
    ``obs.Tracer`` to record the run's span tree into (None = the
    disabled default — the untraced baseline)."""
    import numpy as np

    from elephas_tpu.metrics import mfu
    from elephas_tpu.serving import InferenceEngine

    rng = np.random.default_rng(1)
    vocab = compiled.module.vocab_size
    engine = InferenceEngine(
        compiled,
        max_slots=max_slots,
        max_prompt_len=prompt_len,
        max_len=prompt_len + new_tokens + 1,
        queue_depth=max(requests, 1),
        pipeline=pipeline,
        tracer=tracer,
    )
    # Warm all three compiled programs (prefill, slot admission, decode)
    # outside the timed region — bench_generate does the same with its
    # untimed first run. Serving tok/s measures serving, not XLA
    # compile time.
    engine.result(engine.submit([1] * prompt_len, max_new_tokens=2))
    engine.metrics.reset()
    t0 = time.perf_counter()
    rids = []
    for i in range(requests):
        plen = int(rng.integers(1, prompt_len + 1))
        prompt = rng.integers(1, vocab, plen).tolist()
        rids.append(engine.submit(prompt, max_new_tokens=new_tokens))
        # Stagger: keep the pool busy while later requests arrive.
        if len(rids) >= max_slots:
            engine.step()
    results = [engine.result(r) for r in rids]
    dt = time.perf_counter() - t0
    stats = engine.stats()
    tps = stats["tokens_out"] / dt
    fpt = flops_per_decode_token(compiled, prompt_len + new_tokens)
    return {
        "mode": "serving",
        "pipeline": pipeline,
        "max_slots": max_slots,
        "requests": requests,
        "completed": stats["completed"],
        "tokens_out": stats["tokens_out"],
        "wall_sec": dt,
        "tokens_per_sec": tps,
        "flops_per_token": fpt,
        "mfu": mfu(tps, fpt),
        "ttft_s_avg": stats["ttft_s_avg"],
        "itl_s_avg": stats["itl_s_avg"],
        "dispatch_to_fetch_s_avg": stats["dispatch_to_fetch_s_avg"],
        # Tail latencies from the ServingMetrics histograms: the SLO
        # columns (means hide stall spikes).
        **{
            f"{base}_{p}": stats[f"{base}_{p}"]
            for base in ("ttft_s", "itl_s", "dispatch_to_fetch_s")
            for p in ("p50", "p95", "p99")
        },
        "prefill_traces": stats["prefill_traces"],
        "decode_traces": stats["decode_traces"],
        "pool_admitted_total": stats["pool_admitted_total"],
        "all_completed": all(r.status == "completed" for r in results),
    }


def bench_trace_overhead(compiled, max_slots: int, prompt_len: int,
                         new_tokens: int, requests: int,
                         rounds: int = 3, attempts: int = 3) -> dict:
    """Guardrail: tracing must cost < 2% serving throughput.

    The tracer's pitch is "leave it on in production", so the bench
    enforces it: one DISCARDED warmup run (the first run after a compile
    reads measurably fast — hot caches), then ``rounds`` traced/untraced
    pairs whose within-pair order alternates (decorrelates drift —
    thermal, page cache — from the arm), compared best-of-``rounds``
    (the noise floor on shared CPU runners swamps a 2% signal in means).
    Retries the whole measurement before the assert fires; a persistent
    > 2% gap is a real regression in the record/instant hot path."""
    from elephas_tpu.obs import Tracer

    run = lambda tracer: bench_serving(  # noqa: E731
        compiled, max_slots, prompt_len, new_tokens, requests,
        pipeline=True, tracer=tracer,
    )["tokens_per_sec"]
    run(None)  # warmup, discarded
    for attempt in range(attempts):
        plain, traced = [], []
        for r in range(rounds):
            if r % 2 == 0:
                plain.append(run(None))
                traced.append(run(Tracer()))
            else:
                traced.append(run(Tracer()))
                plain.append(run(None))
        overhead = 1.0 - max(traced) / max(plain)
        if overhead < 0.02:
            break
    rec = {
        "mode": "serving_trace_overhead",
        "rounds": rounds,
        "attempts_used": attempt + 1,
        "tokens_per_sec_untraced": max(plain),
        "tokens_per_sec_traced": max(traced),
        "overhead_pct": overhead * 100.0,
        "within_2pct": overhead < 0.02,
    }
    assert rec["within_2pct"], (
        f"tracing overhead {overhead * 100.0:.2f}% >= 2% after "
        f"{attempts} attempts (traced {max(traced):.0f} vs untraced "
        f"{max(plain):.0f} tok/s)"
    )
    return rec


def bench_store_overhead(compiled, max_slots: int, prompt_len: int,
                         new_tokens: int, requests: int,
                         rounds: int = 3, attempts: int = 3) -> dict:
    """Guardrail: the durable telemetry store must cost < 2% serving
    throughput (the post-mortem plane's pitch is "persist everything,
    pay nothing on the hot path").

    Both arms mount the ops endpoint — history sampler ticking on its
    daemon thread, alert engine scrapable — so the ONLY difference in
    the measured arm is a mounted ``obs.TelemetryStore``: every sampler
    tick, flight note, and alert transition journals to disk (write +
    flush per record). Same discipline as the trace/canary overhead
    gates: discarded warmup, alternating within-pair order, best-of-
    ``rounds``, whole-measurement retries before the assert fires."""
    import tempfile

    import numpy as np

    from elephas_tpu.serving import InferenceEngine

    vocab = compiled.module.vocab_size

    def run(store_dir):
        rng = np.random.default_rng(1)
        engine = InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=max(requests, 1),
            pipeline=True,
        )
        engine.mount_ops(port=0, store_dir=store_dir)
        try:
            engine.result(engine.submit([1] * prompt_len,
                                        max_new_tokens=2))
            t0 = time.perf_counter()
            rids = []
            for i in range(requests):
                plen = int(rng.integers(1, prompt_len + 1))
                prompt = rng.integers(1, vocab, plen).tolist()
                rids.append(engine.submit(prompt,
                                          max_new_tokens=new_tokens))
                if len(rids) >= max_slots:
                    engine.step()
            results = [engine.result(r) for r in rids]
            dt = time.perf_counter() - t0
            tokens = sum(len(r.tokens) for r in results)
            journaled = (engine.store.stats()["records"]
                         if engine.store is not None else 0)
            return tokens / dt, journaled
        finally:
            engine.unmount_ops()

    with tempfile.TemporaryDirectory() as root:
        dirs = iter(range(10000))  # fresh store dir per measured run

        def on():
            return run(os.path.join(root, f"s{next(dirs)}", "telemetry"))

        run(None)  # warmup, discarded
        for attempt in range(attempts):
            plain, stored = [], []
            for r in range(rounds):
                if r % 2 == 0:
                    plain.append(run(None)[0])
                    stored.append(on())
                else:
                    stored.append(on())
                    plain.append(run(None)[0])
            overhead = 1.0 - max(s[0] for s in stored) / max(plain)
            if overhead < 0.02:
                break
    rec = {
        "mode": "serving_store_overhead",
        "rounds": rounds,
        "attempts_used": attempt + 1,
        "tokens_per_sec_unstored": max(plain),
        "tokens_per_sec_stored": max(s[0] for s in stored),
        "journaled_records": max(s[1] for s in stored),
        "overhead_pct": overhead * 100.0,
        "within_2pct": overhead < 0.02,
    }
    assert rec["within_2pct"], (
        f"telemetry store overhead {overhead * 100.0:.2f}% >= 2% after "
        f"{attempts} attempts (stored {rec['tokens_per_sec_stored']:.0f} "
        f"vs unstored {rec['tokens_per_sec_unstored']:.0f} tok/s)"
    )
    return rec


def bench_slo(compiled, max_slots: int, prompt_len: int, new_tokens: int,
              requests: int, probes: int = 3, rounds: int = 3,
              attempts: int = 3) -> dict:
    """Goodput + canary arm: serve the standard mixed workload with
    blackbox canary probes riding the real submit path, and commit both
    the SLO attainment (per-objective goodput ratios, canary-excluded
    by construction) and the canary's own blackbox SLIs. Probe cost is
    measured with the tracing-guardrail discipline — a discarded
    warmup, then ``rounds`` canaried/plain pairs with alternating
    within-pair order, compared best-of-rounds on *real-traffic*
    tokens/sec — and gated under 2% by scripts/bench_gate.py."""
    import numpy as np

    from elephas_tpu.obs.canary import CanaryDriver
    from elephas_tpu.serving import InferenceEngine

    vocab = compiled.module.vocab_size

    def run(canaried: bool):
        rng = np.random.default_rng(1)
        engine = InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=max(requests, 1) + probes,
            pipeline=True,
        )
        driver = CanaryDriver(engine) if canaried else None
        engine.result(engine.submit([1] * prompt_len, max_new_tokens=2))
        engine.metrics.reset()
        # Probes fire spread through the submit schedule so they share
        # the batch with real traffic (the realistic interference case).
        probe_at = {max(1, (i + 1) * requests // (probes + 1))
                    for i in range(probes)} if canaried else set()
        t0 = time.perf_counter()
        rids = []
        for i in range(requests):
            plen = int(rng.integers(1, prompt_len + 1))
            prompt = rng.integers(1, vocab, plen).tolist()
            rids.append(engine.submit(prompt, max_new_tokens=new_tokens))
            if len(rids) >= max_slots:
                engine.step()
            if i in probe_at:
                driver.probe()
        results = [engine.result(r) for r in rids]
        dt = time.perf_counter() - t0
        real_tokens = sum(len(r.tokens) for r in results)
        return real_tokens / dt, engine, driver, results

    run(False)  # warmup, discarded
    for attempt in range(attempts):
        plain, canaried = [], []
        for r in range(rounds):
            if r % 2 == 0:
                plain.append(run(False)[0])
                canaried.append(run(True))
            else:
                canaried.append(run(True))
                plain.append(run(False)[0])
        overhead = 1.0 - max(c[0] for c in canaried) / max(plain)
        if overhead < 0.02:
            break
    best = max(canaried, key=lambda c: c[0])
    _, engine, driver, results = best
    slo = engine.slo.snapshot()
    probe_doc = driver.snapshot()
    return {
        "mode": "serving_slo",
        "pipeline": True,
        "max_slots": max_slots,
        "requests": requests,
        "evaluated": slo["evaluated"],
        "goodput": slo["goodput"]["lifetime"],
        "goodput_ratio": slo["goodput_ratio"],
        "canary_probes": probe_doc["probes"],
        "canary_failures": probe_doc["failures"],
        "canary_e2e_s_avg": probe_doc["e2e_s_avg"],
        "canary_e2e_s_max": probe_doc["e2e_s_max"],
        "tokens_per_sec_plain": max(plain),
        "tokens_per_sec_canaried": max(c[0] for c in canaried),
        "canary_overhead_pct": overhead * 100.0,
        "within_2pct": overhead < 0.02,
        "attempts_used": attempt + 1,
        "rounds": rounds,
        "all_completed": all(r.status == "completed" for r in results),
    }


def bench_prefix(compiled, max_slots: int, prompt_len: int,
                 new_tokens: int, *, sessions: int = 4, turns: int = 3,
                 attempts: int = 3) -> dict:
    """Paged-pool arm (``--prefix``): the three claims the paged KV
    pool makes, measured on one row.

    1. Prefix economics — multi-turn sessions sharing a system prompt
       on the paged engine: committed hit rate (the gate floors it at
       0.5) and prefill tokens the cache actually skipped.
    2. Correctness — every conversation's token stream must equal
       ``generate()`` of its full prompt, one row at a time
       (``token_identical`` is an equal-rule in the gate, like the
       fleet router's).
    3. Chunked prefill — a saturating long-prompt workload (prompts as
       long as the model seats, short decodes, admissions arriving
       faster than prefill drains) run twice: unchunked, every decode
       gap absorbs whole batch-1 prefills; chunked with a one-chunk-
       per-step budget, the per-step stall is bounded at one chunk and
       the backlog moves to the queue (TTFT rises, the deliberate
       trade). The committed ``chunked_itl_ratio`` (chunked ITL p99 /
       unchunked ITL p99) carries an absolute gate ceiling of 1.0 and
       measures ~0.4 here; retried ``attempts`` times because shared
       CI machines jitter the tail.
    """
    import numpy as np

    from elephas_tpu.models.transformer import generate
    from elephas_tpu.serving import InferenceEngine

    vocab = compiled.module.vocab_size
    block = max(2, prompt_len // 4)
    sys_prompt = np.random.default_rng(9).integers(
        1, vocab, 2 * block).tolist()

    def run_conversations():
        eng = InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=sessions * turns + 3 * max_slots + 2,
            pipeline=True,
            kv_block_size=block,
        )
        eng.result(eng.submit([1] * prompt_len, max_new_tokens=2))
        eng.metrics.reset()
        rng = np.random.default_rng(13)
        prompts, streams = [], []
        for _turn in range(turns):
            rids = []
            for _s in range(sessions):
                plen = int(rng.integers(
                    1, prompt_len - len(sys_prompt) + 1))
                prompt = sys_prompt + rng.integers(1, vocab, plen).tolist()
                prompts.append(prompt)
                rids.append(eng.submit(prompt, max_new_tokens=new_tokens))
            # Turn barrier: later turns arrive after earlier ones
            # published their prefixes — the repeat-conversation shape.
            streams.extend(
                list(eng.result(r).tokens) for r in rids)
        return prompts, streams, eng.stats()

    prompts, paged_streams, paged_stats = run_conversations()
    token_identical = all(
        stream == [int(t) for t in generate(
            compiled, np.asarray([prompt], np.int32),
            new_tokens)[0][len(prompt):]]
        for prompt, stream in zip(prompts, paged_streams))

    itl_new = 4
    long_prompt = compiled.module.max_seq_len - itl_new - 1
    itl_requests = 6 * max_slots

    def run_itl(chunk, per_step):
        eng = InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=long_prompt,
            max_len=long_prompt + itl_new + 1,
            queue_depth=itl_requests + 2,
            pipeline=True,
            kv_block_size=block,
            prefill_chunk=chunk,
            prefill_chunks_per_step=per_step,
        )
        eng.result(eng.submit([1] * long_prompt, max_new_tokens=2))
        eng.metrics.reset()
        rng = np.random.default_rng(5)
        rids = []
        for _ in range(itl_requests):
            prompt = rng.integers(1, vocab, long_prompt).tolist()
            rids.append(eng.submit(prompt, max_new_tokens=itl_new))
            if len(rids) >= max_slots:
                eng.step()
        results = [eng.result(r, timeout_s=120.0) for r in rids]
        ok = all(r.status == "completed" for r in results)
        st = eng.stats()
        return st["itl_s_p99"], st["ttft_s_p95"], ok

    chunk_w = max(1, min(8, long_prompt // 2))
    for attempt in range(attempts):
        unchunked_p99, unchunked_ttft, ok_u = run_itl(None, None)
        chunked_p99, chunked_ttft, ok_c = run_itl(chunk_w, 1)
        if chunked_p99 <= unchunked_p99:
            break
    return {
        "mode": "serving_prefix",
        "pipeline": True,
        "paged": True,
        "max_slots": max_slots,
        "kv_block_size": block,
        "sessions": sessions,
        "turns": turns,
        "prefix_hits": paged_stats["prefix_hits"],
        "prefix_lookups": paged_stats["prefix_lookups"],
        "prefix_hit_rate": paged_stats["prefix_hit_rate"],
        "prefill_tokens_saved": paged_stats["prefix_tokens_saved"],
        "token_identical": token_identical,
        "prefill_chunk": chunk_w,
        "long_prompt_len": long_prompt,
        "itl_new_tokens": itl_new,
        "itl_requests": itl_requests,
        "itl_s_p99_chunked": chunked_p99,
        "itl_s_p99_unchunked": unchunked_p99,
        "chunked_itl_ratio": (chunked_p99 / unchunked_p99
                              if unchunked_p99 else None),
        # The other side of the trade, committed for honesty: the chunk
        # budget defers prefill work, so queue wait (TTFT) grows while
        # the decode tail shrinks.
        "ttft_s_p95_chunked": chunked_ttft,
        "ttft_s_p95_unchunked": unchunked_ttft,
        "attempts_used": attempt + 1,
        "all_completed": ok_u and ok_c,
    }


def bench_spec(compiled, max_slots: int, prompt_len: int,
               new_tokens: int, *, sessions: int = 4, turns: int = 3,
               gamma: int = 3, refresh_every: int = 8) -> dict:
    """Speculative-decoding arm (``--spec``): draft-and-verify decode on
    the paged engine, measured against the unspeculated oracle on the
    SAME shared-prefix workload.

    The draft model's params are delivered by a real 2-shard parameter
    server group over sockets (``ShardedParameterClient``, version-gated
    pulls bounded by ``refresh_every``) — the PS-delivered-draft bridge,
    exercised end-to-end rather than faked. At bench scale no distilled
    draft checkpoint exists, so the delivered draft carries the target's
    own weights: the committed ``spec_accept_rate`` is the MECHANICAL
    ceiling (a same-weights draft must accept ~everything; the gate
    floor catches draft-cache/rollback breakage, which shows up as
    silently sunk acceptance, not as wrong tokens). Self-draft
    acceptance on this untrained bench model is measured separately in
    PROFILE.md §22 — it needs a trained target to clear the floor.

    Committed claims: ``token_identical`` (spec streams == oracle
    streams, request-for-request — identity is correctness, equal-rule
    in the gate), ``spec_accept_rate`` (floor 0.5), ``tokens_per_step``
    (floor 1.3 — the whole point of speculation), ``spec_itl_ratio``
    (spec mean ITL / plain mean ITL, ceiling 1.0 — speculation must not
    trade the tail away), and the compile counters (exactly one draft +
    one verify program after warmup).
    """
    import numpy as np

    from elephas_tpu.parameter import ShardGroup
    from elephas_tpu.serving import DraftModelSource, InferenceEngine

    m = compiled.module
    vocab = m.vocab_size
    block = max(2, prompt_len // 4)
    # The speculative pool's virtual row extends ``gamma`` columns past
    # max_len (rounded up to a block); the draft model's pos_embed table
    # must cover it, and pos_embed is sized by max_seq_len — so the spec
    # arm builds its own model with that headroom rather than stretching
    # the shared bench model (which would resize every other arm's
    # params).
    compiled = build_model(
        vocab, m.d_model, m.num_heads, m.num_layers,
        max_seq=prompt_len + new_tokens + 1 + gamma + block,
    )
    sys_prompt = np.random.default_rng(9).integers(
        1, vocab, 2 * block).tolist()

    def run(group=None):
        spec = group is not None
        kw = {}
        if spec:
            kw.update(
                speculative=True, gamma=gamma,
                draft_source=DraftModelSource(
                    compiled.module, group.client(),
                    refresh_every=refresh_every,
                ),
            )
        eng = InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=sessions * turns + 2,
            pipeline=True,
            kv_block_size=block,
            # Model draft sources require prefix_cache=False (a
            # prefix-matched admission would leave the draft cache
            # cold); the oracle matches so the arms differ ONLY in
            # speculation. "Shared prefix" stays a workload shape.
            prefix_cache=False,
            **kw,
        )
        eng.result(eng.submit([1] * prompt_len, max_new_tokens=2))
        eng.metrics.reset()
        rng = np.random.default_rng(13)
        streams, results = [], []
        for _turn in range(turns):
            rids = []
            for _s in range(sessions):
                plen = int(rng.integers(
                    1, prompt_len - len(sys_prompt) + 1))
                prompt = sys_prompt + rng.integers(1, vocab, plen).tolist()
                rids.append(eng.submit(prompt, max_new_tokens=new_tokens))
            for r in rids:
                res = eng.result(r, timeout_s=120.0)
                results.append(res)
                streams.append(list(res.tokens))
        st = eng.stats()
        source = eng.spec.source if spec else None
        return streams, results, st, source

    group = ShardGroup(compiled.params, 2, mode="socket")
    group.start()
    try:
        spec_streams, spec_results, spec_st, source = run(group)
    finally:
        group.stop()
    oracle_streams, oracle_results, plain_st, _ = run(None)
    token_identical = spec_streams == oracle_streams
    # ITL histograms record per-STEP latency (one verify window is one
    # step emitting up to gamma+1 tokens — that's what tokens_per_step
    # disambiguates), so the committed ratio is per emitted TOKEN:
    # spec step cost amortized over its tokens/step, against the plain
    # engine's one-token steps. Below 1.0 means speculation emits
    # tokens faster than plain decode, the claim the gate holds.
    tps = spec_st["spec_tokens_per_step"]
    spec_itl_ratio = (
        (spec_st["itl_s_avg"] / tps) / plain_st["itl_s_avg"]
        if plain_st["itl_s_avg"] and tps else None)
    return {
        "mode": "serving_spec",
        "pipeline": True,
        "paged": True,
        "max_slots": max_slots,
        "requests": sessions * turns,
        "gamma": gamma,
        "draft_source": "model",
        "draft_refresh_every": refresh_every,
        "draft_pulls": source.pulls,
        "spec_windows": spec_st["spec_windows"],
        "spec_accept_rate": spec_st["spec_accept_rate"],
        "tokens_per_step": spec_st["spec_tokens_per_step"],
        "itl_s_p50_spec": spec_st["itl_s_p50"],
        "itl_s_p99_spec": spec_st["itl_s_p99"],
        "itl_s_p50_plain": plain_st["itl_s_p50"],
        "itl_s_p99_plain": plain_st["itl_s_p99"],
        "spec_itl_ratio": spec_itl_ratio,
        "token_identical": token_identical,
        "draft_traces": spec_st["draft_traces"],
        "verify_traces": spec_st["verify_traces"],
        "draft_prefill_traces": spec_st["draft_prefill_traces"],
        "decode_traces_spec": spec_st["decode_traces"],
        "all_completed": all(
            r.status == "completed"
            for r in spec_results + oracle_results),
    }


# -- fleet arms (--fleet → BENCH_FLEET.json) ---------------------------------


def _engine_factory(compiled, max_slots, prompt_len, new_tokens, depth):
    from elephas_tpu.serving import InferenceEngine

    def factory():
        return InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=depth,
            pipeline=True,
        )

    return factory


def _fleet_workload(submit, result, vocab, prompt_len, new_tokens,
                    requests):
    """The standard mixed-length workload against any submit/result
    pair (bare engine or router) — same seed, same prompts, so the two
    arms' token streams are comparable request-for-request."""
    import numpy as np

    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    rids = []
    for _ in range(requests):
        plen = int(rng.integers(1, prompt_len + 1))
        prompt = rng.integers(1, vocab, plen).tolist()
        rids.append(submit(prompt, new_tokens))
    results = [result(r) for r in rids]
    dt = time.perf_counter() - t0
    tokens = [list(r.tokens) for r in results]
    tps = sum(len(t) for t in tokens) / dt
    return tps, tokens, results


def bench_fleet_routed_vs_bare(compiled, max_slots: int, prompt_len: int,
                               new_tokens: int, requests: int,
                               rounds: int = 3, attempts: int = 3) -> dict:
    """Routing guardrail + correctness proof: a single replica behind
    the router must serve the SAME token streams as a bare engine
    (request-for-request identity) at < 2% throughput cost. Both arms
    run a serve thread (the replica's is built in), so the comparison
    isolates the router hop, not a stepping-discipline difference.
    Measured with the trace-overhead discipline: discarded warmup, then
    ``rounds`` bare/routed pairs with alternating within-pair order,
    compared best-of-rounds, retried ``attempts`` times."""
    import threading

    from elephas_tpu.serving import ReplicaSet, Router

    vocab = compiled.module.vocab_size
    factory = _engine_factory(compiled, max_slots, prompt_len, new_tokens,
                              max(requests, 1) + 1)

    def run_bare():
        engine = factory()
        stop = threading.Event()
        th = threading.Thread(target=engine.serve_forever, args=(stop,),
                              daemon=True)
        th.start()
        engine.result(engine.submit([1] * prompt_len, max_new_tokens=2),
                      timeout_s=60.0)
        out = _fleet_workload(
            lambda p, n: engine.submit(p, max_new_tokens=n),
            lambda r: engine.result(r, timeout_s=120.0),
            vocab, prompt_len, new_tokens, requests)
        stop.set()
        th.join(timeout=10.0)
        return out

    def run_routed():
        rs = ReplicaSet(factory, initial=1)
        router = Router(rs)
        router.result(router.submit([1] * prompt_len, max_new_tokens=2),
                      timeout_s=60.0)
        out = _fleet_workload(
            lambda p, n: router.submit(p, max_new_tokens=n),
            lambda r: router.result(r, timeout_s=120.0),
            vocab, prompt_len, new_tokens, requests)
        router.close()
        return out

    run_bare()  # warmup (compile + caches), discarded
    for attempt in range(attempts):
        bare, routed = [], []
        for r in range(rounds):
            if r % 2 == 0:
                bare.append(run_bare())
                routed.append(run_routed())
            else:
                routed.append(run_routed())
                bare.append(run_bare())
        overhead = 1.0 - (max(x[0] for x in routed)
                          / max(x[0] for x in bare))
        if overhead < 0.02:
            break
    token_identical = all(x[1] == bare[0][1] for x in bare + routed)
    all_completed = all(
        res.status == "completed" for x in bare + routed for res in x[2])
    rec = {
        "mode": "fleet_routed_vs_bare",
        "max_slots": max_slots,
        "requests": requests,
        "rounds": rounds,
        "attempts_used": attempt + 1,
        "tokens_per_sec_bare": max(x[0] for x in bare),
        "tokens_per_sec_routed": max(x[0] for x in routed),
        "routed_overhead_pct": overhead * 100.0,
        "token_identical": token_identical,
        "all_completed": all_completed,
        "within_2pct": overhead < 0.02,
    }
    assert token_identical, "routed token streams diverged from bare engine"
    assert rec["within_2pct"], (
        f"router overhead {overhead * 100.0:.2f}% >= 2% after "
        f"{attempts} attempts"
    )
    return rec


def bench_fleet_n(compiled, max_slots: int, prompt_len: int,
                  new_tokens: int, *, replicas: int = 3,
                  sessions: int = 6, turns: int = 4) -> dict:
    """N-replica steady state: multi-turn sessions through the router.
    Every turn after a session's first should land on the replica
    holding its KV state — the committed ``affinity_hit_rate`` is the
    floor the gate holds (0.9; it measures 1.0 when nothing drains)."""
    import numpy as np

    from elephas_tpu.serving import ReplicaSet, Router

    vocab = compiled.module.vocab_size
    factory = _engine_factory(compiled, max_slots, prompt_len, new_tokens,
                              sessions + replicas)
    rs = ReplicaSet(factory, initial=replicas)
    router = Router(rs)
    # Warm every replica's engine paths (spread by queue pressure).
    warm = [router.submit([1] * prompt_len, max_new_tokens=2)
            for _ in range(2 * replicas)]
    for r in warm:
        router.result(r, timeout_s=60.0)

    rng = np.random.default_rng(7)
    names = [f"s{i}" for i in range(sessions)]
    total_tokens = 0
    results = []
    t0 = time.perf_counter()
    for _turn in range(turns):
        rids = []
        for s in names:
            plen = int(rng.integers(1, prompt_len + 1))
            prompt = rng.integers(1, vocab, plen).tolist()
            rids.append(router.submit(prompt, max_new_tokens=new_tokens,
                                      session=s))
        for r in rids:
            res = router.result(r, timeout_s=120.0)
            results.append(res)
            total_tokens += len(res.tokens)
    dt = time.perf_counter() - t0
    follow_ups = router.affinity_hits + router.affinity_misses
    rec = {
        "mode": "fleet_n3",
        "replicas": replicas,
        "sessions": sessions,
        "turns": turns,
        "requests": sessions * turns,
        "tokens_out": total_tokens,
        "wall_sec": dt,
        "tokens_per_sec": total_tokens / dt,
        "affinity_hits": router.affinity_hits,
        "affinity_misses": router.affinity_misses,
        "affinity_hit_rate": (router.affinity_hits / follow_ups
                              if follow_ups else None),
        "all_completed": all(r.status == "completed" for r in results),
    }
    router.close()
    return rec


def bench_fleet_kill(compiled, max_slots: int, prompt_len: int,
                     new_tokens: int, *, replicas: int = 3) -> dict:
    """Chaos arm: kill a replica mid-traffic and measure the outage
    from three vantage points at once — the fleet plane (the killed
    replica's alive→stale→dead→alive transition arc through real HTTP
    scrapes), the blackbox clients (canary probes routed through the
    fleet during the outage — the router should mask most or all of
    it), and the real goodput ledger (requeued requests pay a bounded
    TTFT hit, they don't fail)."""
    import threading

    from elephas_tpu.obs.fleet import FleetAggregator
    from elephas_tpu.serving import ReplicaSet, Router

    vocab = compiled.module.vocab_size
    requests = 3 * replicas
    factory = _engine_factory(compiled, max_slots, prompt_len, new_tokens,
                              requests + 4)
    rs = ReplicaSet(factory, initial=replicas, mount_ops=True)
    router = Router(rs)
    router.mount_ops(port=0)

    agg = FleetAggregator(dead_after=1.0, timeout=1.0)
    for rid, rep in rs.replicas.items():
        agg.add(f"http://127.0.0.1:{rep.engine.ops.port}", name=rid)
    agg.add(f"http://127.0.0.1:{router.ops.port}", name="router")
    poll_stop = threading.Event()

    def poller():
        while not poll_stop.is_set():
            agg.poll()
            poll_stop.wait(0.15)

    poll_thread = threading.Thread(target=poller, daemon=True)
    poll_thread.start()

    import numpy as np

    rng = np.random.default_rng(3)
    names = [f"s{i}" for i in range(2 * replicas)]
    # First turn pins every session somewhere (and warms the engines).
    for s in names:
        router.result(router.submit([1, 2, 3], max_new_tokens=2,
                                    session=s), timeout_s=60.0)
    victim = router.session_replica(names[0])

    # Long decodes in flight across the fleet, then kill the pinned
    # replica under them.
    rids = []
    for i in range(requests):
        plen = int(rng.integers(1, prompt_len + 1))
        prompt = rng.integers(1, vocab, plen).tolist()
        rids.append(router.submit(prompt, max_new_tokens=new_tokens,
                                  session=names[i % len(names)]))
    t_kill = time.perf_counter()
    rs.kill(victim)

    # Blackbox canary probes through the router while degraded.
    probes = []
    while time.perf_counter() - t_kill < 1.5:
        t_p = time.perf_counter()
        try:
            pid = router.submit([1, 2, 3], max_new_tokens=2, canary=True)
            ok = router.result(pid, timeout_s=5.0).status == "completed"
        except Exception:
            ok = False
        probes.append((t_p - t_kill, ok))
        time.sleep(0.05)
    fails = [t for t, ok in probes if not ok]
    outage_canary_s = (max(fails) - min(fails)) + 0.05 if fails else 0.0

    results = [router.result(r, timeout_s=120.0) for r in rids]
    misses_after_kill = router.affinity_misses

    # Restart the victim (same name, new boot, new port) and wait for
    # the fleet plane to narrate the full arc.
    while time.perf_counter() - t_kill < 2.0:
        time.sleep(0.05)
    rs.restart(victim)
    agg.add(f"http://127.0.0.1:{rs.get(victim).engine.ops.port}",
            name=victim)
    saw_outage = False
    t_recover = None
    deadline = time.perf_counter() + 20.0
    while time.perf_counter() < deadline:
        proc = agg.snapshot()["processes"].get(victim)
        if proc is not None:
            states = [s for _, s in proc["transitions"]]
            if "dead" in states and proc["status"] == "alive":
                saw_outage = True
                t_recover = time.perf_counter() - t_kill
                break
        time.sleep(0.1)
    poll_stop.set()
    poll_thread.join(timeout=5.0)

    slo = router.slo.snapshot()
    rec = {
        "mode": "fleet_kill",
        "replicas": replicas,
        "requests": requests,
        "victim": victim,
        "requeues": router.requeues,
        "affinity_misses_after_kill": misses_after_kill,
        "canary_probes": len(probes),
        "canary_failed_probes": len(fails),
        "outage_canary_s": outage_canary_s,
        "fleet_saw_replica_outage": saw_outage,
        "fleet_recover_s": t_recover,
        "goodput_ratio_after_kill": slo["goodput_ratio"],
        "all_completed": all(r.status == "completed" for r in results),
        "victim_boot_after": rs.get(victim).boot,
    }
    router.close()
    return rec


def bench_fleet_autoscale() -> dict:
    """Autoscaler replay arm: a seeded burn ladder (burst, then quiet)
    through the pure decision core. No engines, no clocks — the
    committed decision sequence IS the replay baseline; the gate's
    equal-rules hold the scale-up-under-burst and
    scale-down-after-cooldown bits."""
    from elephas_tpu.serving import FleetAutoscaler

    auto = FleetAutoscaler(min_replicas=1, max_replicas=3, up_burn=1.0,
                           down_burn=0.25, up_after=2, down_after=3,
                           cooldown_s=60.0)
    schedule = []
    t = 0.0
    for _ in range(4):          # seeded burst: sustained critical burn
        schedule.append((t, 5.0))
        t += 10.0
    for _ in range(12):         # quiet tail: budget recovered
        schedule.append((t, 0.0))
        t += 30.0
    n = 1
    for t_obs, burn in schedule:
        decision = auto.observe(burn=burn, n_replicas=n, now=t_obs)
        if decision == "up":
            n += 1
        elif decision == "down":
            n -= 1
    ups = [d["t"] for d in auto.decisions if d["direction"] == "up"]
    downs = [d["t"] for d in auto.decisions if d["direction"] == "down"]
    return {
        "mode": "fleet_autoscale",
        "observations": auto.observations,
        "decisions": [[d["t"], d["direction"], d["replicas"]]
                      for d in auto.decisions],
        "scaled_up_under_burst": bool(ups) and ups[0] <= 40.0,
        "scaled_down_after_cooldown": (bool(ups) and bool(downs)
                                       and downs[0] >= ups[0] + 60.0),
        "final_replicas": n,
    }


def bench_fleet_tenants(compiled, max_slots: int, prompt_len: int,
                        new_tokens: int, requests: int,
                        rounds: int = 3, attempts: int = 3) -> dict:
    """Tenancy guardrail + attribution proof (``--tenants``).

    Two claims in one arm. First, tagging is free: the standard
    workload with every submit carrying a ``tenant=`` tag must match
    the untagged arm token-for-token at < 2% throughput cost (same
    warmup/rounds/best-of discipline as the router overhead arm).
    Second, attribution is exact: a mixed two-tenant workload —
    ``interactive`` (short prompts, short decodes) interleaved with
    ``batch`` (full-length everything) — runs through the router with
    tracing live, and afterwards the per-tenant ledger must conserve
    tokens EXACTLY (sum over tenants of prefill/decode tokens ==
    the engine's ``ServingMetrics`` totals), and at least one
    ``serving_itl_seconds`` histogram exemplar must join a trace id
    present in the span dump (the p99-to-span-tree pivot the exemplar
    plane exists for)."""
    import tempfile
    import threading

    import numpy as np

    from elephas_tpu import obs
    from elephas_tpu.obs import Tracer
    from elephas_tpu.serving import InferenceEngine, ReplicaSet, Router

    vocab = compiled.module.vocab_size
    factory = _engine_factory(compiled, max_slots, prompt_len, new_tokens,
                              max(requests, 1) + 1)

    def run(tagged):
        engine = factory()
        stop = threading.Event()
        th = threading.Thread(target=engine.serve_forever, args=(stop,),
                              daemon=True)
        th.start()
        engine.result(engine.submit([1] * prompt_len, max_new_tokens=2),
                      timeout_s=60.0)
        seq = [0]

        def submit(p, n):
            if not tagged:
                return engine.submit(p, max_new_tokens=n)
            seq[0] += 1
            return engine.submit(
                p, max_new_tokens=n,
                tenant="interactive" if seq[0] % 2 else "batch")

        out = _fleet_workload(
            submit, lambda r: engine.result(r, timeout_s=120.0),
            vocab, prompt_len, new_tokens, requests)
        stop.set()
        th.join(timeout=10.0)
        return out

    run(True)  # warmup (compile + caches), discarded
    for attempt in range(attempts):
        plain, tagged = [], []
        for r in range(rounds):
            if r % 2 == 0:
                plain.append(run(False))
                tagged.append(run(True))
            else:
                tagged.append(run(True))
                plain.append(run(False))
        overhead = 1.0 - (max(x[0] for x in tagged)
                          / max(x[0] for x in plain))
        if overhead < 0.02:
            break
    token_identical = all(x[1] == plain[0][1] for x in plain + tagged)

    # -- attribution proof: mixed two-tenant traffic through the router,
    # tracing live so the finish-side exemplar latch has ids to latch.
    tracer = Tracer()

    def traced_factory():
        return InferenceEngine(
            compiled, max_slots=max_slots, max_prompt_len=prompt_len,
            max_len=prompt_len + new_tokens + 1,
            queue_depth=2 * requests + 4, pipeline=True, tracer=tracer)

    rs = ReplicaSet(traced_factory, initial=1)
    router = Router(rs)
    prompt_total = prompt_len  # router warmup bills as tenant "default"
    router.result(router.submit([1] * prompt_len, max_new_tokens=2),
                  timeout_s=60.0)
    rng = np.random.default_rng(11)
    rids = []
    by_tenant = {"interactive": [], "batch": []}
    for i in range(2 * requests):
        if i % 2 == 0:
            tenant = "interactive"
            plen = int(rng.integers(1, max(2, prompt_len // 2)))
            n = max(2, new_tokens // 4)
        else:
            tenant = "batch"
            plen = prompt_len
            n = new_tokens
        prompt = rng.integers(1, vocab, plen).tolist()
        prompt_total += plen
        rids.append((tenant,
                     router.submit(prompt, max_new_tokens=n,
                                   tenant=tenant)))
    results = []
    for tenant, rid in rids:
        res = router.result(rid, timeout_s=120.0)
        results.append(res)
        by_tenant[tenant].append(res)

    engine = next(iter(rs.replicas.values())).engine
    snap = engine.costs.snapshot()
    rows = snap["tenants"]
    dec_diff = (sum(r["decode_tokens"] for r in rows.values())
                - engine.metrics.tokens_out)
    pre_diff = (sum(r["prefill_tokens"] for r in rows.values())
                - prompt_total)

    # Exemplar→trace join: some ITL bucket's latched trace id must be a
    # trace id the span dump actually contains.
    reg_ex = obs.default_registry().exemplars().get(
        "serving_itl_seconds", {})
    exemplar_ids = {v for v in reg_ex.values() if v}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        tracer.export_chrome(path)
        with open(path) as f:
            doc = json.load(f)
    trace_ids = {(e.get("args") or {}).get("trace_id")
                 for e in doc.get("traceEvents", ())}
    exemplar_joined = bool(exemplar_ids & trace_ids)

    def mean(xs):
        xs = [x for x in xs if x is not None]
        return sum(xs) / len(xs) if xs else None

    rec = {
        "mode": "fleet_tenants",
        "requests": 2 * requests,
        "rounds": rounds,
        "attempts_used": attempt + 1,
        "tokens_per_sec_plain": max(x[0] for x in plain),
        "tokens_per_sec_tagged": max(x[0] for x in tagged),
        "tenant_overhead_pct": overhead * 100.0,
        "token_identical": token_identical,
        "tenants": sorted(rows),
        "decode_tokens_by_tenant": {
            t: r["decode_tokens"] for t, r in sorted(rows.items())},
        "kv_block_seconds_by_tenant": {
            t: r["kv_block_seconds"] for t, r in sorted(rows.items())},
        "queue_seconds_by_tenant": {
            t: r["queue_seconds"] for t, r in sorted(rows.items())},
        "ttft_s_avg_by_tenant": {
            t: mean([r.ttft_s for r in rs_])
            for t, rs_ in sorted(by_tenant.items())},
        "itl_s_avg_by_tenant": {
            t: mean([r.itl_s_avg for r in rs_])
            for t, rs_ in sorted(by_tenant.items())},
        "tenant_token_conservation": float(abs(dec_diff) + abs(pre_diff)),
        "interactive_goodput_ratio": (
            rows["interactive"]["goodput"]["ratio"]),
        "batch_goodput_ratio": rows["batch"]["goodput"]["ratio"],
        "tenant_exemplar_joined": exemplar_joined,
        "all_completed": all(r.status == "completed" for r in results),
        "within_2pct": overhead < 0.02,
    }
    router.close()
    assert token_identical, "tagged token streams diverged from untagged"
    assert rec["tenant_token_conservation"] == 0.0, (
        f"attribution leak: decode diff {dec_diff}, prefill diff "
        f"{pre_diff} (per-tenant sums must equal fleet totals exactly)")
    assert rec["within_2pct"], (
        f"tenant tagging overhead {overhead * 100.0:.2f}% >= 2% after "
        f"{attempts} attempts")
    return rec


def bench_fleet_disagg(compiled, max_slots: int, prompt_len: int,
                       new_tokens: int, requests: int,
                       attempts: int = 3) -> dict:
    """Disaggregated-tiers arm (``--disagg``).

    The same two-tenant interference workload runs through two fleet
    topologies built from identical paged engines: a 2-replica
    monolithic fleet, and a 1-prefill + 1-decode tiered fleet where
    every request is prefilled on the prefill tier and its filled KV
    blocks cross the wire (``encode_handoff``/``submit_handoff``) to
    join the decode tier's batch. Four claims on one row:

    1. Identity — the tiered fleet serves byte-equal token streams to
       the monolithic fleet, request-for-request (the handoff is a
       transport, not a resample; gate equal-rule).
    2. Interference — decode-tier ITL p99 with the ``batch`` tenant
       streaming full-length prompts: on the monolithic fleet every
       batch prefill stalls a decode step, so the worst per-request
       mean inter-token gap eats whole prefill forwards; on the decode
       tier the only foreign work is the (device-side) block import.
       The committed ratio (decode tier's engine ITL p99 over the
       worst monolithic engine's) carries an absolute gate ceiling of
       1.0; retried ``attempts`` times for CI tail jitter. The
       interactive tenant's per-request view rides the row ungated —
       at CI scale its means are dominated by scheduler noise, while
       the engine-level p99 is where a stolen prefill step lands.
    3. Handoff cost — p50/p99 wall ms of export→encode→import,
       measured after a per-shape warmup (the import's donating
       scatter compiles once per block-count shape); p99 gate ceiling.
    4. QoS — both tenants run under admission (priority 0 vs 2,
       asymmetric weights) and the WORST tenant's goodput ratio is
       committed with an absolute floor: fair share may deprioritize
       the batch tenant, it must not starve it.

    Cross-tier prefix economics ride the same row: every prompt opens
    with a shared two-block system prefix, so after the first import
    the decode pool should satisfy each handoff's prefix from resident
    blocks — the committed hit rate is the fraction of handoffs that
    re-used at least one resident block (gate floor 0.5).
    """
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from elephas_tpu import obs
    from elephas_tpu.serving import InferenceEngine, ReplicaSet, Router
    from elephas_tpu.serving.fleet import QoSPolicy

    vocab = compiled.module.vocab_size
    block = max(2, prompt_len // 4)
    sys_prompt = np.random.default_rng(17).integers(
        1, vocab, 2 * block).tolist()
    # The interference must be REAL prefill work: batch prompts use the
    # model's whole sequence budget (the saturating-long-prompt shape
    # the --prefix ITL arm established), so on the monolithic fleet
    # every batch admission absorbs a full-length forward between two
    # decode steps. The decode tier's only foreign work is the block
    # import — a single donating scatter, whose cost does not grow
    # with prompt compute.
    interactive_new = min(new_tokens, 32)
    long_len = compiled.module.max_seq_len - interactive_new - 1

    def factory():
        return InferenceEngine(
            compiled,
            max_slots=max_slots,
            max_prompt_len=long_len,
            max_len=long_len + interactive_new + 1,
            queue_depth=2 * requests + 8,
            pipeline=True,
            kv_block_size=block,
        )

    # One deterministic workload, shared by both arms: interactive
    # (short suffix, long decode — the ITL victim) interleaved with
    # batch (full-length prompts, short decodes — the interference).
    rng = np.random.default_rng(23)
    work = []
    for i in range(2 * requests):
        if i % 2 == 0:
            tenant = "interactive"
            plen = int(rng.integers(1, block + 1))
            n = interactive_new
        else:
            tenant = "batch"
            plen = long_len - len(sys_prompt)
            n = 2
        prompt = sys_prompt + rng.integers(1, vocab, plen).tolist()
        work.append((tenant, prompt, n))
    # Warmup shapes: one per distinct prompt block count (the decode
    # pool's import scatter compiles per shape; an unwarmed shape
    # would bill one XLA compile to a handoff sample).
    warm_prompts = [sys_prompt + [1] * 1, sys_prompt + [1] * (
        long_len - len(sys_prompt))]

    flight = obs.default_flight_recorder()

    def run(tiered):
        if tiered:
            rs = ReplicaSet(factory, tiers={"prefill": 1, "decode": 1})
            qos = QoSPolicy(
                buckets={"interactive": (1e9, 1e9), "batch": (1e9, 1e9)},
                weights={"interactive": 4.0, "batch": 1.0},
                priorities={"interactive": 0, "batch": 2})
            router = Router(rs, qos=qos)
        else:
            rs = ReplicaSet(factory, initial=2)
            router = Router(rs)
        for p in warm_prompts * 2:
            router.result(router.submit(p, max_new_tokens=2),
                          timeout_s=60.0)
        for rep in rs.replicas.values():
            rep.engine.metrics.reset()
        router._handoff_s.clear()  # timed samples only (warmup compiled)
        handoffs0, fails0 = router.handoffs, router.handoff_fails
        kv_evs0 = len(flight.events(kind="kv_handoff"))

        t0 = time.perf_counter()
        rids = [(tenant,
                 router.submit(prompt, max_new_tokens=n, tenant=tenant))
                for tenant, prompt, n in work]
        with ThreadPoolExecutor(max_workers=len(rids)) as ex:
            futs = [ex.submit(router.result, rid, 180.0)
                    for _, rid in rids]
            results = [f.result() for f in futs]
        dt = time.perf_counter() - t0

        streams = [list(r.tokens) for r in results]
        tps = sum(len(s) for s in streams) / dt
        itl_interactive = [
            r.itl_s_avg for (tenant, _, _), r in zip(work, results)
            if tenant == "interactive" and r.itl_s_avg is not None]
        if tiered:
            decode_eng = rs.serving("decode")[0].engine
            itl_engine_p99 = decode_eng.stats()["itl_s_p99"]
        else:
            itl_engine_p99 = max(
                rep.engine.stats()["itl_s_p99"]
                for rep in rs.replicas.values())
        # Per-tenant goodput: min ratio across engines (finish-side
        # ledgers live on whichever tier published the result).
        ratios = {}
        for rep in rs.replicas.values():
            for t, row in rep.engine.costs.snapshot()["tenants"].items():
                r = (row.get("goodput") or {}).get("ratio")
                if t in ("interactive", "batch") and r is not None:
                    ratios[t] = min(ratios.get(t, 1.0), r)
        kv_evs = flight.events(kind="kv_handoff")[kv_evs0:]
        out = {
            "tps": tps,
            "streams": streams,
            "ok": all(r.status == "completed" for r in results),
            "itl_interactive": itl_interactive,
            "itl_engine_p99": itl_engine_p99,
            "goodput_by_tenant": ratios,
            "handoffs": router.handoffs - handoffs0,
            "handoff_fails": router.handoff_fails - fails0,
            "handoff_s": list(router._handoff_s),
            "preemptions": router.preemptions,
            "prefix_matched": sum(
                1 for e in kv_evs if e.detail.get("matched", 0) >= 1),
        }
        router.close()
        return out

    def pctl(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None

    for attempt in range(attempts):
        mono = run(False)
        disagg = run(True)
        mono_p99 = pctl(mono["itl_interactive"], 0.99)
        dis_p99 = pctl(disagg["itl_interactive"], 0.99)
        ratio = (disagg["itl_engine_p99"] / mono["itl_engine_p99"]
                 if mono["itl_engine_p99"] else None)
        if ratio is not None and ratio <= 1.0:
            break
    token_identical = mono["streams"] == disagg["streams"]
    handoff_ms = [1000.0 * s for s in disagg["handoff_s"]]
    hit_rate = (disagg["prefix_matched"] / disagg["handoffs"]
                if disagg["handoffs"] else None)
    rec = {
        "mode": "fleet_disagg",
        "replicas_mono": 2,
        "tiers": {"prefill": 1, "decode": 1},
        "requests": 2 * requests,
        "kv_block_size": block,
        "sys_prompt_blocks": 2,
        "attempts_used": attempt + 1,
        "tokens_per_sec_mono": mono["tps"],
        "tokens_per_sec_disagg": disagg["tps"],
        "itl_s_p99_interactive_mono": mono_p99,
        "itl_s_p99_interactive_disagg": dis_p99,
        "itl_s_p99_engine_mono": mono["itl_engine_p99"],
        "itl_s_p99_engine_disagg": disagg["itl_engine_p99"],
        "disagg_itl_p99_ratio": ratio,
        "handoffs": disagg["handoffs"],
        "handoff_fails": disagg["handoff_fails"],
        "handoff_p50_ms": pctl(handoff_ms, 0.50),
        "handoff_p99_ms": pctl(handoff_ms, 0.99),
        "cross_tier_prefix_hit_rate": hit_rate,
        "goodput_by_tenant": disagg["goodput_by_tenant"],
        "goodput_floor_min_tenant": (
            min(disagg["goodput_by_tenant"].values())
            if disagg["goodput_by_tenant"] else None),
        "preemptions": disagg["preemptions"],
        "token_identical": token_identical,
        "all_completed": mono["ok"] and disagg["ok"],
    }
    assert token_identical, (
        "disaggregated token streams diverged from the monolithic fleet")
    assert disagg["handoff_fails"] == 0, (
        f"{disagg['handoff_fails']} handoffs degraded to local re-prefill")
    return rec


def bench_fleet_rollout(compiled, max_slots: int, prompt_len: int,
                        new_tokens: int, requests: int) -> dict:
    """Live-model-delivery arm (``--rollout``): a live trainer pushes
    into a PS group while the fleet serves, and the rollout plane
    delivers. Three phases on one row:

    1. **Swap tax + identity** — two 2-replica fleets run the standard
       seeded workload: one bare, one with a per-step version-gated
       ``WeightSubscriber`` (follow mode) on every engine while a
       trainer thread pushes ZERO deltas. The swaps are real (version
       changes, ``install_weights`` fires mid-stream) but the weights
       are byte-identical, so the token streams must equal the bare
       fleet's — the atomic-swap proof the gate holds with the
       ``token_identical`` equal-rule. The ITL p99 ratio between the
       arms is the swap tax (``swap_itl_p99_ratio``, ceiling 1.5), and
       a post-push quiet window measures the steady-state wire cost of
       the subscription (not-modified frames only).
    2. **Canary promote** — a 3-replica fleet under a
       ``RolloutController`` (goodput judge, short bake): one real
       delta push must reach every replica through the canary arc with
       zero dropped requests while traffic flows.
    3. **Forced rollback** — a second push with the judge pinned to
       "bad": the canary must return to the approved version, and
       ``rollback_served_stale`` counts non-canary replicas ever
       OBSERVED at the poisoned version — committed at exactly 0 (the
       blast-radius proof).

    The whole-arc ``rollout_goodput_ratio`` (router ledger, lifetime
    worst objective) carries the gate floor: delivery must not cost the
    fleet its attainment. The controller's replay-stable event digest
    rides the row for the incident-timeline cross-check.
    """
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np

    from elephas_tpu.parameter import ShardGroup
    from elephas_tpu.parameter.server import _ps_counters
    from elephas_tpu.rollout import (RolloutController, WeightSubscriber,
                                     goodput_judge)
    from elephas_tpu.serving import ReplicaSet, Router

    vocab = compiled.module.vocab_size
    factory = _engine_factory(compiled, max_slots, prompt_len, new_tokens,
                              2 * max(requests, 1) + 8)
    zero_delta = jax.tree_util.tree_map(
        lambda a: np.zeros_like(np.asarray(a)), compiled.params)
    _, bytes_tx, _ = _ps_counters("socket")

    # -- phase 1: swap tax + mid-stream token identity ------------------
    def run_arm(subscribe: bool):
        group = ShardGroup(compiled.params, 2, mode="socket")
        group.start()
        rs = ReplicaSet(factory, initial=2)
        router = Router(rs)
        stop = threading.Event()
        pusher = None
        subs = []
        try:
            router.result(router.submit([1] * prompt_len, max_new_tokens=2),
                          timeout_s=60.0)
            for rep in rs.serving():
                rep.engine.metrics.reset()
            if subscribe:
                client = group.client()
                subs = [WeightSubscriber(client, every=1, follow=True)
                        .attach(rep.engine) for rep in rs.serving()]

                def push_loop():
                    trainer = group.client()
                    while not stop.is_set():
                        trainer.update_parameters(zero_delta)
                        time.sleep(0.03)

                pusher = threading.Thread(target=push_loop, daemon=True)
                pusher.start()
            tps, tokens, results = _fleet_workload(
                lambda p, n: router.submit(p, max_new_tokens=n),
                lambda r: router.result(r, timeout_s=120.0),
                vocab, prompt_len, new_tokens, requests)
            stop.set()
            if pusher is not None:
                pusher.join(timeout=5.0)
            steady = None
            if subscribe:
                # Quiet window: pushes stopped, version static — every
                # subscriber poll must now cost only not-modified
                # frames. The byte delta is the steady-state wire tax.
                polls0 = sum(s.pulls for s in subs)
                b0 = bytes_tx.value
                _fleet_workload(
                    lambda p, n: router.submit(p, max_new_tokens=n),
                    lambda r: router.result(r, timeout_s=120.0),
                    vocab, prompt_len, new_tokens, max(4, requests // 3))
                polls = sum(s.pulls for s in subs) - polls0
                steady = {
                    "bytes": bytes_tx.value - b0,
                    "polls": polls,
                    "swaps": sum(s.swaps for s in subs),
                    "unchanged": sum(s.unchanged for s in subs),
                    "failures": sum(s.failures for s in subs),
                }
            itl = max(rep.engine.stats()["itl_s_p99"] or 0.0
                      for rep in rs.serving())
            ok = all(r.status == "completed" for r in results)
            return tokens, itl, ok, steady
        finally:
            router.close()
            group.stop()

    bare_tokens, bare_itl, bare_ok, _ = run_arm(False)
    swap_tokens, swap_itl, swap_ok, steady = run_arm(True)
    token_identical = bare_tokens == swap_tokens
    swap_ratio = (swap_itl / bare_itl) if bare_itl else None
    assert token_identical, (
        "mid-stream zero-delta swaps changed the token streams — the "
        "step-boundary install is not atomic")
    assert steady["swaps"] >= 1, (
        "the subscriber arm never actually swapped — the phase proved "
        "nothing")

    # -- phases 2+3: canary promote, then forced rollback ---------------
    wal_root = tempfile.mkdtemp(prefix="rollout-bench-wal-")
    group = ShardGroup(compiled.params, 2, mode="socket",
                       wal_root=wal_root, wal_keep=16)
    group.start()
    rs = ReplicaSet(factory, initial=3)
    router = Router(rs)
    ctrl = RolloutController(
        rs, group.client(), bake_s=0.2, min_results=2,
        judge=goodput_judge(tolerance=0.5))
    router.attach_rollout(ctrl)
    trainer = group.client()
    real_delta = jax.tree_util.tree_map(
        lambda a: np.full_like(np.asarray(a), 1e-4), compiled.params)
    rng = np.random.default_rng(31)
    all_ok = [True]
    stale = [0]
    bad_version = [None]

    def wave(n: int):
        rids = []
        for _ in range(n):
            plen = int(rng.integers(1, prompt_len + 1))
            prompt = rng.integers(1, vocab, plen).tolist()
            rids.append(router.submit(prompt, max_new_tokens=new_tokens))
        for r in rids:
            res = router.result(r, timeout_s=120.0)
            all_ok[0] = all_ok[0] and res.status == "completed"
            router.tick()
            if bad_version[0] is not None:
                for rep in rs.serving():
                    if rep.rollout_canary or rep.engine is None:
                        continue
                    if rep.engine.model_version == bad_version[0]:
                        stale[0] += 1

    try:
        router.result(router.submit([1] * prompt_len, max_new_tokens=2),
                      timeout_s=60.0)
        router.tick()  # seeds the approved baseline (version 0)
        base = ctrl.doc()["approved_version"]
        trainer.update_parameters(real_delta)
        good_version = (base or 0) + 1
        deadline = time.perf_counter() + 90.0
        while ctrl.rollouts < 1 and time.perf_counter() < deadline:
            wave(3)
        promoted = ctrl.rollouts >= 1
        converged = promoted and all(
            rep.engine.model_version == good_version
            for rep in rs.serving())
        assert converged, (
            f"promote arc did not converge: phase={ctrl.doc()['phase']} "
            f"versions={ctrl.doc()['versions']}")

        ctrl.judge = lambda canary, fleet, window_s, now: False
        trainer.update_parameters(real_delta)
        bad_version[0] = good_version + 1
        deadline = time.perf_counter() + 90.0
        while ctrl.rollbacks < 1 and time.perf_counter() < deadline:
            wave(3)
        doc = ctrl.doc()
        rolled_back = ctrl.rollbacks >= 1
        assert rolled_back and doc["approved_version"] == good_version, (
            f"rollback arc did not converge: phase={doc['phase']} "
            f"approved={doc['approved_version']}")
        slo = router.slo.snapshot()
        rec = {
            "mode": "fleet_rollout",
            "replicas": 3,
            "requests": requests,
            "token_identical": token_identical,
            "all_completed": bare_ok and swap_ok and all_ok[0],
            "swap_itl_p99_ratio": swap_ratio,
            "itl_s_p99_bare": bare_itl,
            "itl_s_p99_subscribed": swap_itl,
            "steady_pull_bytes": steady["bytes"],
            "steady_pull_polls": steady["polls"],
            "steady_pull_bytes_per_poll": (
                steady["bytes"] / steady["polls"] if steady["polls"]
                else None),
            "swaps_delivered": steady["swaps"],
            "pull_failures": steady["failures"],
            "rollout_promoted": ctrl.rollouts,
            "rollout_rolled_back": ctrl.rollbacks,
            "rollback_served_stale": stale[0],
            "rollout_goodput_ratio": slo["goodput_ratio"],
            "approved_version": doc["approved_version"],
            "rejected_version": bad_version[0],
            "rollout_digest": doc["digest"],
            "rollout_events": [e["kind"] for e in doc["events"]],
        }
    finally:
        router.close()
        group.stop()
        shutil.rmtree(wal_root, ignore_errors=True)
    assert stale[0] == 0, (
        f"{stale[0]} non-canary observations served the poisoned "
        "version — canary containment failed")
    return rec


def main(argv=None) -> list:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--batches", type=int, nargs="+", default=[1, 8, 32])
    parser.add_argument("--prompt-len", type=int, default=32)
    parser.add_argument("--new", type=int, default=64)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--vocab", type=int, default=512)
    parser.add_argument("--d-model", type=int, default=128)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--serving-slots", type=int, default=4)
    parser.add_argument("--serving-requests", type=int, default=12)
    parser.add_argument("--out", type=str, default=None,
                        help="also write records as a JSON array")
    parser.add_argument("--serve-out", type=str, default=None,
                        help="write the serving arms (before/after "
                             "pipelining) as their own JSON artifact")
    parser.add_argument("--trace", type=str, default=None,
                        help="record one traced pipelined serving run's "
                             "span tree to this Chrome trace JSON, plus a "
                             "trace_report.py summary next to it (.md)")
    parser.add_argument("--no-overhead-check", action="store_true",
                        help="skip the traced-vs-untraced < 2%% guardrail "
                             "(6 extra serving runs)")
    parser.add_argument("--store-overhead", action="store_true",
                        help="append the durable-telemetry-store "
                             "overhead row: serving throughput with the "
                             "ops endpoint mounted, store vs no store "
                             "(gated under 2%% like trace/canary)")
    parser.add_argument("--slo", action="store_true",
                        help="run the goodput + blackbox-canary arm "
                             "(SLO attainment ratios, canary probe SLIs, "
                             "and the canaried-vs-plain < 2%% overhead "
                             "measurement)")
    parser.add_argument("--prefix", action="store_true",
                        help="run the paged-pool arm: prefix-cache hit "
                             "economics on a shared-system-prompt "
                             "multi-turn workload, token identity "
                             "to generate() per row, and the chunked-vs-"
                             "unchunked prefill ITL p99 tail")
    parser.add_argument("--spec", action="store_true",
                        help="run the speculative-decoding arm: draft-"
                             "and-verify vs the unspeculated oracle on "
                             "the shared-prefix workload — accept rate, "
                             "tokens/step, ITL ratio, token identity, "
                             "compile-counter pins; draft params "
                             "delivered by a real 2-shard PS group")
    parser.add_argument("--gamma", type=int, default=3,
                        help="draft window length for the --spec arm")
    parser.add_argument("--fleet", action="store_true",
                        help="run the replicated-fleet arms: routed-vs-"
                             "bare overhead + token identity, N-replica "
                             "session-affinity throughput, kill-a-"
                             "replica-mid-traffic chaos, and the "
                             "autoscaler decision replay")
    parser.add_argument("--tenants", action="store_true",
                        help="run the two-tenant cost-attribution arm: "
                             "tagged-vs-untagged overhead (< 2%%), mixed "
                             "interactive/batch traffic through the "
                             "router with exact per-tenant token "
                             "conservation and the exemplar-to-trace "
                             "join (appends to the fleet artifact)")
    parser.add_argument("--disagg", action="store_true",
                        help="run the disaggregated prefill/decode tier "
                             "arm: tiered-vs-monolithic token identity "
                             "across the KV-block handoff, decode-tier "
                             "ITL p99 under long-prompt interference, "
                             "handoff latency p50/p99, cross-tier "
                             "prefix hits, and the per-tenant fair-"
                             "share goodput floor (appends to the "
                             "fleet artifact)")
    parser.add_argument("--rollout", action="store_true",
                        help="run the live-model-delivery arm: "
                             "mid-stream swap identity + swap-tax ITL "
                             "ratio, steady-state subscription bytes, "
                             "and a full canary promote + forced "
                             "rollback under a live trainer (appends "
                             "to the fleet artifact)")
    parser.add_argument("--fleet-out", type=str, default=None,
                        help="write the fleet arms as their own JSON "
                             "artifact (BENCH_FLEET.json)")
    parser.add_argument("--fleet-replicas", type=int, default=3)
    parser.add_argument("--fleet-sessions", type=int, default=6)
    parser.add_argument("--fleet-turns", type=int, default=4)
    args = parser.parse_args(argv)

    import jax

    compiled = build_model(
        args.vocab, args.d_model, args.heads, args.layers,
        max_seq=args.prompt_len + args.new + 1,
    )
    records = [{
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "params": compiled.count_params(),
        "d_model": args.d_model,
        "layers": args.layers,
    }]
    for batch in args.batches:
        for use_cache in (True, False):
            rec = bench_generate(
                compiled, batch, args.prompt_len, args.new, use_cache,
                args.reps,
            )
            records.append(rec)
            print(json.dumps(rec))
    serving_records = []
    for pipeline in (False, True):  # reference first, then the hot path
        rec = bench_serving(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests, pipeline=pipeline,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if not args.no_overhead_check:
        rec = bench_trace_overhead(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.store_overhead:
        rec = bench_store_overhead(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.slo:
        # 3x the serving-arm request count: the canary arm measures
        # probe cost as a throughput delta, and at the base count the
        # fixed 3 probes are a 25% probe rate — an interference stress
        # test, not the guardrail's claim. Tripling the real traffic
        # amortizes probes to ~8%, still far above any production
        # canary rate, so the 2% ceiling gates probe COST rather than
        # the workload's granularity.
        rec = bench_slo(
            compiled, args.serving_slots, args.prompt_len, args.new,
            3 * args.serving_requests,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.prefix:
        rec = bench_prefix(
            compiled, args.serving_slots, args.prompt_len, args.new,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.spec:
        rec = bench_spec(
            compiled, args.serving_slots, args.prompt_len, args.new,
            gamma=args.gamma,
        )
        serving_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    fleet_records = []
    if args.fleet:
        for rec in (
            bench_fleet_routed_vs_bare(
                compiled, args.serving_slots, args.prompt_len, args.new,
                args.serving_requests,
            ),
            bench_fleet_n(
                compiled, args.serving_slots, args.prompt_len, args.new,
                replicas=args.fleet_replicas,
                sessions=args.fleet_sessions, turns=args.fleet_turns,
            ),
            bench_fleet_kill(
                compiled, args.serving_slots, args.prompt_len, args.new,
                replicas=args.fleet_replicas,
            ),
            bench_fleet_autoscale(),
        ):
            fleet_records.append(rec)
            records.append(rec)
            print(json.dumps(rec))
    if args.tenants:
        rec = bench_fleet_tenants(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests,
        )
        fleet_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.disagg:
        rec = bench_fleet_disagg(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests,
        )
        fleet_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.rollout:
        rec = bench_fleet_rollout(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests,
        )
        fleet_records.append(rec)
        records.append(rec)
        print(json.dumps(rec))
    if args.trace:
        from elephas_tpu.obs import Tracer

        import scripts.trace_report as trace_report

        tracer = Tracer()
        bench_serving(
            compiled, args.serving_slots, args.prompt_len, args.new,
            args.serving_requests, pipeline=True, tracer=tracer,
        )
        tracer.export_chrome(args.trace)
        report_path = os.path.splitext(args.trace)[0] + ".md"
        text = trace_report.report(args.trace)
        with open(report_path, "w") as f:
            f.write(text)
        print(f"trace: {args.trace} (Perfetto-viewable); report: "
              f"{report_path}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    if args.serve_out:
        with open(args.serve_out, "w") as f:
            json.dump([records[0], *serving_records], f, indent=1)
    if args.fleet_out:
        with open(args.fleet_out, "w") as f:
            json.dump([records[0], *fleet_records], f, indent=1)
    return records


if __name__ == "__main__":
    from elephas_tpu.utils.compiler import configure_compile_cache

    configure_compile_cache()
    main()
