"""Pipelined decode hot path: token identity, donation safety, overlap.

The pipelined scheduler (one-step lookahead: dispatch decode N+1 before
reading N's tokens) must be OBSERVABLY IDENTICAL to the unpipelined
reference path — same token streams per request over the full serving
matrix (ragged prompts, EOS stops, deadline evictions, mid-decode
admissions, slot reuse). The allowed differences are internal: stop
detection lands one decode iteration late (exactly one extra dispatched
step per workload tail), and admissions join the decode batch one step
later.

Buffer donation is the other invariant under test: every program that
rewrites the KV pool donates it, the stale buffers really die
(``is_deleted``), and the pool boundary turns any stale read into
``DonatedBufferError`` — while a full serving workload never trips it.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.metrics import JsonlSink
from elephas_tpu.models import get_model
from elephas_tpu.serving import DonatedBufferError, InferenceEngine
from tests.test_serving import FakeClock, _engine, _per_row

VOCAB, SEQ = 97, 64


@pytest.fixture(scope="module")
def compiled():
    return CompiledModel(
        get_model(
            "transformer_lm", vocab_size=VOCAB, d_model=32, num_heads=4,
            num_layers=2, max_seq_len=SEQ,
        ),
        optimizer={"name": "adam", "learning_rate": 3e-3},
        loss="sparse_categorical_crossentropy",
        metrics=[],
        input_shape=(SEQ,),
        input_dtype=jnp.int32,
        seed=0,
    )


def _run_both(compiled, script, **engine_kw):
    """Run the same scripted workload on a pipelined and an unpipelined
    engine; return both result dicts keyed by the script's request tags.

    ``script`` is a list of ops executed in order against each engine:
    ``("submit", tag, prompt, kwargs)`` / ``("step", n)`` /
    ``("advance", dt)`` (FakeClock only) / ``("drain",)``.
    """
    out = []
    for pipeline in (True, False):
        kw = dict(engine_kw)
        clock = kw.pop("fake_clock", None)
        if clock is not None:
            kw["clock"] = FakeClock()
        eng = _engine(compiled, pipeline=pipeline, **kw)
        rids = {}
        for op in script:
            if op[0] == "submit":
                _, tag, prompt, skw = op
                rids[tag] = eng.submit(prompt, **skw)
            elif op[0] == "step":
                for _ in range(op[1]):
                    eng.step()
            elif op[0] == "advance":
                eng.clock.advance(op[1])
            elif op[0] == "drain":
                eng.run_until_drained()
        results = {
            tag: eng.result(rid, timeout_s=120) for tag, rid in rids.items()
        }
        stats = eng.stats()
        assert stats["prefill_traces"] == 1, f"pipeline={pipeline} retraced"
        assert stats["decode_traces"] == 1, f"pipeline={pipeline} retraced"
        out.append(results)
    pipelined, sync = out
    assert pipelined.keys() == sync.keys()
    return pipelined, sync


def _assert_identical(pipelined, sync):
    for tag in sync:
        assert pipelined[tag].status == sync[tag].status, tag
        assert pipelined[tag].tokens == sync[tag].tokens, (
            f"request {tag!r}: pipelined {pipelined[tag].tokens} != "
            f"unpipelined {sync[tag].tokens}"
        )


# -- token identity matrix -------------------------------------------------


def test_identity_ragged_prompts_with_slot_reuse(compiled):
    """More ragged requests than slots: identical streams in both modes,
    and both match single-row generate."""
    prompts = [[5, 3, 9], [7, 2, 8, 4, 1, 6], [11, 12], [1, 2, 3, 4],
               [9, 8, 7], [2, 4, 6, 8, 1]]
    script = [("submit", i, p, {"max_new_tokens": 6}) for i, p in
              enumerate(prompts)] + [("drain",)]
    pipelined, sync = _run_both(compiled, script, max_slots=3)
    _assert_identical(pipelined, sync)
    for i, p in enumerate(prompts):
        assert pipelined[i].tokens == _per_row(compiled, p, 6)


def test_identity_eos_stop(compiled):
    """EOS mid-stream: both modes stop at the same token even though the
    pipelined path detects the stop one iteration late."""
    free = _per_row(compiled, [5, 3, 9], 10)
    stop = free[3]
    script = [
        ("submit", "a", [5, 3, 9], {"max_new_tokens": 10}),
        ("submit", "b", [7, 2, 8, 4], {"max_new_tokens": 10}),
        ("drain",),
    ]
    pipelined, sync = _run_both(compiled, script, stop_token=stop)
    _assert_identical(pipelined, sync)
    # stopped at EOS inclusive: its first occurrence in the free stream
    assert pipelined["a"].tokens == free[:free.index(stop) + 1]


def test_identity_mid_decode_admission(compiled):
    """A request admitted while another is mid-decode: both modes serve
    both requests identically (admission joining one step later on the
    pipelined path must not change any stream)."""
    script = [
        ("submit", "first", [5, 3, 9], {"max_new_tokens": 10}),
        ("step", 3),
        ("submit", "late", [7, 2, 8, 4], {"max_new_tokens": 8}),
        ("drain",),
    ]
    pipelined, sync = _run_both(compiled, script, max_slots=2)
    _assert_identical(pipelined, sync)
    assert pipelined["late"].tokens == _per_row(compiled, [7, 2, 8, 4], 8)


def test_identity_deadline_eviction(compiled):
    """Deadline eviction under a fake clock: the evicted request returns
    the SAME partial token list in both modes (pipelined harvests the
    previous step before evicting; unpipelined evicts before decoding —
    the orderings cancel the one-step lag exactly)."""
    script = [
        ("submit", "doomed", [5, 3, 9],
         {"max_new_tokens": 1000, "timeout_s": 5.0}),
        ("submit", "healthy", [7, 2], {"max_new_tokens": 4}),
    ]
    for _ in range(7):
        script += [("advance", 1.0), ("step", 1)]
    script += [("drain",)]
    pipelined, sync = _run_both(
        compiled, script, max_slots=2, fake_clock=True
    )
    _assert_identical(pipelined, sync)
    assert pipelined["doomed"].status == "timeout"
    assert 0 < len(pipelined["doomed"].tokens) < 1000
    assert pipelined["healthy"].status == "completed"
    assert pipelined["healthy"].tokens == _per_row(compiled, [7, 2], 4)


def test_identity_expiry_in_queue(compiled):
    """A request that times out while still queued: empty timeout result
    in both modes, no prefill burned."""
    script = [
        ("submit", "busy", [1, 2], {"max_new_tokens": 30}),
        ("submit", "doomed", [3, 4], {"max_new_tokens": 5, "timeout_s": 2.0}),
    ]
    for _ in range(6):
        script += [("advance", 1.0), ("step", 1)]
    script += [("drain",)]
    pipelined, sync = _run_both(
        compiled, script, max_slots=1, fake_clock=True
    )
    _assert_identical(pipelined, sync)
    assert pipelined["doomed"].status == "timeout"
    assert pipelined["doomed"].tokens == []


def test_stop_detection_costs_exactly_one_iteration(compiled):
    """The pipelined path's documented cost: one extra dispatched decode
    iteration per request tail (the step in flight when the final token
    is harvested), and not one more."""
    counts = {}
    for pipeline in (True, False):
        eng = _engine(compiled, max_slots=1, pipeline=pipeline)
        calls = []
        inner = eng.scheduler.decode_fn
        eng.scheduler.decode_fn = lambda *a, **k: (calls.append(1),
                                                  inner(*a, **k))[1]
        res = eng.result(eng.submit([5, 3, 9], max_new_tokens=6),
                         timeout_s=120)
        assert res.tokens == _per_row(compiled, [5, 3, 9], 6)
        eng.run_until_drained()  # retire the trailing in-flight step
        counts[pipeline] = len(calls)
    assert counts[True] == counts[False] + 1


# -- donation safety -------------------------------------------------------


def test_decode_donation_kills_stale_cache_reference(compiled):
    """The decode step really donates: buffers held before a step are
    deleted after it, and reading them raises — stale aliases cannot
    silently see pre-donation data."""
    eng = _engine(compiled, max_slots=2)
    eng.submit([5, 3, 9], max_new_tokens=6)
    eng.step()  # admit (the chunk program already donates the pool)
    stale = eng.pool.cache
    eng.step()  # decode step donates `stale`
    leaf = jax.tree_util.tree_leaves(stale)[0]
    assert leaf.is_deleted()
    # JAX's own check says "Array has been deleted" (RuntimeError); under
    # load the runtime's comes first now and then: "Buffer has been deleted
    # or donated" (ValueError). Either is the loud error this test wants.
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        jnp.sum(leaf).block_until_ready()
    eng.run_until_drained()


def test_pool_guard_raises_donated_buffer_error(compiled):
    """The pool boundary refuses to hand out donated buffers: a swap
    back to a stale tree (the forgot-to-swap failure mode) surfaces as
    DonatedBufferError at `.cache`, not a deep XLA error."""
    eng = _engine(compiled, max_slots=2)
    eng.submit([5, 3, 9], max_new_tokens=4)
    eng.step()
    stale = eng.pool.cache
    eng.step()  # donates `stale`
    live = eng.pool.cache  # fine: the pool swapped in the fresh tree
    assert not jax.tree_util.tree_leaves(live)[0].is_deleted()
    eng.pool.swap(stale)  # simulate the bug the guard exists for
    with pytest.raises(DonatedBufferError):
        _ = eng.pool.cache
    eng.pool.swap(live)  # restore and finish cleanly
    eng.run_until_drained()


def test_engine_never_trips_donation_guard(compiled):
    """A full mixed workload (ragged prompts, EOS, slot reuse) runs with
    donation on every decode step and never reads a dead buffer."""
    free = _per_row(compiled, [5, 3, 9], 8)
    eng = _engine(compiled, max_slots=2, stop_token=free[4])
    prompts = [[5, 3, 9], [7, 2, 8, 4], [11, 12], [1, 2, 3]]
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    for rid in rids:
        assert eng.result(rid, timeout_s=120).status == "completed"
    assert not jax.tree_util.tree_leaves(eng.pool.cache)[0].is_deleted()


# -- overlap gauge ---------------------------------------------------------


def test_dispatch_to_fetch_gauge_in_sink(compiled, tmp_path):
    """Every harvested step records its dispatch→fetch window; the gauge
    reaches the JSONL step records and the summary."""
    path = str(tmp_path / "serving.jsonl")
    with JsonlSink(path) as sink:
        eng = _engine(compiled, sink=sink)
        eng.result(eng.submit([5, 3, 9], max_new_tokens=5), timeout_s=120)
    steps = [
        json.loads(l) for l in open(path)
        if json.loads(l)["event"] == "step"
    ]
    gauges = [s["dispatch_to_fetch_s"] for s in steps]
    harvested = [g for g in gauges if g is not None]
    assert harvested and all(g >= 0 for g in harvested)
    summary = eng.metrics.summary()
    assert summary["dispatch_to_fetch_s_avg"] is not None
    assert summary["dispatch_to_fetch_s_avg"] >= 0
