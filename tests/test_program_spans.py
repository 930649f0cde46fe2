"""The spans and counters the program records where its work happens
(ISSUE 27): the phases of a scheduler step, per-request queue and token
times, the parts of a `fit` call and of an epoch, compiles as spans, and
the dynamics gauges computed on the device.
"""

import os
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import SparkModel, obs, to_simple_rdd
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.engine.sync import _dynamics_norms
from elephas_tpu.models import get_model
from elephas_tpu.obs import Tracer
from elephas_tpu.serving import InferenceEngine
from elephas_tpu.serving.metrics import ServingMetrics

from conftest import make_blobs

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import trace_report  # noqa: E402

VOCAB, SEQ = 97, 64
PROMPTS = [[5, 3, 9, 4, 1, 6, 2], [7, 2, 8, 4, 1, 6, 11, 12, 13, 14, 15],
           [11, 12], [1, 2, 3, 4, 9, 9, 9, 9, 9], [3, 1, 4, 1, 5]]
NEW_FIELDS = ("harvest_wait_s", "admit_s", "prefill_s", "dispatch_s",
              "prefill_tokens", "prefill_chunks", "lane_lengths",
              "kv_blocks_in_use", "kv_blocks_total")


class TickClock:
    """A clock that moves a millisecond at every read: spans get lengths
    without a device."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3
        return self.t


class ListSink:
    def __init__(self):
        self.steps, self.requests = [], []

    def log(self, step, **fields):
        {"step": self.steps, "request": self.requests}[fields["event"]].append(fields)


@pytest.fixture(scope="module")
def compiled():
    return CompiledModel(
        get_model("transformer_lm", vocab_size=VOCAB, d_model=32, num_heads=4,
                  num_layers=2, max_seq_len=SEQ),
        optimizer={"name": "adam", "learning_rate": 3e-3},
        loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(SEQ,), input_dtype=jnp.int32, seed=0,
    )


def _serve(compiled, tracer, clock=time.monotonic):
    """Every prompt through a 3-slot paged engine, one chunk a step."""
    sink = ListSink()
    eng = InferenceEngine(compiled, max_slots=3, max_prompt_len=12, max_len=32,
                          queue_depth=8, kv_block_size=4, prefill_chunk=4,
                          prefill_chunks_per_step=1, prefix_cache=False,
                          sink=sink, clock=clock, tracer=tracer)
    ids = [eng.submit(p, max_new_tokens=5 + i, stop_token=None)
           for i, p in enumerate(PROMPTS)]
    results = [eng.result(rid, timeout_s=120) for rid in ids]
    while eng.scheduler.has_work:  # the pipelined step still in flight
        eng.step()
    return eng, sink, results


@pytest.fixture(scope="module")
def traced(compiled):
    clock = TickClock()
    tracer = Tracer(capacity=1 << 16, clock=clock, annotate_device=False)
    eng, sink, results = _serve(compiled, tracer, clock)
    return SimpleNamespace(eng=eng, sink=sink, results=results,
                           events=tracer.events())


def _tiled(parent, kids):
    """`kids`, in order, lie inside `parent` and do not overlap."""
    for kid in kids:
        assert parent.begin_s <= kid.begin_s <= kid.end_s <= parent.end_s, (parent, kid)
    for a, b in zip(kids, kids[1:]):
        assert a.end_s <= b.begin_s, (a, b)
    assert sum(k.duration_s for k in kids) <= parent.duration_s + 1e-12


def test_step_spans_tile_their_step(traced):
    """A step's phases are its children by id and tile it; `step/prefill`
    is one of them, and the chunks and syncs it ran are ITS children;
    `step/record` (what follows the step's close: the `step` event, the
    load tracker) is the step's child by id and begins where it ends, and
    `step/publish` (the engine's, where the step finished a request)
    follows that."""
    steps = [e for e in traced.events if e.name == "sched_step"]
    assert len(steps) == len(traced.sink.steps) > 5
    by_parent = {}
    for e in traced.events:
        if e.name.startswith("step/") or e.name == "dispatch":
            by_parent.setdefault(e.parent_id, []).append(e)
    prefills = {e.span_id: e for e in traced.events if e.name == "step/prefill"}
    assert set(by_parent) <= {s.span_id for s in steps} | set(prefills)
    seen = set()
    for step in steps:
        kids = sorted(by_parent.get(step.span_id, []), key=lambda e: e.begin_s)
        assert kids, "a step with work records its phases"
        seen.update(kid.name for kid in kids)
        inside = [k for k in kids if k.name not in ("step/record", "step/publish")]
        after = kids[len(inside):]
        assert [k.name for k in after] in (["step/record"],
                                           ["step/record", "step/publish"])
        assert after[0].begin_s == step.end_s
        assert all(a.end_s <= b.begin_s for a, b in zip(after, after[1:]))
        _tiled(step, inside)
    assert seen == {"dispatch", "step/harvest_wait", "step/harvest_book",
                    "step/evict", "step/admit", "step/prefill", "step/record",
                    "step/publish"}
    published = [e for e in traced.events if e.name == "step/publish"]
    assert sum(e.args["finished"] for e in published) == len(PROMPTS)
    assert prefills
    for span_id, prefill in prefills.items():
        kids = sorted(by_parent[span_id], key=lambda e: e.begin_s)
        assert {k.name for k in kids} <= {"step/prefill_chunk", "step/prefill_sync"}
        _tiled(prefill, kids)
    assert sum(len(by_parent[i]) for i in prefills) == sum(
        e.name in ("step/prefill_chunk", "step/prefill_sync") for e in traced.events)
    chunk = next(e for e in traced.events if e.name == "step/prefill_chunk")
    assert {k: type(v) for k, v in chunk.args.items()} == {
        "slot": int, "start": int, "valid": int}


def test_step_counters_conserve_tokens(traced):
    steps, metrics = traced.sink.steps, traced.eng.metrics
    assert sum(s["prefill_tokens"] for s in steps) == sum(map(len, PROMPTS))
    assert sum(s["prefill_chunks"] for s in steps) == sum(
        -(-len(p) // 4) for p in PROMPTS)
    assert metrics.tokens_prefilled_total == sum(map(len, PROMPTS))
    # a request's first token is its prefill's; every other is a decode
    # step's, counted in the step that harvested it
    decoded = sum(len(r.tokens) - 1 for r in traced.results)
    assert metrics.tokens_emitted_total == decoded
    assert sum(s["step_tokens"] for s in steps) == decoded
    assert metrics.tokens_out == decoded + len(PROMPTS)
    summary = metrics.summary()
    assert summary["tokens_emitted_total"] == decoded
    assert summary["tokens_prefilled_total"] == sum(map(len, PROMPTS))
    for s in steps:
        host = s["step_seconds"] - s["harvest_wait_s"]
        assert host >= s["admit_s"] + s["prefill_s"] + s["dispatch_s"] - 1e-12
        assert 0 <= s["kv_blocks_in_use"] <= s["kv_blocks_total"] == 24
    assert max(s["kv_blocks_in_use"] for s in steps) >= 3 * 3


def _family(name):
    """A tiny model of each family the engine serves: K/V alone, and K/V
    of one layer in four beside the recurrent and convolution state of the
    others."""
    sizes = dict(vocab_size=VOCAB, d_model=32, num_heads=4, max_seq_len=SEQ)
    if name == "jamba_lm":
        sizes.update(num_layers=4, num_kv_heads=1, d_ff=64, d_state=4, dt_rank=8,
                     attn_period=4, attn_offset=1)
    else:
        sizes.update(num_layers=2)
    return CompiledModel(
        get_model(name, **sizes), optimizer={"name": "adam", "learning_rate": 3e-3},
        loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(SEQ,), input_dtype=jnp.int32, seed=0)


@pytest.mark.parametrize("family", ["transformer_lm", "jamba_lm"])
def test_step_events_and_chunk_spans_say_what_the_engine_was_asked(family):
    """What the benchmark counts from: the `step` events' `prefill_tokens`,
    `prefill_chunks` and `lane_lengths` and the `step/prefill_chunk` spans
    agree with the prompts and answers the engine was asked for, whatever
    the model carries through its steps."""
    clock = TickClock()
    tracer = Tracer(capacity=1 << 16, clock=clock, annotate_device=False)
    eng, sink, results = _serve(_family(family), tracer, clock)
    asked = [(len(p), 5 + i) for i, p in enumerate(PROMPTS)]
    assert [len(r.tokens) for r in results] == [n for _, n in asked]
    assert sum(s["prefill_tokens"] for s in sink.steps) == sum(n for n, _ in asked)
    chunks = sorted((e.args["start"], e.args["valid"]) for e in tracer.events()
                    if e.name == "step/prefill_chunk")
    assert chunks == sorted((at, min(4, n - at)) for n, _ in asked
                            for at in range(0, n, 4))
    assert sum(s["prefill_chunks"] for s in sink.steps) == len(chunks)
    # a prompt of n columns answered with m tokens decodes from n, n + 1,
    # ... n + m - 2 columns (its first token is its prefill's); the step in
    # flight when it finishes may hold it once more
    held = sorted(c for s in sink.steps for c in s["lane_lengths"])
    needed = sorted(c for n, m in asked for c in range(n, n + m - 1))
    spare = list(held)
    for c in needed:
        spare.remove(c)
    assert len(spare) <= len(asked) and set(spare) <= {n + m - 1 for n, m in asked}
    assert all(len(s["lane_lengths"]) <= 3 for s in sink.steps)
    # both programs' attention bodies, on every event and in stats()
    for key in ("decode_attention", "prefill_attention"):
        assert {s[key] for s in sink.steps} == {"paged_xla"} == {eng.stats()[key]}
    rows = 3 if family == "jamba_lm" else 0
    assert {s["state_slots_total"] for s in sink.steps} == {rows}
    assert max(s["state_slots_in_use"] for s in sink.steps) == rows
    assert all((s["state_bytes"] > 0) == bool(rows) for s in sink.steps)


def test_result_carries_queue_prefill_and_token_times(traced):
    for res, event in zip(traced.results,
                          sorted(traced.sink.requests, key=lambda e: e["req_id"])):
        assert res.status == "completed"
        assert res.queue_s > 0 and res.prefill_s > 0
        assert res.queue_s + res.prefill_s == pytest.approx(res.ttft_s, rel=1e-9)
        assert res.token_times[0] == res.ttft_s
        assert len(res.token_times) == len(res.tokens)
        gaps = np.diff(res.token_times)
        assert np.all(gaps > 0)
        assert gaps.mean() == pytest.approx(res.itl_s_avg, rel=1e-9)
        assert event["queue_s"] == res.queue_s and event["prefill_s"] == res.prefill_s
    # three slots: the fourth and fifth request wait for one to free
    assert traced.results[4].queue_s > traced.results[0].queue_s


def test_disabled_tracer_records_nothing_and_events_keep_their_fields(compiled):
    before = len(obs.default_tracer().events())
    _, sink, results = _serve(compiled, obs.NULL_TRACER)
    assert len(obs.default_tracer().events()) == before == 0
    assert len(obs.NULL_TRACER.events()) == 0
    for step in sink.steps:
        assert all(name in step for name in NEW_FIELDS)
    assert sum(s["prefill_tokens"] for s in sink.steps) == sum(map(len, PROMPTS))
    assert all(r.token_times and r.queue_s is not None for r in results)


def test_serving_metrics_reset_zeroes_the_step_counters():
    m = ServingMetrics()
    m.record_step(0, 1, tokens=3, step_seconds=0.1, prefill_tokens=7, prefill_chunks=1)
    assert (m.tokens_emitted_total, m.tokens_prefilled_total) == (3, 7)
    m.reset()
    assert (m.tokens_emitted_total, m.tokens_prefilled_total, m.steps) == (0, 0, 0)
    assert "finish-grained" in ServingMetrics.summary.__doc__


def test_record_takes_a_parent_named_before_it_is_recorded(tmp_path):
    tr = Tracer(capacity=8, annotate_device=False)
    parent = obs.new_span_id()
    tr.record("step/admit", 1.0, 2.0, parent_id=parent)
    tr.record("sched_step", 0.5, 2.5, span_id=parent)
    child, step = tr.events()
    assert child.parent_id == step.span_id == parent
    assert child.trace_id is None and child.span_id is None
    exported = tr.export_chrome(str(tmp_path / "t.json"))["traceEvents"]
    args = {e["name"]: e.get("args", {}) for e in exported if e["ph"] == "X"}
    assert args["step/admit"] == {"parent_id": parent}
    assert args["sched_step"] == {"span_id": parent}
    with obs.activate(obs.new_context()) as ctx:
        tr.record("dispatch", 1.0, 1.5, parent_id=parent)
    last = tr.events()[-1]
    assert last.trace_id == ctx.trace_id and last.parent_id == parent and last.span_id


def test_trace_report_reads_an_export_with_the_new_names(traced, tmp_path):
    from elephas_tpu.obs.trace import export_events

    path = str(tmp_path / "serve.json")
    export_events(traced.events, time.monotonic, path=path)
    rows = {r["phase"]: r for r in trace_report.phase_table(trace_report.load_events(path))}
    assert rows["sched_step"]["count"] == len(traced.sink.steps)
    assert rows["step/prefill_chunk"]["count"] == sum(-(-len(p) // 4) for p in PROMPTS)
    assert rows["step/harvest_wait"]["p50_s"] == pytest.approx(1e-3, rel=1e-6)
    assert "(req:" in trace_report.report(path)


# -- compiles as spans -----------------------------------------------------


def _fresh_program(n):
    return jax.jit(lambda x: jnp.tanh(x * n).sum())


def test_compiles_land_as_spans_only_while_tracing():
    tracer = obs.enable_tracing(capacity=256, annotate_device=False)
    try:
        with tracer.span("cause"):
            _fresh_program(3.0)(jnp.ones((3, 5)))
        events = tracer.events()
    finally:
        obs.disable_tracing()
    names = {e.name for e in events}
    assert {"compile/trace", "compile/lower"} <= names
    assert names & {"compile/backend", "compile/cache_load"}
    cause = next(e for e in events if e.name == "cause")
    for e in events:
        if e.name.startswith("compile/"):
            assert cause.begin_s <= e.begin_s <= e.end_s <= cause.end_s
            assert e.duration_s > 0 and "program" in e.args
    assert any("<lambda>" in e.args["program"] for e in events
               if e.name == "compile/backend")
    # disabled again: a new program compiles and leaves nothing anywhere
    _fresh_program(4.0)(jnp.ones((3, 5)))
    assert len(tracer.events()) == len(events)
    assert len(obs.default_tracer().events()) == 0


# -- the parts of a fit call -----------------------------------------------

NUM_CLASSES, DIM = 4, 16


def _mlp(seed=0):
    return CompiledModel(
        get_model("mlp", features=(32,), num_classes=NUM_CLASSES),
        optimizer={"name": "adam", "learning_rate": 0.01},
        loss="categorical_crossentropy", metrics=["acc"], input_shape=(DIM,), seed=seed,
    )


@pytest.fixture(scope="module")
def blobs16():
    return make_blobs(n=512, num_classes=NUM_CLASSES, dim=DIM, seed=3)


def _inside(inner, outer):
    return outer.begin_s <= inner.begin_s and inner.end_s <= outer.end_s


@pytest.mark.parametrize("stream_batches", [None, 2])
def test_fit_and_epoch_children_lie_inside_their_parents(blobs16, stream_batches):
    x, y = blobs16
    model = SparkModel(_mlp(), mode="synchronous", frequency="epoch", num_workers=2)
    seen = []
    tracer = obs.enable_tracing(capacity=1 << 14, annotate_device=False)
    try:
        model.fit(to_simple_rdd(None, x, y, 2), epochs=3, batch_size=16,
                  validation_split=0.1, stream_batches=stream_batches,
                  callbacks=[lambda epoch, state, metrics: seen.append(epoch)])
        events = tracer.events()
    finally:
        obs.disable_tracing()
    fits = [e for e in events if e.name == "fit"]
    assert len(fits) == 1 and seen == [0, 1, 2]
    fit = fits[0]
    assert fit.args == {"mode": "synchronous", "epochs": 3, "workers": 2}
    top = {"fit/prepare", "fit/trainer", "fit/on_fit_end", "fit/fold_back"}
    if stream_batches is None:
        top |= {"fit/state", "fit/stack", "fit/upload"}
    children = [e for e in events if e.name.startswith("fit/")]
    assert {e.name for e in children} == top
    ordered = sorted(children, key=lambda e: e.begin_s)
    for e in ordered:
        assert _inside(e, fit) and e.trace_id == fit.trace_id and e.parent_id == fit.span_id
    for a, b in zip(ordered, ordered[1:]):
        assert a.end_s <= b.begin_s
    epochs = [e for e in events if e.name == "train/epoch"]
    assert len(epochs) == 3
    for epoch in epochs:
        assert _inside(epoch, fit)
        kids = [e for e in events if e.parent_id == epoch.span_id
                and e.name.startswith("train/")]
        assert [k.name for k in sorted(kids, key=lambda e: e.begin_s)] == [
            "train/epoch/dispatch", "train/epoch/wait", "train/epoch/dynamics",
            "train/eval", "train/epoch/callbacks"]
        assert all(_inside(k, epoch) for k in kids)
        assert {"unit_loss", "delta_norm", "effective_step"} <= set(epoch.args)
    # the epoch program's compile sits inside the fit that caused it
    compiles = [e for e in events if e.name == "compile/backend" and _inside(e, fit)]
    assert compiles and all(e.trace_id == fit.trace_id for e in compiles)


@pytest.mark.parametrize("mode,workers", [("synchronous", 2), ("hogwild", 2),
                                          ("asynchronous", 2)])
def test_epoch_end_times_need_no_callback(blobs16, mode, workers):
    x, y = blobs16
    model = SparkModel(_mlp(), mode=mode, frequency="epoch", num_workers=workers)
    t0 = time.monotonic()
    model.fit(to_simple_rdd(None, x, y, workers), epochs=3, batch_size=16)
    stamps = model.last_epoch_end_times
    assert len(stamps) == 3
    assert t0 < stamps[0] <= stamps[1] <= stamps[2] < time.monotonic()


def test_hogwild_without_callbacks_pulls_no_snapshot(blobs16, monkeypatch):
    from elephas_tpu.parameter.server import LocalServer

    x, y = blobs16
    pulls = []
    real = LocalServer.get_parameters
    monkeypatch.setattr(LocalServer, "get_parameters",
                        lambda self: pulls.append(1) or real(self))
    model = SparkModel(_mlp(), mode="hogwild", frequency="epoch", num_workers=2)
    model.fit(to_simple_rdd(None, x, y, 2), epochs=2, batch_size=16)
    bare = len(pulls)
    del pulls[:]
    model = SparkModel(_mlp(), mode="hogwild", frequency="epoch", num_workers=2)
    model.fit(to_simple_rdd(None, x, y, 2), epochs=2, batch_size=16,
              callbacks=[lambda epoch, state, metrics: None])
    assert len(model.last_epoch_end_times) == 2
    assert len(pulls) >= bare + 2  # one snapshot an epoch, only when asked for


def test_device_norms_equal_tree_norm_of_the_fetched_trees():
    rng = np.random.default_rng(0)
    prev = {"dense": {"kernel": rng.normal(size=(64, 33)).astype(np.float32),
                      "bias": rng.normal(size=(33,)).astype(np.float32)},
            "scale": (rng.normal(size=(7,)) * 1e3).astype(np.float32)}
    new = jax.tree_util.tree_map(
        lambda a: a + rng.normal(size=a.shape).astype(np.float32) * 1e-2, prev)
    delta_norm, param_norm = jax.device_get(_dynamics_norms(
        jax.tree_util.tree_map(jnp.asarray, prev), jax.tree_util.tree_map(jnp.asarray, new)))
    delta = jax.tree_util.tree_map(lambda a, b: a - b, prev, new)
    assert float(delta_norm) == pytest.approx(obs.tree_norm(delta), rel=1e-5)
    assert float(param_norm) == pytest.approx(obs.tree_norm(prev), rel=1e-5)


def test_sync_fit_gauges_keep_their_names_and_values(blobs16):
    x, y = blobs16
    model = SparkModel(_mlp(seed=5), mode="synchronous", frequency="epoch", num_workers=2)
    before = jax.device_get(model.master_network.params)
    model.fit(to_simple_rdd(None, x, y, 2), epochs=1, batch_size=16)
    after = jax.device_get(model.master_network.params)
    snap = obs.default_registry().snapshot()
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), before, after)
    assert snap['train_delta_norm{worker="driver"}'] == pytest.approx(
        obs.tree_norm(delta), rel=1e-5)
    assert snap['train_effective_step{worker="driver"}'] == pytest.approx(
        obs.tree_norm(delta) / obs.tree_norm(before), rel=1e-5)
    assert 'train_unit_loss{worker="driver"}' in snap
