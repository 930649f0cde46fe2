"""Speculative decoding (``elephas_tpu.serving.spec``).

The contract under test: with ``speculative=True`` the engine serves
every request through ONE draft program + ONE verify program, emits
between 1 and gamma + 1 tokens per lane-step — and the emitted streams
are BYTE-IDENTICAL to plain decode, greedy and temperature-matched
alike, across EOS stops, deadline evictions mid-speculation, draft-pull
failures (fallback to plain), and paged-pool churn.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import obs
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.models import get_model
from elephas_tpu.serving import (
    DraftModelSource,
    InferenceEngine,
    SelfDraftSource,
)

VOCAB, SEQ = 97, 64

PROMPTS = [
    ([5, 3, 9], 10),
    ([7, 2, 8, 4, 1, 6], 12),
    ([11, 12], 8),
    ([1, 2, 3, 4], 10),
    ([42, 7, 7, 13, 2], 9),
    ([3], 11),
]


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


def _engine(compiled, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_len", 24)
    kw.setdefault("queue_depth", 8)
    return InferenceEngine(compiled, **kw)


def _serve(engine, prompts=PROMPTS, **submit_kw):
    rids = [engine.submit(p, max_new_tokens=n, **submit_kw)
            for p, n in prompts]
    return [engine.result(r, timeout_s=120) for r in rids]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakePSClient:
    """Stands in for ``ShardedParameterClient``: hands out a param tree
    and counts pulls (the wire client's version gating — NotModified on
    unchanged ``X-Elephas-Version`` — sits below this interface)."""

    def __init__(self, params):
        self.params = params
        self.pulls = 0
        self.fail_next = 0

    def get_parameters(self):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise ConnectionError("draft pull failed (injected)")
        self.pulls += 1
        return self.params


# -- token identity --------------------------------------------------------


@pytest.mark.parametrize("pipeline", [True, False])
def test_greedy_identity_self_draft(compiled, pipeline):
    plain = [r.tokens for r in _serve(_engine(compiled, pipeline=pipeline))]
    eng = _engine(compiled, pipeline=pipeline, speculative=True, gamma=3,
                  draft_layers=1)
    spec = [r.tokens for r in _serve(eng)]
    assert spec == plain


def test_temperature_identity_self_draft(compiled):
    """Sampled decode stays byte-identical: position-keyed sampling
    draws the same random number for the same stream position no matter
    which program samples it."""
    kw = dict(temperature=0.7, top_k=5, seed=3)
    plain = [r.tokens for r in _serve(_engine(compiled, **kw))]
    spec = [r.tokens for r in _serve(_engine(
        compiled, speculative=True, gamma=3, draft_layers=1, **kw))]
    assert spec == plain


def test_greedy_identity_chunked_prefill(compiled):
    """Speculation composes with chunked prefill — both share the
    position-keyed sampler, so splitting prompts into chunks changes
    nothing."""
    plain = [r.tokens for r in _serve(_engine(compiled))]
    spec = [r.tokens for r in _serve(_engine(
        compiled, speculative=True, gamma=2, draft_layers=1,
        prefill_chunk=3, prefill_chunks_per_step=1))]
    assert spec == plain


def test_gamma_sweep_identity(compiled):
    plain = [r.tokens for r in _serve(_engine(compiled))]
    for gamma in (1, 2, 5):
        spec = [r.tokens for r in _serve(_engine(
            compiled, speculative=True, gamma=gamma, draft_layers=1))]
        assert spec == plain, f"gamma={gamma} diverged"


# -- EOS / budget ----------------------------------------------------------


def test_eos_freeze_mid_window(compiled):
    """A stop token landing anywhere inside a speculative window ends
    the stream exactly where plain decode would — later window tokens
    are discarded, never emitted."""
    plain = _serve(_engine(compiled))
    # Pick a token that actually occurs mid-stream so the stop triggers.
    stop = plain[1].tokens[4]
    kw = dict(stop_token=stop)
    base = [r.tokens for r in _serve(_engine(compiled, **kw))]
    spec = [r.tokens for r in _serve(_engine(
        compiled, speculative=True, gamma=4, draft_layers=1, **kw))]
    assert spec == base
    for toks in spec:
        assert stop not in toks[:-1]  # frozen at first occurrence


# -- accept-all / reject-all edge cases ------------------------------------


def test_accept_all_same_model_draft(compiled):
    """The target itself as draft model: every draft token matches, so
    every window emits gamma + 1 tokens and the accept rate is exactly
    1.0 — and the output is still byte-identical."""
    plain = [r.tokens for r in _serve(_engine(compiled))]
    client = FakePSClient(compiled.params)
    eng = _engine(
        compiled, speculative=True, gamma=3, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, client),
    )
    results = _serve(eng)
    assert [r.tokens for r in results] == plain
    st = eng.stats()
    assert st["spec_accept_rate"] == 1.0
    assert st["spec_tokens_per_step"] > 1.3
    assert any(r.tokens_per_step and r.tokens_per_step > 1.3
               for r in results)


def test_reject_all_zero_params_draft(compiled):
    """A draft that constantly proposes token 0 (zeroed params → flat
    logits → argmax 0): acceptance collapses to ~0, throughput
    degrades to plain decode — and output stays byte-identical."""
    plain = [r.tokens for r in _serve(_engine(compiled))]
    zeroed = jax.tree_util.tree_map(jnp.zeros_like, compiled.params)
    client = FakePSClient(zeroed)
    eng = _engine(
        compiled, speculative=True, gamma=3, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, client),
    )
    assert [r.tokens for r in _serve(eng)] == plain
    st = eng.stats()
    # Token 0 may coincide with a real target token occasionally; the
    # rate must sit at (or negligibly above) the reject-all floor.
    assert st["spec_accept_rate"] <= 0.1
    assert st["spec_tokens_per_step"] >= 1.0


# -- compile-program story -------------------------------------------------


def test_compile_counters_pinned(compiled):
    """Mixed traffic (ragged prompts, admissions mid-decode, EOS)
    compiles exactly one draft and one verify program — and the plain
    decode program never runs."""
    eng = _engine(compiled, speculative=True, gamma=3, draft_layers=1)
    _serve(eng)
    _serve(eng)  # second wave: warm programs, zero new traces
    st = eng.stats()
    assert st["draft_traces"] == 1
    assert st["verify_traces"] == 1
    assert st["prefill_traces"] == 1
    assert st["decode_traces"] == 0
    assert st["spec_fallbacks"] == 0
    assert st["spec_windows"] > 0


def test_compile_counters_model_source(compiled):
    client = FakePSClient(compiled.params)
    eng = _engine(
        compiled, speculative=True, gamma=2, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, client),
    )
    _serve(eng)
    _serve(eng)
    st = eng.stats()
    assert st["draft_traces"] == 1
    assert st["verify_traces"] == 1
    assert st["draft_prefill_traces"] == 1


# -- paged rollback / refcount conservation --------------------------------


def test_refcount_conservation_under_churn(compiled):
    """Seeded churn (ragged prompts, shared prefixes, EOS, slot reuse)
    over a speculative engine: every harvest rolls rejected suffixes
    back device-side, and the block ledger must still conserve —
    every block free or accounted for by exactly its refcount."""
    rng = np.random.default_rng(7)
    eng = _engine(compiled, speculative=True, gamma=3, draft_layers=1,
                  queue_depth=32)
    prompts = []
    for _ in range(16):
        plen = int(rng.integers(1, 8))
        if prompts and rng.random() < 0.4:
            base = prompts[int(rng.integers(0, len(prompts)))][0]
            p = (base + [int(t) for t in
                         rng.integers(1, VOCAB, plen)])[:7]
        else:
            p = [int(t) for t in rng.integers(1, VOCAB, plen)]
        prompts.append((p, int(rng.integers(2, 14))))
    results = _serve(eng, prompts=prompts)
    assert all(r.status == "completed" for r in results)
    eng.pool.assert_block_invariants()
    assert eng.pool.active_count == 0


def test_deadline_eviction_mid_speculation(compiled):
    """A deadline expiring while a speculative window is in flight
    evicts the lane cleanly: partial tokens returned, its blocks
    released (ledger conserves), survivors decode on unperturbed."""
    clock = FakeClock()
    eng = _engine(compiled, speculative=True, gamma=3, draft_layers=1,
                  clock=clock)
    doomed = eng.submit([7, 2, 8, 4, 1, 6], max_new_tokens=12,
                        timeout_s=5.0)
    survivor = eng.submit([5, 3, 9], max_new_tokens=10)
    for _ in range(3):  # a couple of windows land before the deadline
        eng.step()
        clock.advance(1.0)
    clock.advance(10.0)  # now past the doomed request's deadline
    res_d = eng.result(doomed, timeout_s=120)
    res_s = eng.result(survivor, timeout_s=120)
    assert res_d.status == "timeout"
    assert res_s.status == "completed"
    # The survivor's stream is the same one a quiet engine produces.
    quiet = _engine(compiled, speculative=True, gamma=3, draft_layers=1)
    rid = quiet.submit([5, 3, 9], max_new_tokens=10)
    assert res_s.tokens == quiet.result(rid, timeout_s=120).tokens
    eng.pool.assert_block_invariants()
    # The evicted lane's partial tokens are a prefix of its full stream.
    full = _engine(compiled, speculative=True, gamma=3, draft_layers=1)
    rid = full.submit([7, 2, 8, 4, 1, 6], max_new_tokens=12)
    assert res_d.tokens == full.result(rid, timeout_s=120).tokens[
        :len(res_d.tokens)]


# -- draft-weights delivery / fallback -------------------------------------


def test_version_gated_draft_refresh(compiled):
    """``refresh_every`` bounds pulls: a large window pulls once for the
    whole run; refresh_every=1 re-asks the (version-gating) client at
    every draft call."""
    lazy = FakePSClient(compiled.params)
    eng = _engine(
        compiled, speculative=True, gamma=2, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, lazy,
                                      refresh_every=10_000),
    )
    _serve(eng)
    assert lazy.pulls == 1

    eager = FakePSClient(compiled.params)
    eng2 = _engine(
        compiled, speculative=True, gamma=2, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, eager,
                                      refresh_every=1),
    )
    _serve(eng2)
    assert eager.pulls > 1
    assert eng.stats()["spec_accept_rate"] == 1.0


def test_spec_fallback_on_pull_failure(compiled):
    """Draft pulls failing mid-serve degrade those windows to plain
    decode (spec_fallback flight kind) — never an error, and the
    emitted streams stay byte-identical."""
    plain = [r.tokens for r in _serve(_engine(compiled))]
    client = FakePSClient(compiled.params)
    eng = _engine(
        compiled, speculative=True, gamma=2, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, client,
                                      refresh_every=1),
    )
    client.fail_next = 3  # the first pulls fail (incl. draft prefill)
    assert [r.tokens for r in _serve(eng)] == plain
    st = eng.stats()
    assert st["spec_fallbacks"] >= 1
    assert st["decode_traces"] <= 1  # at most ONE plain program compiled
    kinds = [e.kind for e in
             obs.default_flight_recorder().events(kind="spec_fallback")]
    assert "spec_fallback" in kinds


# -- metrics / plumbing ----------------------------------------------------


def test_tokens_per_step_plain_is_one(compiled):
    results = _serve(_engine(compiled))
    for r in results:
        if len(r.tokens) > 1:
            assert r.tokens_per_step == pytest.approx(1.0)


def test_spec_load_signals(compiled):
    client = FakePSClient(compiled.params)
    eng = _engine(
        compiled, speculative=True, gamma=3, prefix_cache=False,
        draft_source=DraftModelSource(compiled.module, client),
    )
    _serve(eng)
    signals = eng.load.snapshot()["signals"]
    assert signals["spec_accept_rate"] == 1.0
    assert signals["spec_tokens_per_step"] > 1.3
    plain_eng = _engine(compiled)
    _serve(plain_eng)
    assert "spec_accept_rate" not in plain_eng.load.snapshot()["signals"]


def test_spec_validates(compiled):
    with pytest.raises(ValueError, match="mutually exclusive"):
        _engine(compiled, speculative=True, draft_layers=1,
                draft_source=SelfDraftSource(1))
    with pytest.raises(ValueError, match="speculative"):
        _engine(compiled, draft_layers=1)
    with pytest.raises(ValueError, match="draft_layers"):
        _engine(compiled, speculative=True, draft_layers=2)  # == num_layers
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(compiled, speculative=True,
                draft_source=DraftModelSource(
                    compiled.module, FakePSClient(compiled.params)))
    with pytest.raises(ValueError, match="gamma"):
        _engine(compiled, speculative=True, gamma=0, draft_layers=1)
