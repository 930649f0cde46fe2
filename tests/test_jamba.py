"""The Mamba / attention hybrid (`models/jamba.py`) through the serving
engine: recurrent and convolution state beside paged K/V, carried from
chunk to chunk. Small sizes, seeded weights from the benchmark's family
file, compared with the benchmark's plain reference
(`benchmark/references/jamba.py`: float32, `highest` precision, a
sequential scan over tokens, nothing of the program) or with the
sequential recurrence."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import InferenceEngine, compile_model
from elephas_tpu.models import get_model
from elephas_tpu.models.decode_cache import STATE, leaf_kind, leaves_of_kind
from elephas_tpu.ops.selective_scan import (
    _scan_xla,
    pallas_selective_scan,
    selective_scan,
    selective_scan_step,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SEED = 2147483659
PUBLISHED = dict(
    num_hidden_layers=28, hidden_size=2560, intermediate_size=8192,
    num_attention_heads=20, num_key_value_heads=1, vocab_size=65536, mamba_d_state=16,
    mamba_d_conv=4, mamba_dt_rank=160, mamba_expand=2, attn_layer_period=14,
    attn_layer_offset=7, rms_norm_eps=1e-6, max_position_embeddings=262144)
SMALL = dict(PUBLISHED, num_hidden_layers=4, hidden_size=32, intermediate_size=64,
             num_attention_heads=4, mamba_d_state=4, mamba_dt_rank=8,
             attn_layer_period=4, attn_layer_offset=1, vocab_size=211)


def _bench_module(kind, name):
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def family():
    return _bench_module("models", "jamba")


@pytest.fixture(scope="module")
def reference():
    return _bench_module("references", "jamba")


@pytest.fixture(scope="module")
def served(family):
    """(module, params, compiled) at the small size, weights from the seed."""
    cfg = family.shape(SMALL)
    params = family.params(SEED, cfg, jnp.float32)
    module = family.flax_module(cfg, "float32")
    compiled = compile_model(module, params=params, optimizer="sgd",
                             loss="sparse_categorical_crossentropy", metrics=[],
                             input_shape=(30,), input_dtype=jnp.int32)
    return module, params, compiled


def _engine(compiled, **kw):
    sizes = dict(max_slots=3, max_prompt_len=30, max_len=48, kv_block_size=8,
                 prefill_chunk=8, queue_depth=16)
    sizes.update(kw)
    return InferenceEngine(compiled, **sizes)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SMALL["vocab_size"], n).tolist() for n in lengths]


def _reference_logits(reference, family, tokens, rows):
    cfg = family.shape(SMALL)
    return reference.logits_at(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
        family.top_at(SEED, cfg, jnp.float32),
        lambda layer: family.block_at(SEED, layer, cfg, jnp.float32),
        family.layers(cfg))


def _served_gaps(reference, family, prompts, results):
    """For every served token, how far its reference logit lies below the
    reference's best at its position."""
    gaps = []
    for prompt, res in zip(prompts, results):
        seq = prompt + res.tokens[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        logits = np.asarray(_reference_logits(reference, family, [seq], [rows]))[0]
        gaps += list(logits.max(-1) - logits[np.arange(len(rows)), res.tokens])
    return np.asarray(gaps)


def _serve(eng, prompts, new=8):
    ids = [eng.submit(p, max_new_tokens=new, stop_token=None) for p in prompts]
    return [eng.result(i, timeout_s=300) for i in ids]


# -- (a) the module's full forward ------------------------------------------


def test_full_forward_matches_the_plain_reference(served, family, reference):
    module, params, _ = served
    tokens = np.asarray(_prompts([21, 21], seed=3))
    got = module.apply({"params": params}, jnp.asarray(tokens))
    rows = np.tile(np.arange(21), (2, 1))
    want = _reference_logits(reference, family, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    # the reference stands alone
    source = open(os.path.join(BENCH, "references", "jamba.py")).read()
    assert "elephas_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source and "lax.scan" in source


# -- (b) through the engine: chunks, a ragged last one, then decode ----------


def test_chunked_prefill_and_decode_match_the_reference(served, family, reference):
    _, _, compiled = served
    eng = _engine(compiled)
    assert eng.stateful and eng.pool.prefix is None
    prompts = _prompts([5, 13, 19, 29, 9, 17])  # none a multiple of the chunk of 8
    results = _serve(eng, prompts)
    assert all(r.status == "completed" and len(r.tokens) == 8 for r in results)
    assert _served_gaps(reference, family, prompts, results).max() < 1e-5
    stats = eng.stats()
    assert stats["prefill_traces"] == stats["decode_traces"] == 1
    # a row is zeroed as its request is admitted (by the chunk program, at
    # column 0) and again as it is released
    assert stats["state_resets"] == 2 * len(prompts)
    assert stats["state_slots_total"] == 3 and stats["state_slots_in_use"] == 0
    for _, leaf in leaves_of_kind(eng.pool.cache, STATE):
        assert not np.asarray(leaf).any()  # every slot released: nothing left


@pytest.mark.parametrize("chunk", [1, 5, 8, 16])
def test_every_chunk_width_gives_the_one_shot_prefills_tokens(served, chunk):
    """The hybrid's chunk program over the pool in place (its attention
    layers through ``paged_chunk_attention``, its state carried beside):
    whatever the chunk's width, the tokens are those of a prompt prefilled
    in one chunk, from one trace."""
    _, _, compiled = served
    prompts = _prompts([5, 13, 29, 17])
    whole = [r.tokens for r in _serve(_engine(compiled, prefill_chunk=30), prompts)]
    eng = _engine(compiled, prefill_chunk=chunk)
    assert eng.stats()["prefill_attention"] == "paged_xla"  # the CPU's body
    assert [r.tokens for r in _serve(eng, prompts)] == whole
    stats = eng.stats()
    assert stats["prefill_traces"] == stats["decode_traces"] == 1


def test_the_hybrids_chunk_program_gathers_no_row(served):
    _, _, compiled = served
    eng = _engine(compiled)
    program = str(jax.make_jaxpr(eng._chunk_prefill_impl)(
        eng.params, eng.pool.cache, eng.pool.device_table(),
        jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(0), jnp.int32(8),
        eng._next_rng()))
    layers = sum(eng.decode_module.layer_kind(i) == "attention"
                 for i in range(eng.decode_module.num_layers))
    assert program.count("name=paged_chunk_attention") == layers > 0
    assert "paged_to_contiguous" not in program


def test_dropping_the_carried_state_between_chunks_is_seen(served, family, reference):
    """The fault the comparison has to see: a chunk program that starts
    every chunk from a zero state."""
    _, _, compiled = served
    eng = _engine(compiled)
    whole = eng._jit_prefill

    def amnesiac(params, cache, *rest):
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf) if leaf_kind(path) == STATE else leaf,
            cache)
        return whole(params, cache, *rest)

    eng._jit_prefill = amnesiac
    prompts = _prompts([13, 19, 29, 17])
    results = _serve(eng, prompts)
    assert _served_gaps(reference, family, prompts, results).max() > 1e-5


# -- (c) a reused slot, an idle lane ------------------------------------------


def test_a_reused_slot_gives_the_tokens_it_gives_alone(served):
    _, _, compiled = served
    first, second = _prompts([27, 14], seed=5)
    alone = _serve(_engine(compiled, max_slots=1), [second])[0].tokens
    eng = _engine(compiled, max_slots=1)
    after = _serve(eng, [first, second])
    assert eng.pool.admitted_total == 2 and after[1].tokens == alone


def test_an_idle_lanes_state_does_not_move(served):
    """Decode steps move the state of the active lanes alone: the module
    under an `active` mask, and a live engine's free slot."""
    import dataclasses

    module, params, compiled = served
    decode = dataclasses.replace(module, decode=True)
    shapes = jax.eval_shape(lambda: decode.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32)))["cache"]
    rng = np.random.default_rng(0)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros((2,), jnp.int32) if s.ndim == 0
        else jnp.asarray(rng.normal(size=s.shape), s.dtype), shapes)
    _, mutated = decode.apply({"params": params, "cache": cache},
                              jnp.asarray([[3], [4]]), mutable=["cache"],
                              active=jnp.asarray([True, False]))
    before = dict(leaves_of_kind(cache, STATE))
    for path, after in leaves_of_kind(mutated["cache"], STATE):
        np.testing.assert_array_equal(np.asarray(after[1]), np.asarray(before[path][1]))
        assert np.abs(np.asarray(after[0] - before[path][0])).max() > 0

    eng = _engine(compiled, max_slots=2)
    rid = eng.submit(_prompts([11])[0], max_new_tokens=6, stop_token=None)
    for _ in range(4):
        eng.step()
    rows = [np.asarray(leaf) for _, leaf in leaves_of_kind(eng.pool.cache, STATE)]
    busy = int(eng.pool.active_slots()[0])
    assert all(r[busy].any() and not r[1 - busy].any() for r in rows)
    assert eng.result(rid, timeout_s=120).status == "completed"


# -- (d) the scan -------------------------------------------------------------


def _scan_inputs(b=2, T=128, d=1024, n=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(k[0], (b, T, d))
    delta = jax.nn.softplus(jax.random.normal(k[1], (b, T, d)) - 3.0)
    A = -jnp.exp(jax.random.normal(k[2], (n, d)))
    B, C = jax.random.normal(k[3], (b, T, n)), jax.random.normal(k[4], (b, T, n))
    return u, delta, A, B, C, jnp.ones((d,)), jax.random.normal(k[5], (b, n, d))


def _sequential(u, delta, A, B, C, D, h):
    """The recurrence as written, token by token, in numpy float64."""
    u, delta, A, B, C, D, h = (np.asarray(x, np.float64) for x in (u, delta, A, B, C, D, h))
    ys = []
    for t in range(u.shape[1]):
        h = np.exp(delta[:, t, None, :] * A) * h + \
            (delta[:, t] * u[:, t])[:, None, :] * B[:, t, :, None]
        ys.append((C[:, t, :, None] * h).sum(1) + D * u[:, t])
    return np.stack(ys, 1), h


@pytest.mark.parametrize("body", ["scan_xla", "scan_pallas"])
def test_selective_scan_with_a_state_and_a_ragged_chunk(body):
    u, delta, A, B, C, D, h0 = _scan_inputs()
    valid = jnp.asarray([128, 37])
    if body == "scan_pallas":  # the kernel, interpreted on the CPU: a step
        # past `valid` is a step of `delta` 0, as `selective_scan` makes it
        held = jnp.where(jnp.arange(128)[None, :, None] < valid[:, None, None], delta, 0.0)
        y, h = pallas_selective_scan(u, held, A, B, C, D, h0, interpret=True)
    else:
        y, h = selective_scan(u, delta, A, B, C, D, h0, valid, body=body)
    for row, n in enumerate([128, 37]):
        y_want, h_want = _sequential(*(x[row:row + 1, :n] for x in (u, delta)), A,
                                     *(x[row:row + 1, :n] for x in (B, C)), D,
                                     h0[row:row + 1])
        np.testing.assert_allclose(np.asarray(y[row, :n]), y_want[0], atol=2e-4, rtol=2e-4)
        # the state after `valid` tokens, whatever the padding held
        np.testing.assert_allclose(np.asarray(h[row]), h_want[0], atol=2e-4, rtol=2e-4)


def test_the_one_step_form_is_a_chunk_of_one():
    u, delta, A, B, C, D, h0 = _scan_inputs(T=1, d=64)
    y1, h1 = selective_scan_step(u[:, 0], delta[:, 0], A, B[:, 0], C[:, 0], D, h0)
    y, h = _scan_xla(u, delta, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y[:, 0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h), rtol=1e-6, atol=1e-6)


# -- (f) what is refused, and the prefix cache --------------------------------


def test_speculation_fork_and_handoff_refuse_a_model_with_state(served):
    _, _, compiled = served
    with pytest.raises(NotImplementedError, match="rolled back"):
        _engine(compiled, speculative=True, gamma=2)
    eng = _engine(compiled)
    rid = eng.submit(_prompts([9])[0], max_new_tokens=8, stop_token=None)
    eng.step()
    slot = eng.pool.active_slots()[0]
    for call in (lambda: eng.pool.fork_slot(slot), lambda: eng.pool.export_blocks(slot),
                 lambda: eng.pool.import_blocks(slot, [1, 2], [])):
        with pytest.raises(NotImplementedError, match="per-slot state"):
            call()
    with pytest.raises(NotImplementedError, match="per-slot state"):
        eng.shard_serving(None)
    assert eng.result(rid, timeout_s=120).status == "completed"


@pytest.mark.parametrize("model", ["jamba_lm", "transformer_lm"])
def test_the_prefix_cache_adopts_for_kv_alone(model, served):
    """The same turn twice: a model whose cache is K/V alone re-admits its
    prompt's blocks by refcount; one with state adopts nothing, and says so."""
    if model == "jamba_lm":
        compiled = served[2]
    else:
        compiled = compile_model(
            get_model("transformer_lm", vocab_size=211, d_model=32, num_heads=4,
                      num_layers=2, max_seq_len=64),
            optimizer="sgd", loss="sparse_categorical_crossentropy", metrics=[],
            input_shape=(30,), input_dtype=jnp.int32)
    eng = _engine(compiled)
    prompt = _prompts([25])[0]
    first, again = (_serve(eng, [prompt])[0].tokens for _ in range(2))
    assert first == again
    stats = eng.stats()
    if model == "jamba_lm":
        assert stats["prefix_cache"] == "off: per-slot state"
        assert stats["prefix_lookups"] == stats["prefix_hits"] == 0
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
    else:
        assert stats["prefix_cache"] == "on" and stats["prefix_hits"] == 1
        assert stats["prefix_tokens_saved"] == 24 and stats["state_slots_total"] == 0


# -- (g) the count of parameters ----------------------------------------------


def test_param_count_published_and_drawn(family):
    assert family.param_count(family.shape(PUBLISHED)) == 3_029_337_472
    cfg = family.shape(SMALL)
    drawn = family.params(SEED, cfg, jnp.float32)
    assert family.param_count(cfg) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(drawn))
    assert family.state_bytes_per_slot(family.shape(PUBLISHED)) == 9_318_400
    assert family.kv_bytes_per_token(family.shape(PUBLISHED)) == 1024
