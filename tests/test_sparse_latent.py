"""The latent family's second generation (`models/latent_moe.py` with window
layers, an indexer, a head gate, rescaled latents and a sigmoid router)
through the serving engine: an indexer's key cache and an exact top-k
selection inside paged latent attention, window layers of bounded residency
beside full ones in one pool. The configuration's `rehearsal` sizes (6
layers of the published pattern, hidden 64, window 5, `index_topk` 8, 8 of 16
experts held), seeded weights from the benchmark's family file, compared
with the benchmark's plain reference (`benchmark/references/dots3.py`:
float32, `highest` precision, the selection by a full sort, nothing of the
program) or with plain numpy."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_moe import _bench_module

from elephas_tpu import InferenceEngine, compile_model
from elephas_tpu.models import decode_cache
from elephas_tpu.models.decode_cache import KV, WINDOW, leaf_name, leaves_of_kind
from elephas_tpu.ops import attention, routed_experts, sparse_index
from elephas_tpu.ops.attention_pallas import (
    pallas_latent_chunk_attention,
    pallas_latent_decode_attention,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SEED = 2147483659
with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as _f:
    CONFIG = json.load(_f)
SMALL = {**{k: v for k, v in CONFIG.items() if k != "rehearsal"},
         **{k: v for k, v in CONFIG["rehearsal"].items() if k != "serving"}}
TOP_K, WINDOW_SIZE = SMALL["index_topk"], SMALL["sliding_window_size"]


@pytest.fixture(scope="module")
def family():
    return _bench_module("models", "dots3")


@pytest.fixture(scope="module")
def reference():
    return _bench_module("references", "dots3")


def _compile(module, params):
    return compile_model(module, params=params, optimizer="sgd",
                         loss="sparse_categorical_crossentropy", metrics=[],
                         input_shape=(44,), input_dtype=jnp.int32)


@pytest.fixture(scope="module")
def served(family):
    """(module, params, compiled) at the rehearsal's sizes, weights from the seed."""
    cfg = family.shape(SMALL)
    params = family.params(SEED, cfg, jnp.float32)
    module = family.flax_module(cfg, "float32")
    return module, params, _compile(module, params)


def _engine(compiled, **kw):
    sizes = dict(max_slots=3, max_prompt_len=44, max_len=64, kv_block_size=8,
                 prefill_chunk=8, queue_depth=16)
    sizes.update(kw)
    return InferenceEngine(compiled, **sizes)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SMALL["vocab_size"], n).tolist() for n in lengths]


def _reference_logits(reference, family, tokens, rows, collect=None):
    cfg = family.shape(SMALL)
    return reference.logits_at(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
        family.top_at(SEED, cfg, jnp.float32),
        lambda layer: family.block_at(SEED, layer, cfg, jnp.float32),
        family.layers(cfg), collect=collect)


def _served_gaps(reference, family, prompts, results):
    """For every served token, how far its reference logit lies below the
    reference's best at its position."""
    gaps = []
    for prompt, res in zip(prompts, results):
        seq = prompt + res.tokens[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        # padded to one length, so that the reference compiles once: a later
        # token changes nothing before it
        padded = seq + [0] * (64 - len(seq))
        logits = np.asarray(_reference_logits(reference, family, [padded], [rows]))[0]
        gaps += list(logits.max(-1) - logits[np.arange(len(rows)), res.tokens])
    return np.asarray(gaps)


def _serve(eng, prompts, new=8):
    ids = [eng.submit(p, max_new_tokens=new, stop_token=None) for p in prompts]
    return [eng.result(i, timeout_s=300) for i in ids]


# prompts longer than index_topk (8) and than the window (5) by several chunks
LONG = [37, 23, 44]


@pytest.fixture(scope="module")
def run(served):
    """One engine at chunks and blocks of 8, traced and with a sink, that has
    served ``LONG``: (engine, prompts, results, sink rows, span events, what
    `host_sync.fetch` was handed)."""
    from elephas_tpu.obs import Tracer
    from elephas_tpu.serving import host_sync

    rows, fetches = [], []

    class Sink:
        def log(self, step, **fields):
            rows.append(fields)

    fetch = host_sync.fetch
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(host_sync, "fetch", lambda v: fetches.append(v) or fetch(v))
        tracer = Tracer(annotate_device=False)
        eng = _engine(served[2], sink=Sink(), tracer=tracer)
        prompts = _prompts(LONG)
        results = _serve(eng, prompts)
    return eng, prompts, results, rows, tracer.events(), fetches


# -- (a) the module against the reference -------------------------------------


def test_full_forward_matches_the_plain_reference(served, family, reference):
    module, params, _ = served
    tokens = np.asarray(_prompts([40, 40], seed=3))
    got = module.apply({"params": params}, jnp.asarray(tokens))
    rows = np.tile(np.arange(40), (2, 1))
    collect = {}
    want = _reference_logits(reference, family, tokens, rows, collect=collect)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    # the reference stands alone
    code = open(os.path.join(BENCH, "references", "dots3.py")).read().split('"""', 2)[2]
    assert "elephas_tpu" not in code and "pallas" not in code
    assert 'default_matmul_precision("highest")' in code
    # and hands out, on request, what each full layer selected and each
    # routed layer chose
    full = [i for i, t in enumerate(SMALL["layer_types"][:6]) if t == "full_attention"]
    assert sorted(collect["selected"]) == full and sorted(collect["routed"]) == [1, 2, 3, 4, 5]
    for mask in collect["selected"].values():
        assert mask.shape == (2, 40, 40) and not np.triu(mask[0], 1).any()
        np.testing.assert_array_equal(mask.sum(-1)[0], np.minimum(np.arange(40) + 1, TOP_K))
    assert collect["routed"][1].shape == (2, 40, SMALL["num_experts_per_tok"])


@pytest.mark.parametrize("chunk,block", [(5, 4), (8, 8), (16, 8)])
def test_chunked_prefill_and_decode_match_the_reference(served, family, reference,
                                                        run, chunk, block):
    """Through the scheduler and the pool, chunk by chunk and then token by
    token: every served token is the reference's best at its position, in
    float32 (a gap of 0; logits, not tokens: a second-best token would read
    its distance from the best)."""
    if (chunk, block) == (8, 8):
        eng, prompts, results = run[:3]
    else:
        eng = _engine(served[2], prefill_chunk=chunk, kv_block_size=block)
        prompts = _prompts(LONG)
        results = _serve(eng, prompts)
    assert all(r.status == "completed" and len(r.tokens) == 8 for r in results)
    assert _served_gaps(reference, family, prompts, results).max() <= 1e-5
    stats = eng.stats()
    assert stats["prefill_traces"] == stats["decode_traces"] == 1
    assert stats["prefix_cache"] == "off: window layers keep a ring a slot"


# -- (b) the selection --------------------------------------------------------


def _numpy_selection(scores, last, k):
    """Of each row's columns ``<= last``, the ``k`` with the largest score by
    a stable full sort of the negated scores: the lower column wins a tie."""
    mask = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        live = np.arange(int(last[r]) + 1)
        order = live[np.argsort(-row[live], kind="stable")]
        mask[r, order[:k]] = True
    return mask


@pytest.mark.parametrize("body", attention.PAGED_BODIES)
def test_the_selection_is_exact_ties_included(body):
    rng = np.random.default_rng(5)
    rows, S, k = 40, 256, 24
    scores = rng.normal(size=(rows, S)).astype(np.float32)
    scores[3, :] = 0.0                      # every live column ties
    scores[4, 10:90] = scores[4, 5]         # a run of ties across the k-th
    scores[5] = np.round(scores[5], 1)      # many small ties
    scores[6, 50:60] = -0.0                 # signed zeros tie with zeros
    scores[6, 60:70] = 0.0
    scores[7] = -np.abs(scores[7])          # all negative
    scores[8, ::2] = np.inf
    last = rng.integers(0, S, rows).astype(np.int32)
    last[:3] = [0, k - 1, k]                # fewer live than k, exactly k, k + 1
    last[3:9] = S - 1
    got = sparse_index.select_columns(jnp.asarray(scores), jnp.asarray(last), k,
                                      "paged_xla")
    if body == "paged_pallas":
        got = jax.block_until_ready(sparse_index.pallas_select_columns(
            jnp.asarray(scores), jnp.asarray(last), k, interpret=True))
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got) > 0, _numpy_selection(scores, last, k))


def test_the_references_selection_is_the_same_rule(reference):
    """The reference selects by a stable full sort; a planted tie goes to the
    lower column there too."""
    scores = np.asarray([[0.5, 0.5, 0.1, 0.5, 0.9, 0.5]], np.float32)
    order = np.asarray(jnp.argsort(-jnp.asarray(scores), axis=-1, stable=True))
    assert order[0].tolist() == [4, 0, 1, 3, 5, 2]
    np.testing.assert_array_equal(_numpy_selection(scores, [5], 3)[0],
                                  [True, True, False, False, True, False])


# -- (c) planted faults are seen ----------------------------------------------


@pytest.mark.parametrize("fault", ["the gate left out", "the rescale left out",
                                   "the bias used in the weights"])
def test_a_fault_in_the_module_is_seen(served, family, reference, monkeypatch, fault):
    module, params, _ = served
    if fault == "the gate left out":
        module = module.clone(head_gate=False)
    elif fault == "the rescale left out":
        module = module.clone(latent_rescale=False)
    else:
        chosen = routed_experts.group_limited_top_k
        monkeypatch.setattr(
            routed_experts, "group_limited_top_k",
            lambda p, n_group, topk_group, top_k, by=None: chosen(
                p if by is None else by, n_group, topk_group, top_k))
    tokens = np.asarray(_prompts([40], seed=3))
    got = module.apply({"params": params}, jnp.asarray(tokens))
    want = _reference_logits(reference, family, tokens, [np.arange(40)])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("fault", ["the window off by one",
                                   "the selection taken over the wrong columns"])
def test_a_fault_on_the_serving_path_is_seen(served, family, reference, monkeypatch,
                                             fault):
    module, params, _ = served
    if fault == "the window off by one":
        for name in ("paged_chunk_attention", "paged_decode_attention"):
            body = getattr(attention, name)

            def wider(*args, body=body, window=None, **kw):
                return body(*args, window=None if window is None else window + 1, **kw)

            monkeypatch.setattr(attention, name, wider)
    else:  # the index keys read through the slot's table backwards
        scores = sparse_index.index_scores
        monkeypatch.setattr(
            sparse_index, "index_scores",
            lambda q, w, pool, table, *a, **kw: scores(q, w, pool, table[..., ::-1],
                                                       *a, **kw))
    eng = _engine(_compile(module, params))  # traced under the fault
    prompts = _prompts(LONG)
    gaps = _served_gaps(reference, family, prompts, _serve(eng, prompts))
    assert gaps.max() > 1e-3


# -- (d) each body against dense masked attention -----------------------------


def _latent_case(heads=8, rank=128, pe=64, nope=32, v_head=32, bs=128, bps=8, slots=2):
    width, nb = rank + pe, slots * bps
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    pool = jax.random.normal(keys[0], attention.latent_leaf_shape(nb, bs, width))
    table = jnp.asarray(np.random.default_rng(0).permutation(nb).reshape(slots, bps),
                        jnp.int32)
    kv_b = jax.random.normal(keys[1], (rank, heads, nope + v_head)) * 0.1
    return dict(pool=pool, table=table, kv_b=kv_b, keys=keys, heads=heads, rank=rank,
                pe=pe, nope=nope, v_head=v_head, bs=bs, width=width, slots=slots,
                columns=bps * bs)


def _dense_masked(c, q, latents, seen, scale):
    """Keys and values a head expanded from ``latents`` (L, width); ``q``:
    (heads, Q, nope + pe); ``seen``: (Q, L) what each query attends."""
    k_nope = jnp.einsum("lr,rhf->hlf", latents[:, :c["rank"]], c["kv_b"][..., :c["nope"]])
    v = jnp.einsum("lr,rhf->hlf", latents[:, :c["rank"]], c["kv_b"][..., c["nope"]:])
    s = (jnp.einsum("hqf,hlf->hql", q[..., :c["nope"]], k_nope)
         + jnp.einsum("hqf,lf->hql", q[..., c["nope"]:], latents[:, c["rank"]:])) * scale
    s = jnp.where(seen[None], s, -1e30)
    return jnp.einsum("hql,hlf->hqf", jax.nn.softmax(s, -1), v)


def _row(pool, table_row):
    return jnp.swapaxes(pool[table_row, 0], -1, -2).reshape(-1, pool.shape[2])


def _bound(kind, last, columns, k=150, window=200, seed=11):
    """A selection (random scores, exact top-k) or a window over ``columns``
    columns for queries whose last live columns are ``last``: what the
    bodies are told, and the dense (Q, columns) mask of the same set."""
    cols = np.arange(columns)[None, :]
    live = cols <= np.asarray(last)[:, None]
    if kind == "window":
        return dict(window=window), live & (cols > np.asarray(last)[:, None] - window)
    scores = np.random.default_rng(seed).normal(size=(len(last), columns)).astype(np.float32)
    mask = _numpy_selection(scores, last, k)
    return dict(selected=jnp.asarray(mask, jnp.int8)), mask


# ``start`` / ``valid`` that cross a block, the window's edge (200) and the
# selection's width (150): a chunk of four tiles of 128 queries
_CHUNK_CASES = [(0, 512), (100, 129), (300, 512), (505, 400)]


@pytest.mark.parametrize("body", attention.PAGED_BODIES)
@pytest.mark.parametrize("kind", ["selection", "window"])
@pytest.mark.parametrize("start,valid", _CHUNK_CASES)
def test_bounded_chunk_attention_matches_dense(body, kind, start, valid):
    c = _latent_case()
    chunk = 512
    q = jax.random.normal(c["keys"][2], (c["heads"], chunk, c["nope"] + c["pe"]))
    new = jax.random.normal(c["keys"][3], (1, chunk, c["width"]))
    last = start + np.arange(chunk)
    told, seen = _bound(kind, last, c["columns"])
    out, pool, _ = attention.paged_chunk_attention(
        q, new, None, c["pool"], None, c["table"][1], jnp.int32(start), "paged_xla",
        False, scale=0.11, kv_b=c["kv_b"], valid=jnp.int32(valid), **told)
    if body == "paged_pallas":
        out = jax.block_until_ready(pallas_latent_chunk_attention(
            q, c["kv_b"], pool, c["table"][1], jnp.int32(start), 0.11,
            valid=jnp.int32(valid), tq=128, interpret=True, **told))
    want = _dense_masked(c, q, _row(pool, c["table"][1]), jnp.asarray(seen), 0.11)
    np.testing.assert_allclose(np.asarray(out[:, :valid]), np.asarray(want[:, :valid]),
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("body", attention.PAGED_BODIES)
@pytest.mark.parametrize("kind", ["selection", "window"])
def test_bounded_decode_attention_matches_dense(body, kind):
    c = _latent_case(slots=4, bps=4)
    idx = jnp.asarray([0, 149, 383, 40], jnp.int32)  # under k, at k - 1, past a block
    active = jnp.asarray([True, True, True, False])
    q = jax.random.normal(c["keys"][2], (c["slots"], c["heads"], c["nope"] + c["pe"]))
    new = jax.random.normal(c["keys"][3], (c["slots"], 1, c["width"]))
    told, seen = _bound(kind, np.asarray(idx), c["columns"])
    if body == "paged_xla":
        out, pool, _ = attention.paged_decode_attention(
            q, new, None, c["pool"], None, c["table"], idx, active, body, scale=0.11,
            kv_b=c["kv_b"], **told)
    else:
        absorbed = attention._absorb(q[:, :, None], c["kv_b"], c["width"])[:, :, 0]
        out, pool = pallas_latent_decode_attention(
            absorbed, new, c["pool"], c["table"], idx, active, c["rank"], 0.11, blocks=2,
            interpret=True, **told)
        out = attention._expand_values(jax.block_until_ready(out)[:, :, None], c["kv_b"],
                                       c["nope"])[:, :, 0]
    for s in range(3):
        want = _dense_masked(c, q[s][:, None], _row(pool, c["table"][s]),
                             jnp.asarray(seen[s:s + 1]), 0.11)[:, 0]
        np.testing.assert_allclose(np.asarray(out[s]), np.asarray(want), atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_the_index_kernels_match_the_plain_scores(step):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over the slot's
    index keys through its table row, at every column a query can see."""
    heads, width, bs, bps, slots = 4, 128, 128, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    pool = jax.random.normal(keys[0], attention.latent_leaf_shape(slots * bps, bs, width))
    table = jnp.asarray(np.random.default_rng(1).permutation(slots * bps).reshape(
        slots, bps), jnp.int32)
    if step == "chunk":
        chunk, start, valid = 256, 517, 200
        q = jax.random.normal(keys[1], (heads, chunk, width))
        w = jax.random.normal(keys[2], (chunk, heads))
        want = sparse_index.index_scores(q, w, pool, table[1], jnp.int32(start),
                                         "paged_xla")
        got = jax.block_until_ready(sparse_index.pallas_index_scores(
            q, w, pool, table[1], jnp.int32(start), jnp.int32(valid), tq=128,
            interpret=True))
        keys_of = np.asarray(_row(pool, table[1]))
        plain = (np.maximum(np.einsum("hqd,sd->qhs", np.asarray(q), keys_of), 0.0)
                 * np.asarray(w)[:, :, None]).sum(1)
        seen = np.arange(bps * bs)[None, :] <= (start + np.arange(chunk))[:, None]
        seen[valid:] = False
    else:
        idx = jnp.asarray([0, 600, 1023], jnp.int32)
        active = jnp.asarray([True, True, False])
        q = jax.random.normal(keys[1], (slots, heads, width))
        w = jax.random.normal(keys[2], (slots, heads))
        want = sparse_index.index_scores(q, w, pool, table, idx, "paged_xla",
                                         active=active)
        got = jax.block_until_ready(sparse_index.pallas_index_decode_scores(
            q, w, pool, table, idx, active, interpret=True))
        plain = np.stack([
            (np.maximum(np.asarray(q[s]) @ np.asarray(_row(pool, table[s])).T, 0.0)
             * np.asarray(w[s])[:, None]).sum(0) for s in range(slots)])
        seen = (np.arange(bps * bs)[None, :] <= np.asarray(idx)[:, None]) \
            & np.asarray(active)[:, None]
    assert got.shape[0] == want.shape[0] == seen.shape[0]
    assert want.shape[1] == sparse_index.padded_columns(bps, bs) <= got.shape[1]
    for scores in (got, want):
        np.testing.assert_allclose(np.where(seen, np.asarray(scores)[:, :bps * bs], 0.0),
                                   np.where(seen, plain, 0.0), atol=2e-4, rtol=2e-4)


# -- (e) the pool: three widths of leaf, a ring bounded by the window ---------


def test_the_pool_holds_three_widths_and_bounds_the_window_layers(served, family,
                                                                  reference):
    _, _, compiled = served
    chunk, block = 5, 4
    eng = _engine(compiled, prefill_chunk=chunk, kv_block_size=block, max_slots=2)
    cache = eng.pool.cache
    paged = {leaf_name(p): l for p, l in leaves_of_kind(cache, KV)}
    rings = [l for _, l in leaves_of_kind(cache, WINDOW)]
    kinds = SMALL["layer_types"][:SMALL["num_hidden_layers"]]
    assert len(rings) == kinds.count("sliding_attention") == 3
    assert len(leaves_of_kind(cache, KV)) == 2 * kinds.count("full_attention")
    latent = SMALL["kv_lora_rank"] + SMALL["qk_rope_head_dim"]
    assert paged["cached_latent"].shape[1:] == (1, latent, block)
    assert paged["cached_index_key"].shape[1:] == (1, SMALL["index_head_dim"], block)
    # a slot's ring: window - 1 + chunk columns in blocks, one more since a
    # chunk of 5 need not start on a block of 4
    bound = (-(-(WINDOW_SIZE - 1 + chunk) // block) + 1) * block
    assert bound == decode_cache.ring_blocks(WINDOW_SIZE, chunk, block) * block == 16
    width = SMALL["swa_kv_lora_rank"] + SMALL["swa_qk_rope_head_dim"]
    assert all(r.shape == (2, bound // block, 1, width, block) for r in rings)
    assert eng.stats()["kv_bytes_per_token"] == 3 * (latent + SMALL["index_head_dim"]) * 4

    seen = []

    class Sink:
        def log(self, step, **fields):
            if fields.get("event") == "step":
                seen.append((fields["window_columns_resident"],
                             fields["window_columns_bound"], fields["lane_lengths"]))

    eng.metrics.sink = Sink()
    prompts = _prompts([44, 31])  # nine chunks: the ring wraps three times
    alone = _serve(eng, prompts[:1])
    assert _served_gaps(reference, family, prompts[:1], alone).max() <= 1e-5
    # what the slots hold of a window layer never passes the bound, however
    # long the sequence
    assert seen and max(r for r, _, _ in seen) == bound < 44
    assert all(r <= b == 2 * bound for r, b, _ in seen)
    assert eng.stats()["window_columns_per_slot"] == bound
    # a reused slot gives what it gives alone
    again = _serve(eng, [prompts[1], prompts[0], prompts[1]])
    assert again[1].tokens == alone[0].tokens and again[0].tokens == again[2].tokens
    assert eng.stats()["window_columns_resident"] == 0  # every ring released


def test_what_such_a_pool_cannot_do_is_refused_by_mechanism(served):
    _, _, compiled = served
    with pytest.raises(NotImplementedError, match="window layer's ring"):
        _engine(compiled, speculative=True)
    from elephas_tpu.parallel.mesh import build_mesh
    from elephas_tpu.serving import shard_serving

    with pytest.raises(NotImplementedError, match="window layers"):
        shard_serving(_engine(compiled), build_mesh(num_data=2, num_model=4))
    eng = _engine(compiled)
    assert eng.pool.prefix is None and eng.pool.windowed and not eng.pool.stateful
    slot = eng.pool.acquire()
    # a resident prefix says nothing of a window layer's ring: nothing is adopted
    assert eng.pool.admit_prefix(slot, _prompts([20])[0]) == 0
    for what, call in (("fork_slot", lambda: eng.pool.fork_slot(slot)),
                       ("export_blocks", lambda: eng.pool.export_blocks(slot)),
                       ("import_blocks", lambda: eng.pool.import_blocks(slot, [1], []))):
        with pytest.raises(NotImplementedError, match="outside the block table"):
            call()


# -- (f) the share -------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(family, reference):
    """The parts that `experts_held = (2 k, 2)`, k = 0..7, give, the shared
    expert counted once, equal the uncut layer of 16 experts: the program's
    routed layer against the reference's."""
    from elephas_tpu.models.latent_moe import RoutedExperts

    cfg = family.shape(dict(SMALL, n_routed_experts=16))
    whole = family.block_at(SEED, 1, cfg, jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(4), (30, SMALL["hidden_size"]))
    hyper = dict(family.hyper(cfg), first=0)
    ids, weights = reference.route(whole, y, hyper, reference.identity)

    def expert(e, rows):
        w = whole["experts"]
        return reference.gated({"gate": {"kernel": w["gate"][e]}, "up": {"kernel": w["up"][e]},
                                "down": {"kernel": w["down"][e]}}, rows, reference.identity)

    uncut = sum(jnp.where(ids == e, weights, 0.0).sum(-1)[:, None] * expert(e, y)
                for e in range(16))
    parts = 0.0
    for k in range(8):
        layer = RoutedExperts(
            n_routed_experts=16, experts_held=(2 * k, 2),
            moe_d_ff=SMALL["moe_intermediate_size"], top_k=SMALL["num_experts_per_tok"],
            n_group=1, topk_group=1, routed_scaling_factor=1.0, scoring="sigmoid",
            norm_topk_prob=True, selection_bias=True)
        held = {name: whole["experts"][name][2 * k:2 * k + 2] for name in ("gate", "up", "down")}
        parts = parts + layer.apply(
            {"params": {**held, "router": whole["experts"]["router"],
                        "bias": whole["experts"]["bias"]}}, y, mutable=["counters"])[0]
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut), atol=2e-5, rtol=2e-5)
    # the router chooses by s + b and weighs by s: with this bias they differ
    s = jax.nn.sigmoid(y @ whole["experts"]["router"]["kernel"])
    by_score = np.sort(np.asarray(jax.lax.top_k(s, 2)[1]), -1)
    assert (np.sort(np.asarray(ids), -1) != by_score).any()
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)


# -- (g) the first generation lowers as it did --------------------------------


def _program_digests():
    import test_latent_moe as first

    family = first._bench_module("models", "deepseek_v2")
    _, params, compiled = first._compiled(family)
    eng = first._engine(compiled)
    cache, table, rng = eng.pool.cache, eng.pool.device_table(), eng._next_rng()
    i32, lanes = jnp.int32(0), jnp.zeros((3,), jnp.int32)
    chunk = jax.make_jaxpr(eng._chunk_prefill_impl)(
        params, cache, table, jnp.zeros((1, 8), jnp.int32), i32, i32, jnp.int32(8), rng)
    decode = jax.make_jaxpr(eng._paged_decode_impl)(
        params, cache, table, lanes, lanes, jnp.zeros((3,), bool), jnp.ones((3,), bool),
        lanes, rng)
    assert not any(name in str(chunk) + str(decode) for name in (
        "index_scores", "select_columns", "cached_index_key"))
    return [hashlib.sha256(str(j).encode()).hexdigest() for j in (chunk, decode)]


def test_deepseek_v2_lowers_to_the_parents_two_programs(monkeypatch):
    """The family's first configuration is the case of no window layer, no
    indexer, no gate, no rescale and softmax scoring: no new operand reaches
    its two programs. The digests are of `str(jax.make_jaxpr(...))` of the
    small engine's chunk and decode programs on the CPU with this
    installation's JAX. Whose each pair is: the first is PR 39's, with every
    counter; the second PR 37's and PR 38's (PR 39's parent, 2f05d14), which
    holds with the counter PR 39 sows left out (`moe_rows_combined`: on the
    CPU's packed body it is `moe_assignments` again, one more entry of the
    counters' vector), since the CPU's way back is as it was; the third PR
    36's parent's (6f0661e), which holds with PR 37's counter left out too
    (`moe_weight_passes`, a sum over `load`): the rows of the CPU's body lie
    as they lay."""
    from flax import linen as nn

    from elephas_tpu.models import latent_moe
    from elephas_tpu.ops import routed_experts

    assert _program_digests() == [
        "b5e4ed1472b2ef720e723af6fd00d2cb05b9be0b1d108cc6777019c6010a3f25",
        "7b4a171b84596d29ce4b84cf9fbd286d00492f45b416b1ee0be3ee7c310fc820"]
    left_out = {"moe_rows_combined"}
    monkeypatch.setattr(routed_experts, "rows_combined", lambda *args: 0.0)
    monkeypatch.setattr(
        latent_moe.RoutedExperts, "sow", lambda self, col, name, value, **kw:
        name not in left_out and nn.Module.sow(self, col, name, value, **kw))
    assert _program_digests() == [
        "6ea61899e6ff120e304d48839845ef5c75e975f4fddab4c9504763da365da394",
        "f7dbe5af01b873e2c7ed490d2dd93f90bfaf5505dc1d47e261850268343a2b28"]
    left_out.add("moe_weight_passes")
    monkeypatch.setattr(routed_experts, "weight_passes", lambda load, rows: 0.0)
    assert _program_digests() == [
        "2e1ff27e407d11c5d551576d9b70f33bd2b6de6ce5a06ea3d77c6b5381f11607",
        "1632385df362187c13b6c0b86924723fd1e1fc92a77cf158c987877d8aa4d2dc"]


# -- (h) the counters ride the step's one fetch and the chunk's span ----------


def _live_and_selected(columns):
    """Over queries whose last live columns are ``columns``, summed over the
    three full layers: the live columns and the selected ones."""
    live = sum(c + 1 for c in columns)
    return 3 * live, 3 * sum(min(c + 1, TOP_K) for c in columns)


def test_the_sparse_counters_ride_the_lanes_fetch_and_the_chunks_span(run):
    _, _, _, rows, events, fetches = run
    steps = [r for r in rows if r.get("event") == "step" and "sparse_columns_live" in r]
    assert steps
    lanes_before = None
    for s in steps:  # a step's event carries the counters of the decode it harvested
        assert 0 < s["sparse_columns_selected"] <= s["sparse_columns_live"]
        assert s["moe_assignments"] > 0
        if lanes_before:
            assert (s["sparse_columns_live"], s["sparse_columns_selected"]) == \
                _live_and_selected(lanes_before)
        lanes_before = s["lane_lengths"]
    assert all(isinstance(v, tuple) and len(v) == 2 for v in fetches
               if not hasattr(v, "shape"))
    chunks = [e for e in events if e.name == "step/prefill_chunk"]
    assert len(chunks) == sum(-(-n // 8) for n in LONG)
    for e in chunks:  # padding is scored nowhere: a ragged chunk counts its own queries
        start, valid = e.args["start"], e.args["valid"]
        assert (e.args["sparse_columns_live"], e.args["sparse_columns_selected"]) == \
            _live_and_selected(range(start, start + valid))
