"""MiniCPM-SALA's block (`models/minicpm_sala.py`) through the serving engine:
lightning linear-attention layers with a matrix state a slot, carried chunk to
chunk, beside a block-sparse attention that selects whole pages of the pool
by compressed keys. The configuration's `rehearsal` sizes (4 layers `S L L
L`, hidden 64, 4 heads of 16 on 2 K/V heads, blocks of 8, compressed keys of
4 columns every 2, 6 blocks a query, a window of 16, every column before 32),
seeded weights from the benchmark's family file, compared with the
benchmark's plain reference (`benchmark/references/minicpm_sala.py`:
float32, `highest` precision, the lightning layers as their token recurrence,
the selection by a full sort, nothing of the program) or with plain numpy.
The rehearsal takes 6 blocks a query where the issue's sizes said 4: with 4,
the init block and a window of 2-3 blocks fill the set, and no block would
be chosen by its score."""

import hashlib
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_moe import _bench_module

from elephas_tpu import InferenceEngine, compile_model
from elephas_tpu.models import minicpm_sala, transformer
from elephas_tpu.models.decode_cache import STATE, leaves_of_kind
from elephas_tpu.ops import attention, lightning, sparse_index

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SEED = 2147483659
with open(os.path.join(BENCH, "configs", "minicpm-sala.json")) as _f:
    CONFIG = json.load(_f)
SMALL = {**{k: v for k, v in CONFIG.items() if k != "rehearsal"},
         **{k: v for k, v in CONFIG["rehearsal"].items() if k != "serving"}}
LOGITS_TOL = 2e-5  # float32 throughout: the chunk form against the recurrence


@pytest.fixture(scope="module")
def family():
    return _bench_module("models", "minicpm_sala")


@pytest.fixture(scope="module")
def reference():
    return _bench_module("references", "minicpm_sala")


@pytest.fixture(scope="module")
def served(family):
    """(module, params, compiled, cfg) at the rehearsal's sizes, weights from the seed."""
    cfg = family.shape(SMALL)
    params = family.params(SEED, cfg, jnp.float32)
    module = family.flax_module(cfg, "float32")
    compiled = compile_model(module, params=params, optimizer="sgd",
                             loss="sparse_categorical_crossentropy", metrics=[],
                             input_shape=(64,), input_dtype=jnp.int32)
    return module, params, compiled, cfg


def _engine(compiled, **kw):
    sizes = dict(max_slots=1, max_prompt_len=64, max_len=80, kv_block_size=8,
                 prefill_chunk=16, queue_depth=8)
    sizes.update(kw)
    return InferenceEngine(compiled, **sizes)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SMALL["vocab_size"], n).tolist() for n in lengths]


def _reference_logits(reference, family, cfg, tokens, rows, collect=None):
    return np.asarray(reference.logits_at(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
        family.top_at(SEED, cfg, jnp.float32),
        lambda layer: family.block_at(SEED, layer, cfg, jnp.float32),
        family.layers(cfg), collect=collect))


@pytest.fixture
def captured(monkeypatch):
    """The logits every serving program samples from, in the order the
    programs ran: a chunk's last valid row, (1, vocab), and a decode step's
    lanes, (slots, vocab)."""
    rows = []
    sample = transformer.sample_tokens_at

    def recorded(logits, *args, **kw):
        jax.debug.callback(lambda x: rows.append(np.asarray(x)), logits, ordered=True)
        return sample(logits, *args, **kw)

    monkeypatch.setattr(transformer, "sample_tokens_at", recorded)
    return rows


def _served_logits(compiled, captured, prompt, new, **kw):
    """(tokens, logits (new, vocab)) of one request served alone on one slot:
    its last chunk's row, then each decode step's."""
    eng = _engine(compiled, **kw)
    captured.clear()
    res = eng.result(eng.submit(prompt, max_new_tokens=new, stop_token=None), timeout_s=300)
    chunks = -(-len(prompt) // eng.prefill_chunk)
    steps = [r[0] for r in captured[chunks:]]
    return res.tokens, np.stack([captured[chunks - 1][0]] + steps[:new - 1])


def _gap_to_reference(reference, family, cfg, prompt, tokens, logits):
    seq = prompt + tokens[:-1]
    rows = np.arange(len(prompt) - 1, len(seq))
    want = _reference_logits(reference, family, cfg, [seq + [0] * (80 - len(seq))], [rows])[0]
    return np.abs(logits - want).max()


# -- (a) the module and the serving path against the reference ------------------


def test_full_forward_matches_the_plain_reference(served, family, reference):
    module, params, _, cfg = served
    tokens = np.asarray(_prompts([96, 96], seed=3))
    got = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    rows = np.tile(np.arange(96), (2, 1))
    want = _reference_logits(reference, family, cfg, tokens, rows)
    assert np.abs(got - want).max() < LOGITS_TOL
    assert want.std() > 0.5  # logits that spread: the head is drawn at its scale


@pytest.mark.parametrize("chunk", [16, 12, 5])
def test_chunked_prefill_and_decode_match_the_reference(served, family, reference, captured,
                                                        chunk):
    """Several chunks past `dense_len` (32), a chunk that is no whole number of
    blocks (12) and one that splits compressed keys (5): the served logits
    are the reference's full forward's."""
    _, _, compiled, cfg = served
    for prompt in _prompts([61, 45]):
        tokens, logits = _served_logits(compiled, captured, prompt, 8, prefill_chunk=chunk)
        assert _gap_to_reference(reference, family, cfg, prompt, tokens, logits) < LOGITS_TOL


def test_a_reused_slot_gives_what_it_gives_alone(served, captured):
    """The lightning states are cleared at release and the compressed keys of
    the blocks a slot is handed again are never read before they are
    written: the second request on the one slot is served as it is alone."""
    _, _, compiled, _ = served
    first, second = _prompts([58, 47], seed=5)
    eng = _engine(compiled)
    eng.result(eng.submit(first, max_new_tokens=6, stop_token=None), timeout_s=300)
    for _, leaf in leaves_of_kind(eng.pool.cache, STATE):
        assert not np.asarray(leaf).any()  # released: every state row zero
    after = eng.result(eng.submit(second, max_new_tokens=6, stop_token=None), timeout_s=300)
    alone = _engine(compiled)
    assert after.tokens == alone.result(alone.submit(second, max_new_tokens=6,
                                                     stop_token=None), timeout_s=300).tokens
    assert eng.stats()["state_resets"] == 4  # an admission and a release a request


# -- (b) the lightning forms against the recurrence -----------------------------


def _recurrence(q, k, v, decay, s0):
    """o_t = q_t S_t, S_t = decay S_{t-1} + k_t^T v_t, in numpy float64."""
    S, out = np.asarray(s0, np.float64), []
    for t in range(q.shape[1]):
        S = decay[:, None, None] * S + k[:, t, :, None] * v[:, t, None, :]
        out.append(np.einsum("hd,hde->he", q[:, t], S))
    return np.stack(out, 1), S


@pytest.mark.parametrize("body", ["lightning_xla", "lightning_pallas"])
def test_the_chunk_form_carries_the_state_and_keeps_padding_out(body):
    rng = np.random.default_rng(0)
    H, T, D = 2, 64, 128
    q, k, v = (rng.normal(size=(H, 2 * T, D)).astype(np.float32) * 0.2 for _ in range(3))
    decay = np.exp(-rng.uniform(0.002, 0.9, H)).astype(np.float32)
    s0 = rng.normal(size=(H, D, D)).astype(np.float32) * 0.1

    def chunk(qc, kc, vc, s, valid):
        if body == "lightning_pallas":
            out = lightning.pallas_lightning_chunk(qc, kc, vc, jnp.log(decay), s,
                                                   jnp.int32(valid), interpret=True)
            return jax.block_until_ready(out)
        return lightning.lightning_chunk(qc, kc, vc, jnp.log(decay), s, valid)

    # the second chunk ragged: 37 real tokens, the rest padding that holds anything
    o1, s1 = chunk(q[:, :T], k[:, :T], v[:, :T], s0, T)
    o2, s2 = chunk(q[:, T:], k[:, T:], v[:, T:], s1, 37)
    want, state = _recurrence(q[:, :T + 37], k[:, :T + 37], v[:, :T + 37], decay, s0)
    got = np.concatenate([np.asarray(o1), np.asarray(o2)[:, :37]], 1)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(np.asarray(s2) - state).max() < 1e-4 * np.abs(state).max()


@pytest.mark.parametrize("active", [[True, False, True, False], [False, True, True, True]])
def test_the_step_form_steps_the_active_lanes_alone(active):
    rng = np.random.default_rng(1)
    S, H, D = 4, 8, 128
    q, k, v = (rng.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    decay = np.exp(-rng.uniform(0.002, 0.9, H)).astype(np.float32)
    state = rng.normal(size=(S, H, D, D)).astype(np.float32)
    active = np.array(active)
    o, new = lightning.lightning_step(q, k, v, decay, jnp.asarray(state),
                                      jnp.asarray(active))
    o, new = np.asarray(o), np.asarray(new)
    for s in range(S):
        if not active[s]:
            assert (new[s] == state[s]).all()
            continue
        want_o, want_s = _recurrence(q[s][:, None], k[s][:, None], v[s][:, None], decay,
                                     state[s])
        assert np.abs(new[s] - want_s).max() < 1e-4
        assert np.abs(o[s] - want_o[:, 0]).max() < 1e-3


# -- (c) compressed keys and the selection ---------------------------------------


def test_the_pool_holds_the_means_of_their_columns(served):
    """After a prompt, every compressed key of the slot that exists is the mean
    of its kernel's columns of the pool's keys, those that straddle two
    blocks among them, at chunk widths that split keys; none past them."""
    _, _, compiled, _ = served
    sel = sparse_index.BlockSelection(*compiled.module.selection)
    for chunk in (16, 5):
        eng = _engine(compiled, prefill_chunk=chunk)
        prompt = _prompts([53])[0]
        slot = eng.pool.acquire()
        eng.pool.ensure_cols(slot, len(prompt))
        for start in range(0, len(prompt), chunk):
            part = prompt[start:start + chunk]
            eng._chunk_prefill(jnp.asarray([part + [0] * (chunk - len(part))], jnp.int32),
                               jnp.int32(slot), jnp.int32(start), jnp.int32(len(part)))
        layer = eng.pool.cache["Layer_0"]["attention"]
        row = eng.pool.table.rows[slot]
        keys = attention.paged_to_contiguous(layer["cached_key"], jnp.asarray(row)[None],
                                             16)[0]  # (heads, columns, D)
        comp = np.asarray(sparse_index.row_keys(layer["cached_compressed_key"],
                                                jnp.asarray(row)[None])[0])
        n = (len(prompt) - sel.kernel) // sel.stride + 1
        for i in range(n):
            want = np.asarray(keys[:, i * sel.stride:i * sel.stride + sel.kernel]).mean(1)
            assert np.abs(comp[:, i] - want).max() < 1e-6, (chunk, i)
        assert not comp[:, n:-(-len(prompt) // sel.block) * sel.keys_per_block].any()


def _numpy_selection(scores, t, sel):
    """Sel(t) by a stable sort, in numpy: the forced blocks, then the highest
    scores, the lower block first on a tie."""
    nb = len(scores)
    blocks = np.arange(nb)
    live = blocks <= t // sel.block
    if t < sel.dense_len:
        return set(blocks[live])
    forced = live & ((blocks < sel.init_blocks) |
                     ((blocks + 1) * sel.block - 1 >= t - sel.window + 1))
    rest = [b for b in sorted(blocks[live & ~forced], key=lambda b: (-scores[b], b))]
    return set(blocks[forced]) | set(rest[:sel.top_k - forced.sum()])


@pytest.mark.parametrize("body", ["paged_xla", "paged_pallas"])
def test_the_selection_is_exact_ties_included(body, monkeypatch):
    if body == "paged_pallas":
        kernel = sparse_index.pallas_select_columns
        monkeypatch.setattr(sparse_index, "pallas_select_columns",
                            lambda s, last, k: jax.block_until_ready(
                                kernel(s, last, k, interpret=True)))
    sel = sparse_index.BlockSelection(*CONFIG["rehearsal"]["sparse_config"].values())
    rng = np.random.default_rng(7)
    nb = 40
    last = rng.integers(0, nb * sel.block, 64)
    scores = rng.integers(0, 5, (64, nb)).astype(np.float32) / 4  # ties everywhere
    # a planted tie: six blocks that are not forced score alike and highest,
    # and two places are left beside the init block and the window's three
    scores[0] = 0.0
    scores[0, 3:9] = 1.0
    last[0] = 30 * sel.block + 2
    mask = np.asarray(sparse_index.select_blocks(jnp.asarray(scores), jnp.asarray(last), sel,
                                                 body))
    for r in range(64):
        assert set(np.flatnonzero(mask[r])) == _numpy_selection(scores[r], last[r], sel), r
    assert set(np.flatnonzero(mask[0])) == {0, 3, 4, 28, 29, 30}


def test_the_selection_is_the_references(served, family, reference):
    """The sparse layer's blocks, from the program's compressed keys, scores and
    selection, are the reference's (its full sort), every query and K/V head."""
    module, params, _, cfg = served
    tokens = np.asarray(_prompts([96], seed=9))
    collect = {}
    _reference_logits(reference, family, cfg, tokens, [[95]], collect=collect)
    w = params["Layer_0"]
    x = params["tok_embed"]["embedding"][tokens[0]] * cfg["scale_emb"]
    eps = cfg["rms_norm_eps"]

    def rms(y, scale):
        return y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps) * scale

    with jax.default_matmul_precision("highest"):
        y = rms(x, w["mixer_norm"]["scale"])
        a = w["attention"]
        q = rms(jnp.einsum("td,dhe->the", y, a["q"]["kernel"]), a["q_norm"]["scale"])
        k = rms(jnp.einsum("td,dhe->the", y, a["k"]["kernel"]), a["k_norm"]["scale"])
        sel = sparse_index.BlockSelection(*module.selection)
        T, heads = len(tokens[0]), k.shape[1]
        n_keys = -(-T // sel.block) * sel.keys_per_block
        keys = sparse_index._mean_keys(jnp.moveaxis(k, 1, 0)[None],
                                       (jnp.arange(n_keys) * sel.stride)[None], sel.kernel)[0]
        last = jnp.arange(T)
        scores = sparse_index.block_scores(q.reshape(T, heads, -1, q.shape[-1]),
                                           jnp.moveaxis(keys, 0, 1), last, sel,
                                           q.shape[-1] ** -0.5)
        mask = sparse_index.select_blocks(scores.reshape(T * heads, -1),
                                          jnp.repeat(last, heads), sel, "paged_xla")
    got = np.asarray(mask).reshape(T, heads, -1) > 0
    want = np.asarray(collect["selected"][0][0])
    assert (got == want).all()
    # past dense_len: top_k blocks, or every live one where there are fewer
    blocks = np.minimum(sel.top_k, np.arange(T) // sel.block + 1)
    assert (got.sum(-1)[sel.dense_len:] == blocks[sel.dense_len:, None]).all()


# -- (d) a planted fault is seen --------------------------------------------------


def _with(monkeypatch, module, name, wrap):
    monkeypatch.setattr(module, name, wrap(getattr(module, name)))


def _sel_changed(**change):
    """Call the wrapped function with its selection's sizes changed."""
    def wrap(f):
        def changed(*args, **kw):
            return f(*[a._replace(**change) if isinstance(a, sparse_index.BlockSelection)
                       else a for a in args], **kw)
        return changed
    return wrap


def _rotary_in_sparse(f):
    def wrapped(q, k, v, sel, scale):
        at = jnp.arange(q.shape[2])[None]

        def rot(x):
            return jnp.swapaxes(minicpm_sala.rope(jnp.swapaxes(x, 1, 2).astype(jnp.float32),
                                                  at, 10000.0), 1, 2).astype(x.dtype)
        return f(rot(q), rot(k), v, sel, scale)
    return wrapped


FULL_FORWARD_FAULTS = {
    # a compressed key scored one stride before its last column is there
    "incomplete_key_scored": lambda mp: _with(mp, sparse_index, "block_scores",
                                              _sel_changed(kernel=2)),
    "window_one_block_short": lambda mp: _with(mp, sparse_index, "select_blocks",
                                               _sel_changed(window=8)),
    "init_block_dropped": lambda mp: _with(mp, sparse_index, "select_blocks",
                                           _sel_changed(init_blocks=0)),
    "rotary_in_the_sparse_layer": lambda mp: _with(mp, minicpm_sala, "_full_block_sparse",
                                                   _rotary_in_sparse),
    "a_gate_left_out": lambda mp: mp.setattr(nn, "sigmoid", lambda x: jnp.ones_like(x)),
    "output_norm_over_the_whole_width": lambda mp: mp.setattr(
        minicpm_sala, "head_norm", lambda o, eps: o * jax.lax.rsqrt(
            (o * o).mean((-2, -1), keepdims=True) + eps)),
}


@pytest.mark.parametrize("fault", sorted(FULL_FORWARD_FAULTS))
def test_a_fault_in_the_module_is_seen(served, family, reference, monkeypatch, fault):
    module, params, _, cfg = served
    tokens = np.asarray(_prompts([128], seed=13))
    want = _reference_logits(reference, family, cfg, tokens, [np.arange(128)])
    FULL_FORWARD_FAULTS[fault](monkeypatch)
    got = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    assert np.abs(got - want).max() > 1e-3


SERVING_FAULTS = {
    # lambda^i where the recurrence has lambda^(i + 1): the chunk form's powers
    # one step short, inside a chunk and across
    "decay_off_by_one_power": lambda mp: mp.setattr(
        lightning, "_steps", lambda pos, valid: jnp.minimum(pos, valid).astype(jnp.float32)),
    "state_not_carried": lambda mp: _with(
        mp, lightning, "lightning_chunk",
        lambda f: lambda q, k, v, d, s0, *a, **kw: f(q, k, v, d, jnp.zeros_like(s0), *a, **kw)),
}


@pytest.fixture
def fresh_traces():
    """Jitted functions keep their traces: a fault planted inside one is seen
    only by a trace made after it, and the faulty trace must not outlive it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(SERVING_FAULTS))
def test_a_fault_on_the_serving_path_is_seen(served, family, reference, captured,
                                             monkeypatch, fresh_traces, fault):
    _, _, compiled, cfg = served
    SERVING_FAULTS[fault](monkeypatch)
    prompt = _prompts([61])[0]
    tokens, logits = _served_logits(compiled, captured, prompt, 8)
    assert _gap_to_reference(reference, family, cfg, prompt, tokens, logits) > 1e-3


# -- (e) the counters, the pool, the refusals --------------------------------------


@pytest.fixture(scope="module")
def run(served):
    """One engine on two slots, traced and with a sink, that has served three
    prompts past `dense_len`: (engine, prompts, sink rows, span events)."""
    from elephas_tpu.obs import Tracer

    rows = []

    class Sink:
        def log(self, step, **fields):
            rows.append(fields)

    tracer = Tracer(annotate_device=False)
    eng = _engine(served[2], max_slots=2, sink=Sink(), tracer=tracer)
    prompts = _prompts([61, 37, 50])
    ids = [eng.submit(p, max_new_tokens=8, stop_token=None) for p in prompts]
    for i in ids:
        eng.result(i, timeout_s=300)
    return eng, prompts, rows, tracer.events()


def _counts(columns, sel, heads=2):
    """(live, scored) over queries whose columns are `columns`, every K/V head."""
    live = heads * sum(c + 1 for c in columns)
    scored = heads * sum(c // sel.block + 1 for c in columns if c >= sel.dense_len)
    return live, scored


def test_the_counters_ride_the_lanes_fetch_and_the_chunks_span(run, served):
    eng, prompts, rows, events = run
    sel = sparse_index.BlockSelection(*served[0].selection)
    steps = [r for r in rows if r.get("event") == "step"]
    assert sum("sparse_columns_live" in s for s in steps) > 10
    for before, s in zip(steps, steps[1:]):
        # a step's event carries the counters of the decode it harvested: the
        # one the step before launched on its lanes
        if "sparse_columns_live" in s:
            assert 0 < s["sparse_columns_selected"] <= s["sparse_columns_live"]
            assert (s["sparse_columns_live"], s["sparse_blocks_scored"]) == \
                _counts(before["lane_lengths"], sel)
    chunks = [e for e in events if e.name == "step/prefill_chunk"]
    assert len(chunks) == sum(-(-len(p) // 16) for p in prompts)
    for e in chunks:  # padding is scored nowhere: a ragged chunk counts its own queries
        start, valid = e.args["start"], e.args["valid"]
        columns = range(start, start + valid)
        assert (e.args["sparse_columns_live"], e.args["sparse_blocks_scored"]) == \
            _counts(columns, sel)
        dense = [c for c in columns if c < sel.dense_len]
        assert e.args["sparse_columns_selected"] >= 2 * sum(c + 1 for c in dense)
        assert e.args["sparse_pages_gathered"] == 0  # the XLA body walks every chunk


def test_the_pool_holds_matrix_states_and_compressed_keys(run, served, family):
    eng, _, rows, _ = run
    cfg = served[3]
    stats = eng.stats()
    assert eng.stateful and stats["prefix_cache"] == "off: per-slot state"
    # a sparse layer's key and value, and a half of a compressed key, a column
    assert stats["kv_bytes_per_token"] == family.kv_bytes_per_token(cfg, 4) == \
        2 * 16 * 4 * 5 // 2
    # three lightning layers' (4, 16, 16) float32 states a slot, two slots
    assert stats["state_bytes"] == 2 * family.state_bytes_per_slot(cfg) == 2 * 3 * 4 * 256 * 4
    assert all(r["state_bytes"] == stats["state_bytes"] for r in rows if r.get("event") == "step")


def test_what_such_a_pool_cannot_do_is_refused_by_mechanism(served):
    _, _, compiled, _ = served
    with pytest.raises(NotImplementedError, match="per-slot state"):
        _engine(compiled, speculative=True)
    from elephas_tpu.parallel.mesh import build_mesh
    from elephas_tpu.serving import shard_serving

    with pytest.raises(NotImplementedError, match="per-slot state"):
        shard_serving(_engine(compiled), build_mesh(num_data=2, num_model=4))
    eng = _engine(compiled, max_slots=2)
    # a resident prefix says nothing of the states, nor of the compressed keys
    # that go with its blocks: nothing is adopted, and no prefix is kept
    assert eng.pool.prefix is None
    slot = eng.pool.acquire()
    assert eng.pool.admit_prefix(slot, _prompts([40])[0]) == 0
    for what, call in (("fork_slot", lambda: eng.pool.fork_slot(slot)),
                       ("export_blocks", lambda: eng.pool.export_blocks(slot)),
                       ("import_blocks", lambda: eng.pool.import_blocks(slot, [1], []))):
        with pytest.raises(NotImplementedError, match="per-slot state"):
            call()


# -- (f) the kernels against their XLA bodies ---------------------------------------


def test_the_block_scores_kernel_is_the_xla_body():
    sel = sparse_index.BlockSelection(32, 16, 64, 64, 1, 2048, 256)
    rng = np.random.default_rng(0)
    H, g, T, D, nb = 2, 4, 256, 128, 40
    q = jnp.asarray(rng.normal(size=(H, g, T, D)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(H, nb * 4, D)) * 0.3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for start in (0, 1024, 2304):
            got = jax.block_until_ready(sparse_index.pallas_block_scores(
                q, keys, jnp.int32(start), sel, 0.088, tq=64, interpret=True))
            last = start + jnp.arange(T)
            want = jnp.moveaxis(sparse_index.block_scores(jnp.moveaxis(q, 2, 0), keys, last,
                                                          sel, 0.088), 0, 1)
            selecting = np.asarray(last >= sel.dense_len)
            assert np.abs(np.asarray(got[:, :, :nb] - want))[:, selecting].max(
                initial=0.0) < 1e-6
            assert not np.asarray(got)[:, ~selecting].any()  # a dense tile is not scored


def test_the_chunk_attention_kernel_is_the_xla_body():
    rng = np.random.default_rng(0)
    H, g, T, D, B, bps, nbk = 2, 2, 256, 128, 64, 24, 60
    kp = jnp.asarray(rng.normal(size=(nbk, H, B, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nbk, H, B, D)), jnp.float32)
    row = jnp.asarray(rng.permutation(nbk)[:bps], jnp.int32)
    q = jnp.asarray(rng.normal(size=(H, g, T, D)), jnp.float32)
    sel = sparse_index.BlockSelection(32, 16, B, 8, 1, 128, bps * B)  # no query selects
    with jax.default_matmul_precision("highest"):
        for start in (0, 640, 1280):
            last = start + np.arange(T)
            picked = (rng.random((H, bps, T)) < 0.3) & \
                (np.arange(bps)[None, :, None] <= (last // B)[None, None])
            picked[:, 0] = True
            want = attention._chunk_walk_xla(q, kp, vp, row, jnp.int32(start),
                                             jnp.asarray(picked), D, 0.1)
            got, fetched = attention.pallas_block_sparse_chunk_attention(
                jnp.moveaxis(q, 2, 0), kp, vp, row, jnp.int32(start), jnp.asarray(picked),
                0.1, sel, jnp.int32(T), tq=128, interpret=True)
            got = jnp.moveaxis(jax.block_until_ready(got).reshape(T, H, g, D), 0, 2)
            assert np.abs(np.asarray(got - want)).max() < 1e-5
            assert int(fetched) == 0  # before dense_len every tile walks


# A selection at interpret-mode sizes: blocks of 16, 12 a query, a window of
# 64 columns, every column before 256; chunks of 256 in tiles of 128.
_GATHER_SEL = sparse_index.BlockSelection(4, 2, 16, 12, 1, 64, 256)


def _gather_case(start, rng, sel=_GATHER_SEL):
    """(q, pools, row, picked) of one chunk of 256 queries from ``start``: a
    selection by random scores through `select_blocks`, as a layer makes it."""
    H, g, T, D, bps, nbk = 2, 2, 256, 128, 64, 150
    kp = jnp.asarray(rng.normal(size=(nbk, H, sel.block, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nbk, H, sel.block, D)), jnp.float32)
    row = jnp.asarray(rng.permutation(nbk)[:bps], jnp.int32)
    q = jnp.asarray(rng.normal(size=(H, g, T, D)), jnp.float32)
    last = start + np.arange(T)
    mask = sparse_index.select_blocks(jnp.asarray(rng.random((T * H, bps)), jnp.float32),
                                      jnp.repeat(jnp.asarray(last), H), sel, "paged_xla")
    picked = np.array(jnp.moveaxis(mask.reshape(T, H, bps), 0, 2))
    return q, kp, vp, row, picked


def _attend_f32(q, kp, vp, row, start, picked, valid, scale=0.1):
    """Each query's heads over the columns of its picked blocks up to its own,
    in float64 numpy: the plain reference of the chunk attention."""
    q, kp, vp, row = (np.asarray(x, np.float64) for x in (q, kp, vp, row))
    H, g, T, D = q.shape
    bs = kp.shape[2]
    keys = kp[row.astype(int)].transpose(1, 0, 2, 3).reshape(H, -1, D)  # (H, columns, D)
    vals = vp[row.astype(int)].transpose(1, 0, 2, 3).reshape(H, -1, D)
    out = np.zeros((H, g, valid, D))
    for h in range(H):
        for t in range(valid):
            cols = np.arange(keys.shape[1])
            ok = picked[h, cols // bs, t].astype(bool) & (cols <= start + t)
            s = q[h, :, t] @ keys[h, ok].T * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[h, :, t] = (p / p.sum(-1, keepdims=True)) @ vals[h, ok]
    return out


def _band_of(start, t, sel=_GATHER_SEL, tq=128):
    """(init blocks, first block of the band) of query ``t``'s tile."""
    first = start + (t // tq) * tq
    if first < sel.dense_len:
        return 0, 0
    lo = max(first - sel.window + 1, 0) // sel.block
    hi = (first + tq - 1) // sel.block
    lo = min(lo - lo % 8, hi - hi % 8)
    return min(sel.init_blocks, lo), lo


@pytest.mark.parametrize("start,valid,dense_len", [
    (0, 256, 256), (128, 256, 256), (640, 256, 256), (768, 200, 256), (128, 256, 80)],
    ids=["before-dense_len", "straddles", "far-past", "ragged", "window-past-init"])
def test_the_gathered_chunk_attention_is_the_walk_and_the_reference(start, valid, dense_len):
    """Past `dense_len` a tile walks its band and each query fetches the
    pages it chose outside it: the same attention as the XLA walk and the
    plain reference, and the pages fetched are each query's chosen blocks
    outside its tile's band, none at or past `valid`, and no more than it
    chose by score. The last case puts `dense_len` a window past the init
    block, the nearest the forced blocks can lie and stay apart."""
    sel = _GATHER_SEL._replace(dense_len=dense_len)
    rng = np.random.default_rng(start)
    q, kp, vp, row, picked = _gather_case(start, rng, sel)
    H, g, T, D = q.shape
    with jax.default_matmul_precision("highest"):
        want = attention._chunk_walk_xla(q, kp, vp, row, jnp.int32(start),
                                         jnp.asarray(picked), D, 0.1)
        got, fetched = attention.pallas_block_sparse_chunk_attention(
            jnp.moveaxis(q, 2, 0), kp, vp, row, jnp.int32(start), jnp.asarray(picked), 0.1,
            sel, jnp.int32(valid), tq=128, interpret=True)
    got = np.asarray(jnp.moveaxis(jax.block_until_ready(got).reshape(T, H, g, D), 0, 2))
    assert np.abs(got - np.asarray(want))[:, :, :valid].max() < 1e-5
    assert np.abs(got[:, :, :valid] - _attend_f32(q, kp, vp, row, start, picked, valid)).max() < 1e-5
    outside = sum(int(picked[h, init:lo, t].sum()) for h in range(H) for t in range(valid)
                  for init, lo in [_band_of(start, t, sel)])
    assert int(fetched) == outside
    assert (int(fetched) > 0) == (start + T - 128 >= sel.dense_len)
    _, _, lists, _ = attention.block_sparse_chunk_plan(jnp.asarray(picked), jnp.int32(start),
                                                       valid, sel, 128, sel.block)
    assert np.asarray(lists).reshape(H, T, -1)[..., 0].max() <= sparse_index.scored_blocks(sel)


def test_a_selection_keeps_its_window_apart_from_its_init_blocks():
    """A query past `dense_len` chooses by score at most `top_k` less its
    forced blocks, which holds only where its window cannot reach the init
    blocks: a selection where it can is refused."""
    assert sparse_index.scored_blocks(_GATHER_SEL) == 12 - 1 - 4
    assert sparse_index.scored_blocks(sparse_index.BlockSelection(
        32, 16, 64, 64, 1, 2048, 8192)) == 31  # minicpm-sala's published selection
    with pytest.raises(ValueError, match="dense_len"):
        sparse_index.scored_blocks(_GATHER_SEL._replace(dense_len=79))


def test_queries_of_a_tile_fetch_their_own_pages():
    """Two queries of one tile that chose disjoint pages outside the band,
    and one whose scored pages all lie inside it (it fetches none): each
    attends its own choice."""
    sel, start = _GATHER_SEL, 640
    rng = np.random.default_rng(3)
    q, kp, vp, row, picked = _gather_case(start, rng)
    H, g, T, D = q.shape
    init, lo = _band_of(start, 200)
    width = sparse_index.scored_blocks(sel)
    for t, scored in ((200, range(init, init + width)), (201, range(lo - width, lo)),
                      (202, range(lo, lo + width))):
        own = (start + t) // sel.block
        forced = set(range(sel.init_blocks)) | {
            b for b in range(own + 1) if (b + 1) * sel.block - 1 >= start + t - sel.window + 1}
        picked[:, :, t] = 0
        for b in forced | set(scored):
            picked[:, b, t] = 1
    assert not set(range(init, init + width)) & set(range(lo - width, lo))
    with jax.default_matmul_precision("highest"):
        got, fetched = attention.pallas_block_sparse_chunk_attention(
            jnp.moveaxis(q, 2, 0), kp, vp, row, jnp.int32(start), jnp.asarray(picked), 0.1,
            sel, jnp.int32(T), tq=128, interpret=True)
    got = np.asarray(jnp.moveaxis(jax.block_until_ready(got).reshape(T, H, g, D), 0, 2))
    want = _attend_f32(q, kp, vp, row, start, picked, T)
    assert np.abs(got[:, :, 200:203] - want[:, :, 200:203]).max() < 1e-5
    assert np.abs(got - want).max() < 1e-5
    _, _, lists, _ = attention.block_sparse_chunk_plan(jnp.asarray(picked), jnp.int32(start),
                                                       T, sel, 128, sel.block)
    records = np.asarray(lists).reshape(H, T, -1)  # a query's count, then its blocks
    assert list(records[0, 200, :width + 1]) == [width, *range(init, init + width)]
    assert list(records[0, 201, :width + 1]) == [width, *range(lo - width, lo)]
    assert records[0, 202, 0] == 0


# -- (g) the other hybrids lower as they did ------------------------------------------


def _program_digests(compiled, params, **sizes):
    eng = InferenceEngine(compiled, max_slots=3, prefill_chunk=8, **sizes)
    cache, table, rng = eng.pool.cache, eng.pool.device_table(), eng._next_rng()
    i32, lanes = jnp.int32(0), jnp.zeros((3,), jnp.int32)
    chunk = jax.make_jaxpr(eng._chunk_prefill_impl)(
        params, cache, table, jnp.zeros((1, 8), jnp.int32), i32, i32, jnp.int32(8), rng)
    decode = jax.make_jaxpr(eng._paged_decode_impl)(
        params, cache, table, lanes, lanes, jnp.zeros((3,), bool), jnp.ones((3,), bool),
        lanes, rng)
    return [hashlib.sha256(str(j).encode()).hexdigest() for j in (chunk, decode)]


def test_jamba_and_dots3_lower_to_the_parents_two_programs():
    """No operand of the new leaves or layers reaches the programs of the two
    configurations whose code this family shares (the state rows and GQA
    paged K/V with `jamba2-3b`, the selection path and `ops/sparse_index.py`
    with `dots3-note-prev`). The digests are of `str(jax.make_jaxpr(...))` of
    small engines' chunk and decode programs on the CPU, taken on the parent
    commit (934f671) with this installation's JAX."""
    from elephas_tpu.models import get_model

    m = get_model("jamba_lm", vocab_size=97, d_model=32, num_layers=4, num_heads=4,
                  num_kv_heads=1, d_ff=64, d_state=4, dt_rank=8)
    p = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    c = compile_model(m, params=p, optimizer="sgd", loss="sparse_categorical_crossentropy",
                      metrics=[], input_shape=(16,), input_dtype=jnp.int32)
    assert _program_digests(c, p, max_prompt_len=16, max_len=32, kv_block_size=4) == [
        "078923d928ed721c89649f70f0a559bb7efa7c6b9636ac0a6285e1021c2a1215",
        "c11937278b1e9149a1c9b5631c5e0f4332bad5789387b75104d0ee8e050a25c9"]
    dots3 = _bench_module("models", "dots3")
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        config = json.load(f)
    small = {**{k: v for k, v in config.items() if k != "rehearsal"},
             **{k: v for k, v in config["rehearsal"].items() if k != "serving"}}
    cfg = dots3.shape(small)
    p = dots3.params(SEED, cfg, jnp.float32)
    c = compile_model(dots3.flax_module(cfg, "float32"), params=p, optimizer="sgd",
                      loss="sparse_categorical_crossentropy", metrics=[], input_shape=(44,),
                      input_dtype=jnp.int32)
    assert _program_digests(c, p, max_prompt_len=44, max_len=64, kv_block_size=8) == [
        "fb841f0881adf908860b0ce1eb9bf3f1ffc1dfb88893b6ee820f812c7a0858da",
        "c31f4e96dc26de0d73b05b35fc018a52ceabeb60c8bd37c74d4992665d16e4e6"]


def test_the_count_of_parameters_is_the_cut_and_the_published_model(family):
    cfg = family.shape(CONFIG)
    assert family.param_count(cfg) == 1_711_129_600
    published = {**CONFIG["published"], "mixer_types": CONFIG["mixer_types"]}
    assert family.param_count(cfg, published) == 9_477_206_016
    small = family.shape(SMALL)
    drawn = family.params(SEED, small, jnp.float32)
    assert family.param_count(small) == sum(x.size for x in jax.tree_util.tree_leaves(drawn))
