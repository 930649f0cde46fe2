"""Paged KV pool: allocator invariants, copy-on-write, prefix caching,
and chunked-prefill token identity.

The block allocator's contract is conservation — a block is free iff
its refcount is 0, and the refcount equals the number of holders (slot
rows + prefix-cache entries) at all times, including after adversarial
seeded churn. The serving contract is identity: the paged layout and
the chunked prefill program must emit EXACTLY the tokens the per-row
``generate()`` oracle emits, over the full matrix (ragged prompts, EOS
stops, deadline evictions, prefix-cache hits, per-step chunk budgets).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import obs
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.models import get_model
from elephas_tpu.obs.flight import FlightRecorder
from elephas_tpu.serving import (
    DonatedBufferError,
    InferenceEngine,
    PagedKVPool,
    PrefixCache,
)
from tests.test_serving import FakeClock, _per_row

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


@pytest.fixture()
def flight():
    previous = obs.default_flight_recorder()
    recorder = FlightRecorder(capacity=256)
    obs.set_default_flight_recorder(recorder)
    try:
        yield recorder
    finally:
        obs.set_default_flight_recorder(previous)


def _pool(compiled, max_slots=3, max_len=24, **kw):
    decode_module = dataclasses.replace(
        compiled.module, decode=True, attention="dense"
    )
    kw.setdefault("block_size", 4)
    return PagedKVPool(decode_module, max_slots, max_len, **kw)


def _paged_engine(compiled, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_len", 24)
    kw.setdefault("queue_depth", 8)
    return InferenceEngine(compiled, **kw)


# -- allocator invariants ----------------------------------------------------


def test_block_acquire_release_refcount_invariants(compiled):
    pool = _pool(compiled)
    assert pool.free_blocks == pool.num_blocks
    slot = pool.acquire()
    pool.ensure_cols(slot, 10)  # 3 blocks at block_size=4
    assert pool.blocks_in_use == 3
    held = [int(b) for b in pool.table.rows[slot] if b >= 0]
    assert len(held) == 3 and all(pool._ref[b] == 1 for b in held)
    pool.assert_block_invariants()
    pool.release(slot)  # no chain: every block must come back
    assert pool.free_blocks == pool.num_blocks
    assert all(pool._ref[b] == 0 for b in held)
    pool.assert_block_invariants()


def test_slot_double_release_raises(compiled):
    pool = _pool(compiled)
    slot = pool.acquire()
    pool.ensure_cols(slot, 4)
    pool.release(slot)
    with pytest.raises(ValueError, match="already free"):
        pool.release(slot)


def test_block_double_release_fails_loudly(compiled):
    pool = _pool(compiled)
    slot = pool.acquire()
    pool.ensure_cols(slot, 4)
    block = int(pool.table.rows[slot, 0])
    pool._decref(block)  # simulate a corrupt row releasing early
    with pytest.raises(RuntimeError, match="double-released"):
        pool._decref(block)


def test_undersized_pool_dead_ends_loudly(compiled):
    """With no prefix cache to evict, exhausting the blocks raises the
    sizing error instead of looping."""
    pool = _pool(compiled, max_slots=2, num_blocks=6, prefix_cache=False)
    a, b = pool.acquire(), pool.acquire()
    pool.ensure_cols(a, pool.virtual_len)  # 6 blocks: takes everything
    with pytest.raises(RuntimeError, match="out of KV blocks"):
        pool.ensure_cols(b, 4)


def test_cow_fork_preserves_content_and_isolates_writes(compiled):
    pool = _pool(compiled)
    parent = pool.acquire()
    pool.ensure_cols(parent, 8)
    pblock = int(pool.table.rows[parent, 0])
    # Stamp recognizable K/V into the parent's first block.
    pool.swap(jax.tree_util.tree_map(
        lambda leaf: leaf.at[pblock].set(7.5) if leaf.ndim == 4 else leaf,
        pool.cache,
    ))
    child = pool.fork_slot(parent)
    assert child is not None
    assert int(pool.table.rows[child, 0]) == pblock  # aliased, not copied
    assert pool._ref[pblock] == 2
    fresh = pool.ensure_writable(child, 0)  # a "write" hits the COW guard
    assert fresh != pblock
    assert int(pool.table.rows[parent, 0]) == pblock
    assert pool._ref[pblock] == 1 and pool._ref[fresh] == 1
    for leaf in jax.tree_util.tree_leaves(pool.cache):
        if leaf.ndim == 4:
            # The fork's block is a faithful copy of the shared content.
            np.testing.assert_array_equal(
                np.asarray(leaf[fresh]), np.asarray(leaf[pblock])
            )
    # Writing the fork's copy must not touch the parent's block.
    pool.swap(jax.tree_util.tree_map(
        lambda leaf: leaf.at[fresh].set(-3.0) if leaf.ndim == 4 else leaf,
        pool.cache,
    ))
    leaf = next(l for l in jax.tree_util.tree_leaves(pool.cache)
                if l.ndim == 4)
    assert float(np.asarray(leaf[pblock]).max()) == 7.5
    pool.assert_block_invariants()


def test_ensure_cols_rejects_past_virtual_length(compiled):
    pool = _pool(compiled)
    slot = pool.acquire()
    with pytest.raises(ValueError, match="columns"):
        pool.ensure_cols(slot, pool.virtual_len + 1)


# -- prefix cache ------------------------------------------------------------


def test_prefix_cache_matches_longest_strictly_shorter_prefix():
    cache = PrefixCache(block_size=4)
    incref = lambda b: None
    cache.insert((1, 2, 3, 4, 5, 6, 7, 8), [10, 11], incref)
    assert len(cache) == 2  # every full-block prefix registered
    matched, blocks = cache.match((1, 2, 3, 4, 5, 6, 7, 8, 9))
    assert matched == 8 and blocks == [10, 11]
    # The exact chain is capped one block short: >= 1 token must prefill.
    matched, blocks = cache.match((1, 2, 3, 4, 5, 6, 7, 8))
    assert matched == 4 and blocks == [10]
    assert cache.match((9, 9, 9, 9, 9))[0] == 0
    assert cache.hits_total == 2 and cache.lookups_total == 3
    assert cache.tokens_saved_total == 12


def test_release_publishes_full_block_chain(compiled):
    pool = _pool(compiled)
    slot = pool.acquire()
    pool.ensure_cols(slot, 10)
    chain = list(range(30, 40))  # 10 tokens -> 2 full blocks resident
    held = [int(b) for b in pool.table.rows[slot][:2]]
    pool.release(slot, tokens=chain)
    assert len(pool.prefix) == 2
    assert all(pool._ref[b] > 0 for b in held)  # pinned by the cache
    matched, blocks = pool.prefix.match(tuple(chain))
    assert matched == 8 and blocks == held
    pool.assert_block_invariants()


def test_lru_eviction_under_pressure_notes_flight(compiled, flight):
    """Allocation pressure evicts the LEAST-recently-used resident
    prefix (flight kind ``prefix_evict``), never a slot-held block."""
    pool = _pool(compiled, max_slots=2, max_len=8, block_size=4)
    assert pool.num_blocks == 4
    for start in (0, 40):  # two resident 1-block chains
        slot = pool.acquire()
        pool.ensure_cols(slot, 4)
        pool.release(slot, tokens=list(range(start, start + 4)))
    assert pool.free_blocks == 2 and len(pool.prefix) == 2
    pool.prefix.match(tuple(range(0, 4)) + (9,))  # freshen the first chain
    a, b = pool.acquire(), pool.acquire()
    pool.ensure_cols(a, pool.virtual_len)  # 2 blocks: drains the free list
    pool.ensure_cols(b, 4)  # 3rd block only exists by evicting a prefix
    assert len(pool.prefix) == 1  # LRU (the 40.. chain) was evicted
    assert pool.prefix.match(tuple(range(0, 4)) + (9,))[0] == 4
    events = flight.events(kind="prefix_evict")
    assert len(events) == 1
    assert events[0].detail["blocks"] == 1
    assert pool.prefix.evictions_total == 1
    pool.assert_block_invariants()


def test_free_count_conservation_after_seeded_churn(compiled):
    """Adversarial churn — admissions with shared prefixes, forks, COW
    writes, chain-publishing releases — conserves every block: the
    invariant checker passes at every step and all blocks are accounted
    for at the end."""
    rng = np.random.default_rng(0)
    pool = _pool(compiled, max_slots=4, max_len=16, block_size=4)
    live = {}  # slot -> token chain
    vocab = list(range(50, 60))
    for _ in range(200):
        op = rng.choice(["admit", "grow", "fork", "release"])
        try:
            if op == "admit" and pool.free_count > 0:
                slot = pool.acquire()
                prompt = [int(rng.choice(vocab))
                          for _ in range(int(rng.integers(1, 9)))]
                pool.admit_prefix(slot, prompt)
                live[slot] = prompt
                pool.ensure_cols(slot, len(prompt))
            elif op == "grow" and live:
                slot = int(rng.choice(list(live)))
                upto = min(len(live[slot]) + int(rng.integers(0, 5)),
                           pool.virtual_len)
                pool.ensure_cols(slot, upto)
                live[slot] += [int(rng.choice(vocab))
                               for _ in range(upto - len(live[slot]))]
                pool.ensure_writable(slot, upto - 1)
            elif op == "fork" and live and pool.free_count > 0:
                parent = int(rng.choice(list(live)))
                child = pool.fork_slot(parent)
                if child is not None:
                    live[child] = list(live[parent])
            elif op == "release" and live:
                slot = int(rng.choice(list(live)))
                pool.release(slot, tokens=live.pop(slot))
        except RuntimeError as e:
            # COW copies under full occupancy can legitimately exhaust
            # the pool; partial allocation must still conserve blocks.
            assert "out of KV blocks" in str(e)
        pool.assert_block_invariants()
    for slot in list(live):
        pool.release(slot, tokens=live.pop(slot))
    pool.assert_block_invariants()
    # Every block is either free or pinned by a resident prefix entry.
    resident = {b for e in pool.prefix._entries.values()
                for b in e.blocks}
    assert pool.free_blocks + len(resident) == pool.num_blocks


# -- serving identity --------------------------------------------------------


def _serve_all(eng, prompts, max_new_tokens=10, **submit_kw):
    rids = [eng.submit(p, max_new_tokens=max_new_tokens, **submit_kw)
            for p in prompts]
    return [eng.result(r, timeout_s=120).tokens for r in rids]


PROMPTS = [[5, 3, 9], [1, 2, 3, 4, 5, 6, 7], [11, 12]]


@pytest.mark.parametrize("layout", [{}, {"kv_block_size": 4},
                                    {"kv_block_size": 5}],
                         ids=["default_block", "block4", "block5"])
def test_paged_identical_to_per_row_oracle(compiled, layout):
    """THE tentpole pin: the paged layout emits exactly the tokens
    ``generate()`` gives each row alone, at one prefill and one decode
    compile, across block sizes that do and don't divide the
    prompt/cache lengths."""
    eng = _paged_engine(compiled, **layout)
    got = _serve_all(eng, PROMPTS)
    st = eng.stats()
    assert st["prefill_traces"] == 1 and st["decode_traces"] == 1
    for prompt, tokens in zip(PROMPTS, got):
        assert tokens == _per_row(compiled, prompt, 10)


def test_chunked_prefill_identical_to_one_shot(compiled):
    """Chunked prefill is the same math as one-shot (causal attention
    decomposes over chunks): every chunk width and per-step budget
    yields the per-row oracle's tokens, still one compile each."""
    for chunk, per_step in ((3, None), (3, 1), (2, 2), (1, 1)):
        eng = _paged_engine(compiled, kv_block_size=4, prefill_chunk=chunk,
                            prefill_chunks_per_step=per_step)
        got = _serve_all(eng, PROMPTS)
        st = eng.stats()
        assert st["prefill_traces"] == 1 and st["decode_traces"] == 1
        for prompt, tokens in zip(PROMPTS, got):
            assert tokens == _per_row(compiled, prompt, 10), (chunk, per_step)


def test_chunked_prefill_eos_stop_identity(compiled):
    free = _per_row(compiled, [5, 3, 9], 10)
    stop = free[3]
    eng = _paged_engine(compiled, stop_token=stop, kv_block_size=4,
                        prefill_chunk=2, prefill_chunks_per_step=1)
    res = eng.result(eng.submit([5, 3, 9], max_new_tokens=10), timeout_s=120)
    assert res.status == "completed"
    assert res.tokens == free[:free.index(stop) + 1]  # its first occurrence
    assert eng.pool.free_count == eng.pool.max_slots
    eng.pool.assert_block_invariants()


def test_chunked_prefill_deadline_eviction(compiled):
    """A request whose deadline expires MID-CHUNKED-PREFILL times out
    with zero tokens and returns every block it had bound."""
    clock = FakeClock()
    eng = _paged_engine(compiled, max_slots=1, clock=clock, kv_block_size=4,
                        prefill_chunk=2, prefill_chunks_per_step=1)
    busy = eng.submit([1, 2], max_new_tokens=40)
    doomed = eng.submit([3, 4, 5, 6, 7, 8], max_new_tokens=5, timeout_s=2.0)
    for _ in range(3):
        eng.step()
    clock.advance(5.0)  # doomed expires while queued behind the busy slot
    eng.run_until_drained()
    assert eng.result(doomed, timeout_s=10).status == "timeout"
    assert eng.result(busy, timeout_s=10).status == "completed"
    assert eng.pool.free_count == eng.pool.max_slots
    eng.pool.assert_block_invariants()


def test_deadline_eviction_mid_prefill_returns_blocks(compiled):
    """Expiry of a PARKED mid-prefill slot (chunk budget starves it
    while decode lanes run) releases the slot and its blocks."""
    clock = FakeClock()
    eng = _paged_engine(compiled, max_slots=2, clock=clock, kv_block_size=4,
                        prefill_chunk=1, prefill_chunks_per_step=1)
    busy = eng.submit([1, 2], max_new_tokens=30)
    eng.step()  # busy admits and starts decoding
    doomed = eng.submit([3, 4, 5, 6, 7, 8], max_new_tokens=5, timeout_s=1.0)
    eng.step()  # doomed claims a slot; 1-chunk budget leaves it parked
    assert eng.scheduler._prefilling  # mid-prefill, blocks bound
    held = eng.pool.blocks_in_use
    assert held > 0
    clock.advance(3.0)
    eng.run_until_drained()
    assert eng.result(doomed, timeout_s=10).status == "timeout"
    assert eng.result(busy, timeout_s=10).status == "completed"
    assert eng.pool.free_count == eng.pool.max_slots
    eng.pool.assert_block_invariants()


def test_prefix_hit_skips_prefill_and_stays_identical(compiled):
    """Back-to-back conversations sharing a full-block system prompt:
    the later ones admit off resident blocks (hit counters move, saved
    tokens accrue) and still emit oracle tokens."""
    sys_prompt = [7, 8, 9, 10]
    prompts = [sys_prompt + [1, 2], sys_prompt + [3, 4, 5], sys_prompt + [1, 2]]
    eng = _paged_engine(compiled, max_slots=2, kv_block_size=4)
    outs = []
    for p in prompts:  # sequential turns → later ones can share
        outs.append(eng.result(eng.submit(p, max_new_tokens=6),
                               timeout_s=120).tokens)
    for p, tokens in zip(prompts, outs):
        assert tokens == _per_row(compiled, p, 6)
    st = eng.stats()
    assert st["prefix_hits"] == 2 and st["prefix_lookups"] == 3
    assert st["prefix_tokens_saved"] == 8
    assert st["prefix_hit_rate"] == pytest.approx(2 / 3)
    eng.pool.assert_block_invariants()


@pytest.mark.parametrize("chunk,per_step", [(3, None), (3, 1), (2, 2), (1, 1),
                                            (4, None), (8, None)])
def test_paged_chunks_with_a_prefix_hit_identical_to_per_row(
        compiled, chunk, per_step):
    """The chunk program over the pool in place against ``generate()`` of
    each turn's full prompt, turn by turn, at every chunk width: the later
    turns adopt the shared system prompt's block, so their first chunk
    starts at ``start > 0`` behind blocks it reads and never writes."""
    sys_prompt = [7, 8, 9, 10]
    prompts = [sys_prompt + [1, 2], sys_prompt + [3, 4, 5], [11, 12],
               sys_prompt + [1, 2]]
    eng = _paged_engine(compiled, max_slots=2, kv_block_size=4,
                        prefill_chunk=chunk, prefill_chunks_per_step=per_step)
    for prompt in prompts:
        served = eng.result(eng.submit(prompt, max_new_tokens=6), timeout_s=120)
        assert served.tokens == _per_row(compiled, prompt, 6), prompt
    st = eng.stats()
    assert st["prefill_traces"] == 1 and st["decode_traces"] == 1
    assert st["prefix_hits"] == 2 and st["prefix_tokens_saved"] == 8
    eng.pool.assert_block_invariants()


def test_chunk_program_gathers_no_row(compiled):
    """The chunk program calls the paged chunk attention, once a layer, and
    nowhere ``paged_to_contiguous``: no contiguous row is built."""
    eng = _paged_engine(compiled, kv_block_size=4, prefill_chunk=3)
    program = str(jax.make_jaxpr(eng._chunk_prefill_impl)(
        eng.params, eng.pool.cache, eng.pool.device_table(),
        jnp.zeros((1, 3), jnp.int32), jnp.int32(0), jnp.int32(0), jnp.int32(3),
        eng._next_rng()))
    assert program.count("name=paged_chunk_attention") == 2
    assert "paged_to_contiguous" not in program


def test_paged_stats_and_load_signals(compiled):
    eng = _paged_engine(compiled, kv_block_size=4)
    _serve_all(eng, [[5, 3, 9]], max_new_tokens=4)
    st = eng.stats()
    assert st["kv_blocks_total"] == eng.pool.num_blocks
    assert 0 <= st["kv_blocks_free"] <= st["kv_blocks_total"]
    sig = eng.load.snapshot()["signals"]
    assert sig["kv_blocks_total"] == eng.pool.num_blocks
    assert sig["kv_free_frac"] == pytest.approx(
        sig["kv_blocks_free"] / sig["kv_blocks_total"])
    assert "prefix_hit_rate" in sig


def test_paged_pool_donation_guard(compiled):
    eng = _paged_engine(compiled, kv_block_size=4)
    eng.submit([5, 3, 9], max_new_tokens=4)
    eng.step()
    stale = eng.pool.cache
    eng.step()  # decode donates the pool; `stale` buffers die
    assert any(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(stale))
    eng.run_until_drained()
    eng.pool.swap(stale)
    with pytest.raises(DonatedBufferError):
        _ = eng.pool.cache


def test_paged_shard_serving_refuses_warm_engine(compiled):
    eng = _paged_engine(compiled, kv_block_size=4)
    _serve_all(eng, [[5, 3, 9]], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="before the first request"):
        eng.shard_serving(None)


# -- paged decode attention: the pool read and written in place ---------------

# (heads, block_size, head_dim): two columns to a 128-lane row (the served
# layout's kind), four to a 32-lane row, one column a row, and a block
# size that packs nothing.
_LAYOUTS = {"pack2": (3, 8, 64), "pack4": (5, 4, 8), "pack1": (2, 8, 128),
            "odd": (3, 5, 8),
            # grouped heads: 20 query heads on the pool's one K/V head of
            # 128, and 6 on 2 with two columns to a row
            "grouped20on1": (1, 8, 128), "grouped6on2": (2, 8, 64)}
_QUERY_HEADS = {"grouped20on1": 20, "grouped6on2": 6}
_BPS, _BLOCKS = 4, 10  # blocks per slot; physical blocks (id 10 is unallocated)
_LONG_BPS, _LONG_BLOCKS = 11, 40  # rows long enough for several grid steps
_LONG_CASES = ("many_steps", "one_beside_many")


def _paged_case(name, bs):
    """table rows, the column each lane writes, and the active mask."""
    long = list(range(1, _LONG_BLOCKS))
    rows = {
        # one lane at the very first column of its only block
        "column0": ([[3], [1, 4]], [0, bs + 2], [True, True]),
        # a lane writing the first column of a fresh block
        "block_edge": ([[3, 6], [1, 4]], [bs, 1], [True, True]),
        # a lane at the last column of its last block
        "last_column": ([[1, 5, 6, 7], [2]], [4 * bs - 1, 3], [True, True]),
        # an inactive lane rides along: nothing of it is written
        "inactive": ([[3, 6], [1, 4]], [bs + 1, 5], [True, False]),
        # two lanes share their first (prefix) block and own their tails
        "shared_prefix": ([[1, 4], [1, 5], [1, 6]],
                          [bs, 2 * bs - 1, bs + 2], [True, True, True]),
        # entries past a lane's allocation hold the out-of-range id
        "unallocated": ([[2], [7, 8], []], [bs - 1, bs, 0],
                        [True, True, False]),
        # lanes over several grid steps of 2 and of 4 blocks: 8 live
        # blocks (a multiple of both, the tail last in its step), 9 (the
        # tail first in its step, the rest of the step dead), 10 and 11
        # (the tail in the middle of a step of 4, last and first of 2)
        "many_steps": ([long[0:8], long[8:17], long[17:27], long[27:38]],
                       [8 * bs - 1, 8 * bs, 9 * bs + bs // 2, 11 * bs - 1],
                       [True] * 4),
        # a lane of one block beside a lane of many, and an inactive lane
        # of many between two active ones
        "one_beside_many": ([long[0:1], long[1:11], long[11:22], long[22:24]],
                            [bs // 2, 9 * bs + 1, 10 * bs + 2, bs],
                            [True, True, False, True]),
    }[name]
    bps, unallocated = ((_LONG_BPS, _LONG_BLOCKS) if name in _LONG_CASES
                        else (_BPS, _BLOCKS))
    table = np.full((len(rows[0]), bps), unallocated, np.int32)
    for s, ids in enumerate(rows[0]):
        table[s, :len(ids)] = ids
    return (jnp.asarray(table), jnp.asarray(rows[1], jnp.int32),
            jnp.asarray(rows[2]))


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("body", ["paged_xla", "paged_pallas-1",
                                  "paged_pallas-2", "paged_pallas-4"])
@pytest.mark.parametrize("case", ["column0", "block_edge", "last_column",
                                  "inactive", "shared_prefix", "unallocated",
                                  *_LONG_CASES])
def test_paged_decode_attention_matches_dense(case, body, layout):
    """Both bodies of the paged decode attention against dense attention
    over the gathered cache, the kernel at 1, 2 and 4 blocks a grid step:
    every active lane's new column lands in its block and nowhere else,
    and its query attends columns ``<= idx``."""
    from elephas_tpu.ops.attention import (
        paged_decode_attention,
        paged_to_contiguous,
        pool_leaf_shape,
    )
    from elephas_tpu.ops.attention_pallas import pallas_paged_decode_attention

    heads, bs, d = _LAYOUTS[layout]
    q_heads = _QUERY_HEADS.get(layout, heads)
    table, idx, active = _paged_case(case, bs)
    slots = table.shape[0]
    num_blocks = _LONG_BLOCKS if case in _LONG_CASES else _BLOCKS
    rng = np.random.default_rng(7)
    shape = pool_leaf_shape(num_blocks, heads, bs, d)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for _ in range(2))
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
                       for h in (q_heads, heads, heads))
    body, _, blocks = body.partition("-")
    if body == "paged_pallas":  # the kernel, interpreted on the CPU
        out, k_after, v_after = pallas_paged_decode_attention(
            q, k_new, v_new, k_pool, v_pool, table, idx, active,
            blocks=int(blocks), interpret=True)
    else:
        out, k_after, v_after = paged_decode_attention(
            q, k_new, v_new, k_pool, v_pool, table, idx, active, body)

    k_want, v_want = (np.array(paged_to_contiguous(p, table, d))
                      for p in (k_pool, v_pool))
    for s in range(slots):
        if active[s]:
            k_want[s, :, int(idx[s])] = k_new[s]
            v_want[s, :, int(idx[s])] = v_new[s]
    k_got = np.asarray(paged_to_contiguous(k_after, table, d))
    v_got = np.asarray(paged_to_contiguous(v_after, table, d))
    for s in range(slots):
        live = int(idx[s]) + 1
        if not active[s]:
            continue
        np.testing.assert_array_equal(k_got[s, :, :live], k_want[s, :, :live])
        np.testing.assert_array_equal(v_got[s, :, :live], v_want[s, :, :live])
        # query head h reads K/V head h // (q_heads // heads)
        kv_of = np.arange(q_heads) // (q_heads // heads)
        scores = np.einsum("hd,hkd->hk", q[s], k_want[s, kv_of, :live]) / np.sqrt(d)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        want = np.einsum("hk,hkd->hd", weights, v_want[s, kv_of, :live])
        np.testing.assert_allclose(np.asarray(out[s]), want,
                                   rtol=2e-5, atol=2e-5)
    # only the active lanes' tail blocks differ from the pool as it was
    tails = {int(table[s, int(idx[s]) // bs]) for s in range(slots)
             if active[s]}
    changed = set(np.flatnonzero(
        (np.asarray(k_after) != np.asarray(k_pool)).any(axis=(1, 2, 3))))
    assert changed == tails
    assert changed == set(np.flatnonzero(
        (np.asarray(v_after) != np.asarray(v_pool)).any(axis=(1, 2, 3))))


# -- paged chunk attention: a prefill chunk over the pool in place ------------


def _chunk_case(name, bs):
    """The one slot's block ids, the chunk's ``start`` and width, how many
    of its tokens are the prompt's (the rest is the right-pad tail), and
    whether the caller promises whole blocks from a block's first column."""
    return {
        # a prompt's first chunk, over a block edge
        "start0": ([3, 6], 0, bs + 2, bs + 2, False),
        # from the middle of a block into the next two
        "mid_block": ([1, 5, 6], bs + bs // 2, bs + 1, bs + 1, False),
        # from a block's first column (a chunk boundary, or a prefix
        # adopted up to there): two whole blocks behind two that are read
        "block_aligned": ([1, 5, 6, 7], 2 * bs, 2 * bs, 2 * bs, False),
        # a prompt's last chunk: two tokens and a right-pad tail
        "ragged_valid": ([2, 4], bs, bs, 2, False),
        # the tail runs past the slot's allocation: those columns drop
        "past_allocation": ([7, 8], bs + 1, bs + 3, bs - 1, False),
        # the same three as whole blocks, written without a read
        "whole_blocks": ([1, 5, 6, 7], 2 * bs, 2 * bs, 2 * bs, True),
        "whole_ragged": ([2, 4], bs, bs, 2, True),
        "whole_past_allocation": ([7, 8], bs, 2 * bs, bs - 1, True),
    }[name]


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("body", ["paged_xla", "paged_pallas"])
@pytest.mark.parametrize("case", ["start0", "mid_block", "block_aligned",
                                  "ragged_valid", "past_allocation",
                                  "whole_blocks", "whole_ragged",
                                  "whole_past_allocation"])
def test_paged_chunk_attention_matches_dense(case, body, layout):
    """Both bodies of the paged chunk attention against dense attention
    over the gathered row: the chunk's columns land in the blocks they
    fall in and nowhere else, and query ``i`` attends columns
    ``<= start + i``."""
    from elephas_tpu.ops.attention import (
        paged_chunk_attention,
        paged_to_contiguous,
        pool_leaf_shape,
        scatter_prefill_blocks,
        scatter_prefill_columns,
    )
    from elephas_tpu.ops.attention_pallas import pallas_paged_chunk_attention

    heads, bs, d = _LAYOUTS[layout]
    q_heads = _QUERY_HEADS.get(layout, heads)
    ids, start, width, valid, aligned = _chunk_case(case, bs)
    row = np.full((_BPS,), _BLOCKS, np.int32)
    row[:len(ids)] = ids
    row, held = jnp.asarray(row), len(ids) * bs
    rng = np.random.default_rng(13)
    shape = pool_leaf_shape(_BLOCKS, heads, bs, d)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for _ in range(2))
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(h, width, d)), jnp.float32)
                       for h in (q_heads, heads, heads))
    at = jnp.int32(start)
    if body == "paged_pallas":  # the kernel, interpreted on the CPU
        write = scatter_prefill_blocks if aligned else scatter_prefill_columns
        k_after = write(k_pool, row, at, k_new)
        v_after = write(v_pool, row, at, v_new)
        out = pallas_paged_chunk_attention(q, k_after, v_after, row, at,
                                           interpret=True)
    else:
        out, k_after, v_after = paged_chunk_attention(
            q, k_new, v_new, k_pool, v_pool, row, at, body, aligned)

    landed = min(width, held - start)  # the rest runs off the allocation
    assert valid <= landed
    want, got = [], []
    for pool, after, new in ((k_pool, k_after, k_new), (v_pool, v_after, v_new)):
        dense = np.array(paged_to_contiguous(pool, row[None], d))[0, :, :held]
        dense[:, start:start + landed] = np.asarray(new)[:, :landed]
        want.append(dense)
        got.append(np.asarray(paged_to_contiguous(after, row[None], d))[0, :, :held])
        np.testing.assert_array_equal(got[-1], dense)
    kv_of = np.arange(q_heads) // (q_heads // heads)
    for i in range(valid):  # the right-pad tail's queries are not read
        live = start + i + 1
        scores = np.einsum("hd,hkd->hk", np.asarray(q)[:, i],
                           want[0][kv_of, :live]) / np.sqrt(d)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(out)[:, i],
            np.einsum("hk,hkd->hd", weights, want[1][kv_of, :live]),
            rtol=2e-5, atol=2e-5)
    # only the slot's own blocks under the chunk differ from the pool as it
    # was: no block below ``start``'s, none of another slot, none unallocated
    under = {ids[j] for j in range(start // bs, (start + width - 1) // bs + 1)
             if j < len(ids)}
    for before, after in ((k_pool, k_after), (v_pool, v_after)):
        changed = set(np.flatnonzero(
            (np.asarray(after) != np.asarray(before)).any(axis=(1, 2, 3))))
        assert changed == under


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_window_scatters_write_their_columns_only(layout):
    """``scatter_prefill_columns`` and ``scatter_spec_columns`` on the
    packed blocks: a window's columns land, across block edges; columns
    in unallocated blocks, inactive lanes and every other block stay."""
    from elephas_tpu.ops.attention import (
        paged_to_contiguous,
        pool_leaf_shape,
        scatter_prefill_columns,
        scatter_spec_columns,
    )

    heads, bs, d = _LAYOUTS[layout]
    table, _, _ = _paged_case("shared_prefix", bs)
    table = table.at[0].set(jnp.asarray([3, _BLOCKS, _BLOCKS, _BLOCKS]))
    rng = np.random.default_rng(11)
    pool = jnp.asarray(rng.normal(
        size=pool_leaf_shape(_BLOCKS, heads, bs, d)), jnp.float32)
    held = [bs, 2 * bs, 2 * bs]  # columns each row's allocated blocks hold

    def rows(leaf):
        view = np.asarray(paged_to_contiguous(leaf, table, d))
        return [view[s, :, :n] for s, n in enumerate(held)]

    before = rows(pool)
    width = bs + 2  # a chunk that crosses a block edge and runs off the row
    chunk = np.asarray(rng.normal(size=(heads, width, d)), np.float32)
    start = bs - 1
    got = rows(scatter_prefill_columns(pool, table[1], jnp.int32(start),
                                       jnp.asarray(chunk)))
    want = [r.copy() for r in before]
    want[1][:, start:] = chunk[:, :2 * bs - start]
    want[2][:, :bs] = want[1][:, :bs]  # the first block is shared
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

    count = 3
    view = np.asarray(rng.normal(size=(3, heads, _BPS * bs + count, d)),
                      np.float32)
    idx = [bs - 1, bs, 2 * bs - 2]
    got = rows(scatter_spec_columns(
        pool, jnp.asarray(view), table, jnp.asarray(idx, jnp.int32), count,
        jnp.asarray([True, False, True])))
    want = [r.copy() for r in before]
    for s in (0, 2):  # lane 1 is inactive; what runs off a row drops
        want[s][:, idx[s]:] = view[s, :, idx[s]:held[s]]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_decode_attention_is_named_on_stats_and_step_events(compiled):
    """``stats()`` and every ``step`` event say which attention body the
    decode program and the chunk program were traced with, and how many
    blocks a grid step of the decode kernel folds; the pool is the donated
    pytree still."""
    events = []

    class Sink:
        def log(self, step, **fields):
            events.append(fields)

    eng = _paged_engine(compiled, kv_block_size=4, sink=Sink())
    assert eng.stats()["decode_attention"] == "paged_xla"  # the CPU's body
    assert eng.stats()["prefill_attention"] == "paged_xla"
    assert eng.stats()["decode_kernel_blocks"] is None  # the kernel's alone
    stale = eng.pool.cache
    res = eng.result(eng.submit([5, 3, 9], max_new_tokens=4), timeout_s=120)
    assert res.status == "completed"
    steps = [e for e in events if e.get("event") == "step"]
    assert steps and all(e["decode_attention"] == "paged_xla" for e in steps)
    assert all(e["prefill_attention"] == "paged_xla" for e in steps)
    assert all(e["decode_kernel_blocks"] is None for e in steps)
    assert eng.stats()["decode_live_blocks"] == 0
    assert eng.stats()["decode_dead_blocks"] == 0
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(stale))

    # what the kernel's steps cover, from the lanes' lengths alone: blocks
    # of 4 columns, 4 blocks a grid step. A lane that holds n columns
    # writes column n: 1, 1, 2, 4, 5 and 8 live blocks, in steps that
    # cover 4, 4, 4, 4, 8 and 8.
    from elephas_tpu.serving.metrics import ServingMetrics

    metrics = ServingMetrics(sink=Sink())
    metrics.decode_attention = "paged_pallas"
    metrics.decode_kernel_blocks, metrics.kv_block_size = 4, 4
    metrics.record_step(0, 4, 4, 0.01, lane_lengths=[0, 3, 4, 15])
    metrics.record_step(0, 2, 2, 0.01, lane_lengths=[16, 31])
    assert events[-1]["decode_kernel_blocks"] == 4
    assert metrics.summary()["decode_live_blocks"] == 1 + 1 + 2 + 4 + 5 + 8
    assert metrics.summary()["decode_dead_blocks"] == 3 + 3 + 2 + 0 + 3 + 0
    metrics.reset()
    assert metrics.summary()["decode_live_blocks"] == 0
    assert metrics.decode_kernel_blocks == 4  # kept, as the bodies' names
