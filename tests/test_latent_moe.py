"""The latent-attention, routed-expert family (`models/latent_moe.py`)
through the serving engine: a latent paged cache with rotary positions on
the decode path, and a routed layer that is told which experts it holds.
Small sizes, seeded weights from the benchmark's family file, compared with
the benchmark's plain reference (`benchmark/references/deepseek_v2.py`:
float32, `highest` precision, keys and values a head, a full causal
softmax, nothing of the program) or with plain numpy."""

import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import InferenceEngine, compile_model
from elephas_tpu.models import latent_moe
from elephas_tpu.models.decode_cache import KV, has_latent, leaves_of_kind
from elephas_tpu.ops import attention, routed_experts
from elephas_tpu.ops.attention_pallas import (
    pallas_latent_chunk_attention,
    pallas_latent_decode_attention,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SEED = 2147483659
YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
            original_max_position_embeddings=4096, type="yarn")
PUBLISHED = dict(
    num_hidden_layers=60, hidden_size=5120, intermediate_size=12288,
    num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, vocab_size=102400, first_k_dense_replace=1,
    n_routed_experts=160, router_experts=160, experts_first=0, n_shared_experts=2,
    moe_intermediate_size=1536, num_experts_per_tok=6, n_group=8, topk_group=3,
    routed_scaling_factor=16, rope_theta=10000, rope_scaling=YARN, rms_norm_eps=1e-6,
    max_position_embeddings=163840, topk_method="group_limited_greedy",
    scoring_func="softmax", norm_topk_prob=False, moe_layer_freq=1,
    tie_word_embeddings=False, attention_bias=False)
SMALL = dict(PUBLISHED, num_hidden_layers=3, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, vocab_size=211, n_routed_experts=4,
             router_experts=8, moe_intermediate_size=32, num_experts_per_tok=2,
             n_group=4, topk_group=2)


def _bench_module(kind, name):
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod  # a dataclass looks its module up there
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def family():
    return _bench_module("models", "deepseek_v2")


@pytest.fixture(scope="module")
def reference():
    return _bench_module("references", "deepseek_v2")


def _compiled(family, sizes=SMALL):
    cfg = family.shape(sizes)
    params = family.params(SEED, cfg, jnp.float32)
    module = family.flax_module(cfg, "float32")
    return module, params, compile_model(
        module, params=params, optimizer="sgd", loss="sparse_categorical_crossentropy",
        metrics=[], input_shape=(30,), input_dtype=jnp.int32)


@pytest.fixture(scope="module")
def served(family):
    """(module, params, compiled) at the small size, weights from the seed."""
    return _compiled(family)


def _engine(compiled, **kw):
    sizes = dict(max_slots=3, max_prompt_len=30, max_len=48, kv_block_size=8,
                 prefill_chunk=8, queue_depth=16)
    sizes.update(kw)
    return InferenceEngine(compiled, **sizes)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SMALL["vocab_size"], n).tolist() for n in lengths]


def _reference_logits(reference, family, tokens, rows, sizes=SMALL):
    cfg = family.shape(sizes)
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
    # the reference stands alone, and is the published form
    source = open(os.path.join(BENCH, "references", "deepseek_v2.py")).read()
    code = source.split('"""', 2)[2]
    assert "elephas_tpu" not in code and "ragged_dot" not in code
    assert 'default_matmul_precision("highest")' in code
    assert "k_nope, v = expanded" in code  # keys and values a head, not absorbed


# -- (b) through the engine: chunks, a ragged last one, then decode ----------


def test_chunked_prefill_and_decode_match_the_reference(served, family, reference):
    _, _, compiled = served
    eng = _engine(compiled)
    assert has_latent(eng.pool.cache) and not eng.stateful and eng.takes_valid
    prompts = _prompts([5, 13, 19, 29, 9, 17])  # none a multiple of the chunk of 8
    results = _serve(eng, prompts)
    assert all(r.status == "completed" and len(r.tokens) == 8 for r in results)
    assert _served_gaps(reference, family, prompts, results).max() < 1e-5
    stats = eng.stats()
    assert stats["prefill_traces"] == stats["decode_traces"] == 1
    assert stats["decode_attention"] == stats["prefill_attention"] == "paged_xla"


@pytest.mark.parametrize("fault", ["a chunk's tokens at the chunk's offset",
                                   "every lane at the first lane's position"])
def test_a_wrong_rotary_position_is_seen(served, family, reference, monkeypatch, fault):
    """The faults the comparison has to see: every token of a chunk rotated
    at the chunk's first position, and every lane of a decode step rotated
    at one lane's position (lanes differ in length)."""
    _, _, compiled = served
    rotate = latent_moe.rotate

    def wrong(x, positions, rope):
        if fault.startswith("a chunk") and positions.shape[1] > 1 and positions.shape[0] == 1:
            positions = jnp.broadcast_to(positions[:, :1], positions.shape)
        if fault.startswith("every lane") and positions.shape[1] == 1:
            positions = jnp.broadcast_to(positions[:1], positions.shape)
        return rotate(x, positions, rope)

    monkeypatch.setattr(latent_moe, "rotate", wrong)
    prompts = _prompts([13, 19, 29])
    results = _serve(_engine(compiled), prompts)
    assert _served_gaps(reference, family, prompts, results).max() > 1e-5


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_every_chunk_width_gives_the_one_shot_prefills_tokens(served, chunk):
    _, _, compiled = served
    prompts = _prompts([5, 13, 29, 17])
    whole = [r.tokens for r in _serve(_engine(compiled, prefill_chunk=30), prompts)]
    assert [r.tokens for r in _serve(_engine(compiled, prefill_chunk=chunk), prompts)] == whole


def test_a_reused_slot_gives_what_it_gives_alone(served):
    _, _, compiled = served
    first, second = _prompts([29, 11], seed=5)
    alone = _serve(_engine(compiled, max_slots=1), [second])[0].tokens
    eng = _engine(compiled, max_slots=1, prefix_cache=False)
    reused = _serve(eng, [first, second])[1].tokens
    assert eng.pool.admitted_total == 2 and reused == alone


def test_the_chunk_program_builds_no_row_and_no_key_a_head(served):
    _, _, compiled = served
    eng = _engine(compiled)
    program = str(jax.make_jaxpr(eng._chunk_prefill_impl)(
        eng.params, eng.pool.cache, eng.pool.device_table(),
        jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(0), jnp.int32(8),
        eng._next_rng()))
    assert program.count("name=paged_chunk_attention") == SMALL["num_hidden_layers"]
    assert "paged_to_contiguous" not in program


# -- (c) the share -----------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer(family, reference):
    """Four devices that each hold a quarter of the experts, each told
    which, and the shared experts counted once, give the uncut routed layer
    of the reference."""
    uncut = dict(SMALL, n_routed_experts=8)
    cfg = family.shape(uncut)
    w = family.block_at(SEED, 1, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 11, cfg["hidden_size"]))
    hyper = {**family.hyper(cfg), "count": 8}
    static = tuple(sorted((k, v) for k, v in hyper.items() if k != "rope"))
    ids, weights, load = reference._route(
        {"ffn_norm": w["ffn_norm"], "experts": {"router": w["experts"]["router"]}},
        x, 1e-6, static, reference.identity)
    want = reference._routed({k: w[k] for k in ("ffn_norm", "shared", "experts")}, x, ids,
                             weights, 1e-6, static, 64, reference.identity)
    y = reference.rms_norm(x, w["ffn_norm"], 1e-6).reshape(-1, cfg["hidden_size"])
    total = x.reshape(y.shape) + reference.gated(w["shared"], y, reference.identity)
    for first in (0, 2, 4, 6):
        layer = latent_moe.RoutedExperts(
            n_routed_experts=8, experts_held=(first, 2), moe_d_ff=32, top_k=2, n_group=4,
            topk_group=2, routed_scaling_factor=16.0)
        held = {"router": w["experts"]["router"],
                **{k: w["experts"][k][first:first + 2] for k in ("gate", "up", "down")}}
        part, counted = layer.apply({"params": held}, y, mutable=["counters"])
        total = total + part
        sown = counted["counters"]
        assert float(sown["moe_assignments"][0]) == y.shape[0] * 2
        assert float(sown["moe_assignments_held"][0]) == float(load[first:first + 2].sum())
    assert int(load.sum()) == y.shape[0] * 2  # held by one of the four, each
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# -- (d) the router ----------------------------------------------------------


def _numpy_group_limited_greedy(p, n_group, topk_group, top_k):
    ids = np.zeros((p.shape[0], top_k), np.int64)
    for t, row in enumerate(p):
        groups = row.reshape(n_group, -1)
        best = np.argsort(-groups.max(-1), kind="stable")[:topk_group]
        limited = np.zeros_like(row).reshape(n_group, -1)
        limited[best] = groups[best]
        ids[t] = np.argsort(-limited.reshape(-1), kind="stable")[:top_k]
    return ids


def test_the_router_is_group_limited_greedy_ties_included():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((64, 160)).astype(np.float32)
    logits[:8] = np.round(logits[:8])  # ties, within a group and across groups
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    ids, values = routed_experts.group_limited_top_k(jnp.asarray(p), 8, 3, 6)
    want = _numpy_group_limited_greedy(p, 8, 3, 6)
    np.testing.assert_array_equal(np.asarray(ids), want)
    assert all(len(set(row // 20)) <= 3 and len(set(row)) == 6 for row in want)
    np.testing.assert_array_equal(np.asarray(values), np.take_along_axis(p, want, 1))
    # the layer weighs 16 p, not renormalised: the six do not add up to 16
    assert not np.allclose(16 * np.asarray(values).sum(-1), 16.0)


# -- (e) the grouped product --------------------------------------------------


def _dense_routed(y, ids, weights, gate, up, down, first):
    out = np.zeros(y.shape, np.float64)
    for t in range(y.shape[0]):
        for e, w in zip(ids[t], weights[t]):
            if first <= e < first + gate.shape[0]:
                h = y[t] @ gate[e - first]
                h = h / (1 + np.exp(-h)) * (y[t] @ up[e - first])
                out[t] += w * (h @ down[e - first])
    return out


@pytest.mark.parametrize("case", ["an expert with no token", "every assignment on one expert",
                                  "no assignment on a held expert", "mixed"])
def test_the_routed_product_is_exact_whatever_the_routing(case):
    rng = np.random.default_rng(1)
    tokens, d, f, top_k, first, count = 24, 16, 8, 3, 4, 4
    y = rng.standard_normal((tokens, d)).astype(np.float32)
    gate, up = (rng.standard_normal((count, d, f)).astype(np.float32) for _ in range(2))
    down = rng.standard_normal((count, f, d)).astype(np.float32)
    weights = rng.uniform(0.1, 2.0, (tokens, top_k)).astype(np.float32)
    ids = {"an expert with no token": rng.choice([0, 4, 5, 7, 9], (tokens, top_k)),
           "every assignment on one expert": np.full((tokens, top_k), 6),
           "no assignment on a held expert": rng.choice([0, 1, 2, 3, 8, 9], (tokens, top_k)),
           "mixed": rng.integers(0, 12, (tokens, top_k))}[case]
    out, load = routed_experts.routed_experts(
        jnp.asarray(y), jnp.asarray(ids), jnp.asarray(weights), jnp.asarray(gate),
        jnp.asarray(up), jnp.asarray(down), first)
    np.testing.assert_allclose(np.asarray(out), _dense_routed(
        y, ids, weights, gate, up, down, first), atol=1e-4, rtol=1e-4)
    held = (ids >= first) & (ids < first + count)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(ids[held] - first, minlength=count))  # nothing dropped


_LAYOUT_CASES = {
    # (tokens, top_k, the experts the ids are drawn from): of 12 experts, 4 .. 7 are held
    "an expert with no token": (200, 3, [0, 4, 5, 7, 9]),
    "every assignment on one expert": (200, 3, [6]),
    "no assignment on a held expert": (200, 3, [0, 1, 2, 3, 8, 9]),
    "mixed": (200, 3, list(range(12))),
    "every assignment held": (100, 3, [4, 5, 6, 7]),
    "a decode step's one tile": (32, 3, list(range(12))),
    "top_k 6, a quarter of the experts held": (100, 6, list(range(16))),
    "top_k 8, an eighth of the experts held": (64, 8, list(range(32))),
}


@pytest.mark.parametrize("case", list(_LAYOUT_CASES))
def test_the_routed_product_is_exact_in_the_kernels_layout(case, monkeypatch):
    """``routed_experts()`` with the kernels for its body, interpreted here:
    over one tile's assignments (600, 300, 512) the rows are laid out anew, a
    group from its own start, and the way back's kernel reads the held ones
    alone; 96 lie packed and are gathered back."""
    import functools

    for kernel in ("pallas_grouped_matmul", "pallas_combine_rows"):
        monkeypatch.setattr(routed_experts, kernel, functools.partial(
            getattr(routed_experts, kernel), interpret=True))
    tokens, top_k, drawn = _LAYOUT_CASES[case]
    rng = np.random.default_rng(3)
    d, f, first, count = 256, 128, 4, 4
    y = rng.standard_normal((tokens, d)).astype(np.float32)
    gate, up = (rng.standard_normal((count, d, f)).astype(np.float32) / 16 for _ in range(2))
    down = rng.standard_normal((count, f, d)).astype(np.float32) / 16
    weights = rng.uniform(0.1, 2.0, (tokens, top_k)).astype(np.float32)
    ids = rng.choice(drawn, (tokens, top_k))
    assert routed_experts._pallas_fits(tokens * top_k, d, f, jnp.float32)
    assert routed_experts._combine_fits(tokens, top_k, d, jnp.float32)
    args = [jnp.asarray(a) for a in (y, ids, weights, gate, up, down)]
    # one program: no host op is dispatched beside the interpreter's callbacks
    out, load = jax.block_until_ready(jax.jit(
        routed_experts.routed_experts, static_argnames=("first", "body"))(
        *args, first=first, body="grouped_pallas"))
    np.testing.assert_allclose(np.asarray(out), _dense_routed(
        y, ids, weights, gate, up, down, first), atol=2e-4, rtol=2e-4)
    held = (ids >= first) & (ids < first + count)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(ids[held] - first, minlength=count))  # nothing dropped
    # the same products in the same order as the packed rows' body: the same sums
    packed, _ = routed_experts.routed_experts(*args, first, body="grouped_xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(packed), atol=1e-5, rtol=1e-5)


def _laid_out(packed, sizes, fill):
    """``packed``'s rows (group after group) in the layout the kernel takes
    for as many, every other row ``fill``; and where each row went."""
    rows = routed_experts.laid_out_rows(packed.shape[0], len(sizes))
    starts = np.asarray(routed_experts.group_starts(jnp.asarray(sizes, jnp.int32), rows))
    where = np.concatenate([starts[g] + np.arange(n) for g, n in enumerate(sizes)]
                           + [np.zeros((0,), np.int64)]).astype(np.int64)
    laid = np.full((rows,) + packed.shape[1:], fill, packed.dtype)
    laid[where] = packed[:len(where)]
    return laid, where


_KERNEL_CASES = {
    # (rows, sizes, depth, cols, dtype); the first five are PR 34's
    "empty groups between": (512, [100, 0, 50, 7, 0, 0, 200, 1], 256, 128, np.float32),
    "every row on one expert": (512, [0, 512, 0, 0], 256, 128, np.float32),
    "no row at all": (512, [0, 0, 0, 0], 256, 128, np.float32),
    "three rows on the last expert": (512, [0, 0, 0, 3], 256, 128, np.float32),
    "two groups of a tile each": (512, [256, 256], 256, 128, np.float32),
    "a group over several row tiles": (1024, [3, 700, 0, 130], 2048, 1536, np.float32),
    "groups smaller than a sub-tile": (512, [5, 17, 100, 1, 0, 90, 127, 2], 256, 1024,
                                       np.float32),
    "a group that ends on a tile's edge": (1024, [256, 128, 512, 1], 256, 128, np.float32),
    "every row held, none to spare": (512, [129, 129, 129, 125], 256, 128, np.float32),
    "a decode step's tile of 96 rows": (96, [5, 0, 31, 1, 0, 40, 0, 6], 2048, 1024,
                                        np.float32),
    "a decode step's tile of 128 rows": (128, [0, 128, 0, 0], 256, 128, np.float32),
    "bfloat16 over several tiles": (768, [300, 0, 20, 256, 1], 2048, 512, jnp.bfloat16),
    "bfloat16 in one tile": (128, [64, 0, 3, 50], 256, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_grouped_kernel_matches_a_loop_of_products(case):
    rows, sizes, depth, cols, dtype = _KERNEL_CASES[case]
    rng = np.random.default_rng(2)
    lhs = np.asarray(jnp.asarray(rng.standard_normal((rows, depth)), dtype))
    rhs = np.asarray(jnp.asarray(rng.standard_normal((len(sizes), depth, cols)), dtype))
    want, at = np.zeros((rows, cols), np.float32), 0
    for g, n in enumerate(sizes):
        want[at:at + n] = lhs[at:at + n].astype(np.float32) @ rhs[g].astype(np.float32)
        at += n
    close = dict(atol=2e-4 * depth ** 0.5, rtol=2e-4) if dtype is np.float32 else \
        dict(atol=0.02 * depth ** 0.5, rtol=0.02)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    # the kernel's layout; a row no group owns must reach no row that one does
    laid, where = _laid_out(lhs, sizes, np.nan)
    got = np.asarray(routed_experts.pallas_grouped_matmul(
        jnp.asarray(laid), jnp.asarray(rhs), group_sizes, interpret=True), np.float32)
    np.testing.assert_allclose(got[where], want[:at], **close)
    if rows <= routed_experts._ROW_TILE:  # one tile lies packed, and its other rows read 0
        np.testing.assert_array_equal(where, np.arange(at))
        assert not got[at:].any()
    else:
        assert (where % routed_experts._SUB_TILE == 0).sum() >= np.count_nonzero(sizes)
    xla = routed_experts.grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs), group_sizes)
    np.testing.assert_allclose(np.asarray(xla, np.float32), want, **close)


def _gather_and_sum(rows, order, weights, sizes):
    """PR 37's way back, the XLA body the kernel replaced: a row gathered
    for every assignment, held or not (one held elsewhere reads the last
    row), weighed under a mask of the held ones and summed a token."""
    laid, d = rows.shape
    tokens, top_k = weights.shape
    sizes = jnp.asarray(sizes, jnp.int32)
    starts, ends = routed_experts.group_starts(sizes, laid), jnp.cumsum(sizes)
    at = jnp.arange(tokens * top_k)
    group = jnp.minimum(jnp.searchsorted(ends, at, side="right"), len(sizes) - 1)
    row = jnp.where(at < ends[-1], starts[group] + at - (ends - sizes)[group], laid)
    back = jnp.zeros((tokens * top_k,), jnp.int32).at[jnp.asarray(order)].set(row)
    part = jnp.asarray(rows)[jnp.minimum(back, laid - 1)].astype(jnp.float32)
    part = jnp.where((back < laid)[:, None],
                     part * jnp.asarray(weights).reshape(-1, 1).astype(jnp.float32), 0)
    return np.asarray(part.reshape(tokens, top_k, d).sum(1))


_COMBINE_CASES = {
    # (tokens, top_k, the experts the ids are drawn from, d, dtype): 0 .. 7 are held
    "top_k 6, a quarter of the experts held": (256, 6, range(32), 256, np.float32),
    "top_k 8, an eighth of the experts held": (256, 8, range(64), 256, np.float32),
    "no assignment held": (100, 6, range(8, 20), 256, np.float32),
    "every assignment held": (100, 6, range(8), 128, np.float32),
    "one group over several row tiles": (300, 2, [3, 11], 128, np.float32),
    "a token count that fills no tile": (203, 6, range(24), 384, np.float32),
    "bfloat16 rows over the whole width": (256, 6, range(32), 1024, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_COMBINE_CASES))
def test_the_way_back_matches_the_gather_and_sum(case):
    """``pallas_combine_rows`` against the body it replaced, over the layout
    of many row tiles in which every row no group owns holds NaN, the last
    one among them (where the old body pointed an assignment held elsewhere):
    the held rows are read alone, and every token comes back finite."""
    tokens, top_k, drawn, d, dtype = _COMBINE_CASES[case]
    rng = np.random.default_rng(4)
    count = 8
    ids = np.stack([rng.choice(list(drawn), top_k, replace=False) for _ in range(tokens)])
    if case == "one group over several row tiles":
        ids[:] = [3, 11]  # every token on expert 3: a group of 300 rows
    local = np.where(ids.reshape(-1) < count, ids.reshape(-1), count)
    order = np.argsort(local, kind="stable").astype(np.int32)
    sizes = np.bincount(local, minlength=count + 1)[:count]
    packed = np.asarray(jnp.asarray(rng.standard_normal((tokens * top_k, d)), dtype))
    laid, _ = _laid_out(packed, sizes, np.nan)
    assert laid.shape[0] > routed_experts._ROW_TILE
    assert np.isnan(np.asarray(laid[-1], np.float32)).all()
    weights = rng.uniform(0.1, 2.0, (tokens, top_k)).astype(np.float32)
    got = np.asarray(routed_experts.pallas_combine_rows(
        jnp.asarray(laid), jnp.asarray(order), jnp.asarray(weights), jnp.asarray(sizes),
        interpret=True))
    assert got.shape == (tokens, d) and got.dtype == np.float32
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _gather_and_sum(laid, order, weights, sizes),
                               atol=1e-5, rtol=1e-6)
    if not sizes.any():
        assert not got.any()


# -- (f) rotary ----------------------------------------------------------------


def test_yarn_frequencies_and_the_softmax_scale(reference):
    rope = (10000.0, 40.0, 4096.0, 32.0, 1.0, 0.707, 0.707)
    got = latent_moe.rotary_frequencies(64, rope)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23) and got.shape == (32,)
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1])       # fast: as they are
    np.testing.assert_allclose(got[high:], plain[high:] / 40)        # slow: stretched
    ramp = (np.arange(low + 1, high) - low) / (high - low)
    np.testing.assert_allclose(got[low + 1:high],
                               plain[low + 1:high] * (1 - ramp) + plain[low + 1:high] / 40 * ramp)
    np.testing.assert_allclose(got, reference.rotary_frequencies(64, rope))
    assert abs(latent_moe.softmax_scale(192, rope) - 0.11472) < 5e-6
    assert abs(reference.softmax_scale(192, rope) - 0.11472) < 5e-6
    assert latent_moe.softmax_scale(192, None) == 192 ** -0.5
    # pairs (2i, 2i + 1): position 0 turns nothing, a pair keeps its norm
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64))
    np.testing.assert_allclose(latent_moe.rotate(x, jnp.zeros((2, 3), jnp.int32), rope), x,
                               atol=1e-6)
    turned = np.asarray(latent_moe.rotate(x, jnp.asarray([[5, 6, 7], [90, 91, 92]]), rope))
    np.testing.assert_allclose((turned.reshape(2, 3, 32, 2) ** 2).sum(-1),
                               (np.asarray(x).reshape(2, 3, 32, 2) ** 2).sum(-1), rtol=1e-5)


# -- (g) both paged bodies on a latent pool ----------------------------------


def _latent_case(heads=8, rank=128, pe=64, nope=32, v_head=32, bs=128, bps=3, slots=4):
    width, nb = rank + pe, slots * bps
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    pool = jax.random.normal(keys[0], attention.latent_leaf_shape(nb, bs, width))
    table = jnp.asarray(np.random.default_rng(0).permutation(nb).reshape(slots, bps),
                        jnp.int32)
    kv_b = jax.random.normal(keys[1], (rank, heads, nope + v_head)) * 0.1
    return dict(pool=pool, table=table, kv_b=kv_b, keys=keys, heads=heads, rank=rank,
                pe=pe, nope=nope, v_head=v_head, bs=bs, width=width, slots=slots)


def _dense_latent_attention(c, q, latents, last, scale):
    """Keys and values a head expanded from ``latents`` (L, width); ``q``:
    (heads, Q, nope + pe); ``last``: (Q,) the last column each attends."""
    k_nope = jnp.einsum("lr,rhf->hlf", latents[:, :c["rank"]], c["kv_b"][..., :c["nope"]])
    v = jnp.einsum("lr,rhf->hlf", latents[:, :c["rank"]], c["kv_b"][..., c["nope"]:])
    s = (jnp.einsum("hqf,hlf->hql", q[..., :c["nope"]], k_nope)
         + jnp.einsum("hqf,lf->hql", q[..., c["nope"]:], latents[:, c["rank"]:])) * scale
    s = jnp.where(jnp.arange(latents.shape[0])[None, :] <= last[:, None], s, -1e30)
    return jnp.einsum("hql,hlf->hqf", jax.nn.softmax(s, -1), v)


def _row(pool, table_row):
    """A slot's latents, contiguous: (blocks * block_size, width)."""
    return jnp.swapaxes(pool[table_row, 0], -1, -2).reshape(-1, pool.shape[2])


@pytest.mark.parametrize("body", attention.PAGED_BODIES)
def test_latent_decode_attention_matches_dense(body):
    c = _latent_case()
    idx = jnp.asarray([0, 130, 383, 40], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    q = jax.random.normal(c["keys"][2], (c["slots"], c["heads"], c["nope"] + c["pe"]))
    new = jax.random.normal(c["keys"][3], (c["slots"], 1, c["width"]))
    if body == "paged_xla":
        out, pool, none = attention.paged_decode_attention(
            q, new, None, c["pool"], None, c["table"], idx, active, body, scale=0.11,
            kv_b=c["kv_b"])
        assert none is None
    else:  # the kernel itself, absorbed by hand, in interpret mode
        absorbed = attention._absorb(q[:, :, None], c["kv_b"], c["width"])[:, :, 0]
        out, pool = pallas_latent_decode_attention(
            absorbed, new, c["pool"], c["table"], idx, active, c["rank"], 0.11, blocks=2,
            interpret=True)
        out = attention._expand_values(out[:, :, None], c["kv_b"], c["v_head"])[:, :, 0]
    for s in range(c["slots"]):
        row = _row(pool, c["table"][s])
        if not bool(active[s]):
            np.testing.assert_array_equal(np.asarray(row), np.asarray(_row(c["pool"], c["table"][s])))
            continue
        np.testing.assert_allclose(np.asarray(row[int(idx[s])]), np.asarray(new[s, 0]), atol=1e-6)
        want = _dense_latent_attention(c, q[s][:, None], row, idx[s][None], 0.11)[:, 0]
        np.testing.assert_allclose(np.asarray(out[s]), np.asarray(want), atol=2e-5, rtol=2e-5)


# A chunk of one query tile and one column step (the three cases PR 34
# wrote), then one of four tiles of 128 queries over steps of 512 columns:
# ``start`` at 0, unaligned, a whole number of steps and past a step's
# middle, ``valid`` around a tile's edge and the whole chunk.
_CHUNK_CASES = [(128, s, None) for s in (0, 128, 37)] + [
    (512, s, v) for s in (0, 37, 512, 700) for v in (1, 127, 128, 129, 512)]


@pytest.mark.parametrize("body", attention.PAGED_BODIES)
@pytest.mark.parametrize("chunk,start,valid", _CHUNK_CASES)
def test_latent_chunk_attention_matches_dense(body, chunk, start, valid):
    """Rows below ``valid`` are dense attention's; the kernel's rows at or
    past it are finite, and zeros in the tiles it never visited."""
    c = _latent_case(bps=3 if chunk == 128 else 10, slots=4 if chunk == 128 else 2)
    q = jax.random.normal(c["keys"][2], (c["heads"], chunk, c["nope"] + c["pe"]))
    new = jax.random.normal(c["keys"][3], (1, chunk, c["width"]))
    told = None if valid is None else jnp.int32(valid)
    out, pool, _ = attention.paged_chunk_attention(
        q, new, None, c["pool"], None, c["table"][1], jnp.int32(start), "paged_xla",
        start % c["bs"] == 0, scale=0.11, kv_b=c["kv_b"], valid=told)
    if body == "paged_pallas":
        # the interpreter's callbacks and the host's own ops share one
        # client: nothing else is dispatched while the kernel runs
        out = jax.block_until_ready(pallas_latent_chunk_attention(
            q, c["kv_b"], pool, c["table"][1], jnp.int32(start), 0.11, valid=told,
            tq=128, interpret=True))
    row = _row(pool, c["table"][1])
    np.testing.assert_allclose(np.asarray(row[start:start + chunk]), np.asarray(new[0]), atol=1e-6)
    want = _dense_latent_attention(c, q, row, start + jnp.arange(chunk), 0.11)
    live = chunk if valid is None else valid
    np.testing.assert_allclose(np.asarray(out[:, :live]), np.asarray(want[:, :live]),
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()
    if body == "paged_pallas":
        assert not np.asarray(out[:, -(-live // 128) * 128:]).any()


def test_the_tiles_a_job_visits_follow_from_its_prompt_lengths():
    """``latent_chunk_tiles_visited`` over the long-document cell's job: the
    traffic file's 16 prompt lengths, chunks of 2,048, steps of 512 columns.
    Against the one 2,048-row tile a step up to the chunk's last column,
    tiles of 512 rows visit 0.789 of the (query, column) pairs and tiles of
    256 rows 0.771 (ISSUE 35's closed form), and each tile size's count is
    what a plain walk over every (tile, step) pair finds live."""
    import json

    with open(os.path.join(BENCH, "traffic", "doc-batch-16k.json")) as f:
        traffic = json.load(f)
    prompts = [p for p, _ in _bench_module("lib", "traffic").length_pairs(traffic)]
    assert prompts == [4480 + 768 * i for i in range(16)]
    chunk, columns = 2048, 512
    assert attention._latent_chunk_tiles(128, 128, chunk) == (
        4, columns // 128, attention._LATENT_QUERY_TILE)
    spans = [(start, min(chunk, p - start)) for p in prompts for start in range(0, p, chunk)]
    assert len(spans) == 88
    live_pairs = sum(v * s + v * (v + 1) // 2 for s, v in spans)
    share = {}
    for tile in (128, 256, 512, 1024, 2048):
        visited = dense = 0
        for start, valid in spans:
            got, whole = attention.latent_chunk_tiles_visited(start, valid, chunk, tile, columns)
            walked = sum(
                1 for j in range(-(-(start + chunk) // columns)) for t in range(chunk // tile)
                if t * tile < valid and j * columns <= start + min((t + 1) * tile, valid) - 1)
            assert got == walked <= whole
            visited, dense = visited + got, dense + whole
        assert dense * tile * columns == sum(
            chunk * columns * ((s + chunk - 1) // columns + 1) for s, _ in spans)
        share[tile] = visited / dense
        assert visited * tile * columns >= live_pairs
    assert round(share[512], 3) == 0.789 and round(share[256], 3) == 0.771
    assert sorted(share.values()) == [share[t] for t in (128, 256, 512, 1024, 2048)]
    dense_pairs = sum(chunk * columns * ((s + chunk - 1) // columns + 1) for s, _ in spans)
    assert round(dense_pairs / live_pairs, 3) == 1.384
    assert round(share[512] * dense_pairs / live_pairs, 3) == 1.092


def test_the_chunk_kernels_tiles_are_counted_on_stats_and_step_events(served):
    """``stats()`` and every ``step`` event carry the latent chunk kernel's
    query tile (None on the CPU, where the chunk program runs XLA's body, and
    nothing is counted); given the kernel's geometry, the metrics count each
    launched chunk's tiles from its ``start`` and ``valid``."""
    from elephas_tpu.serving.metrics import ServingMetrics

    events = []

    class Sink:
        def log(self, step, **fields):
            events.append(fields)

    eng = _engine(served[2], sink=Sink())
    _serve(eng, _prompts([19, 8]), new=3)
    stats = eng.stats()
    assert stats["prefill_query_tile"] is None and eng.prefill_query_tile is None
    assert stats["prefill_tiles_visited"] == stats["prefill_tiles_dense"] == 0
    steps = [e for e in events if e.get("event") == "step"]
    assert steps and all(e["prefill_query_tile"] is None for e in steps)

    metrics = ServingMetrics(sink=Sink())
    metrics.prefill_query_tile, metrics.prefill_step_columns = 512, 512
    metrics.prefill_chunk = 2048
    # a document of 4,480 tokens: two whole chunks and a tail of 384
    metrics.record_step(0, 1, 0, 0.01, prefill_chunks=2, prefill_spans=[(0, 2048), (2048, 2048)])
    metrics.record_step(0, 1, 0, 0.01, prefill_chunks=1, prefill_spans=[(4096, 384)])
    assert events[-1]["prefill_query_tile"] == 512
    # steps x tiles: 4+3+2+1, then 4 steps of 4 tiles and 4+3+2+1, then 9 steps of 1
    assert metrics.summary()["prefill_tiles_visited"] == 10 + 16 + 10 + 9
    assert metrics.summary()["prefill_tiles_dense"] == 4 * (4 + 8 + 12)
    metrics.reset()
    assert metrics.summary()["prefill_tiles_visited"] == 0
    assert metrics.prefill_query_tile == 512  # kept, as the bodies' names


def test_the_pool_holds_the_latent_and_nothing_a_head(served):
    _, _, compiled = served
    eng = _engine(compiled)
    width = SMALL["kv_lora_rank"] + SMALL["qk_rope_head_dim"]
    leaves = leaves_of_kind(eng.pool.cache, KV)
    assert len(leaves) == SMALL["num_hidden_layers"]  # one a layer: no value leaf
    assert all(leaf.shape == (eng.pool.num_blocks, 1, width, 8) for _, leaf in leaves)
    assert eng.stats()["kv_bytes_per_token"] == SMALL["num_hidden_layers"] * width * 4
    assert eng._kv_layout()[1] == width
    # at the published sizes: 576 values a token a layer, dense on the device
    assert attention.latent_leaf_shape(10, 128, 576) == (10, 1, 576, 128)
    assert attention._latent_fits((10, 1, 576, 128), jnp.bfloat16, 576, 128, blocks=11)
    assert not attention._latent_fits((10, 1, 576, 64), jnp.bfloat16, 576, 128)


# -- (h) what rests on "a block is all there is to a prefix" ------------------


def test_prefix_adoption_over_latent_blocks(served):
    _, _, compiled = served
    shared = _prompts([16], seed=8)[0]  # two whole blocks of 8
    prompts = [shared + [1, 2, 3], shared + [4, 5], shared + [1, 2, 3]]
    alone = [_serve(_engine(compiled), [p])[0].tokens for p in prompts]
    eng = _engine(compiled)
    assert eng.pool.prefix is not None
    got = [_serve(eng, [p])[0].tokens for p in prompts]
    assert got == alone
    stats = eng.stats()
    assert stats["prefix_hits"] == 2 and stats["prefix_tokens_saved"] == 32
    eng.pool.assert_block_invariants()


def test_fork_and_handoff_over_latent_blocks(served):
    _, _, compiled = served
    eng, other = _engine(compiled), _engine(compiled)
    prompt = _prompts([19], seed=9)[0]
    _serve(eng, [prompt], new=2)
    pool = eng.pool
    parent = pool.acquire()
    pool.ensure_cols(parent, 16)
    block = int(pool.table.rows[parent, 0])
    pool.swap(jax.tree_util.tree_map(
        lambda leaf: leaf.at[block].set(2.5) if leaf.ndim == 4 else leaf, pool.cache))
    child = pool.fork_slot(parent)
    assert int(pool.table.rows[child, 0]) == block and pool._ref[block] == 2
    fresh = pool.ensure_writable(child, 0)
    for _, leaf in leaves_of_kind(pool.cache, KV):
        np.testing.assert_array_equal(np.asarray(leaf[fresh]), np.asarray(leaf[block]))
    export = pool.export_blocks(parent)
    assert len(export["arrays"]) == SMALL["num_hidden_layers"]
    slot = other.pool.acquire()
    other.pool.import_blocks(slot, list(range(16)), export["arrays"],
                             leaf_names=export["leaves"])
    landed = int(other.pool.table.rows[slot, 0])
    for (_, got), (_, sent) in zip(leaves_of_kind(other.pool.cache, KV),
                                   leaves_of_kind(pool.cache, KV)):
        np.testing.assert_array_equal(np.asarray(got[landed]), np.asarray(sent[block]))
    pool.assert_block_invariants()


def test_speculation_and_a_mesh_are_refused_by_mechanism(served):
    _, _, compiled = served
    with pytest.raises(NotImplementedError, match="latent cache"):
        _engine(compiled, speculative=True)
    from elephas_tpu.parallel.mesh import build_mesh
    from elephas_tpu.serving import shard_serving

    with pytest.raises(NotImplementedError, match="routed expert layer"):
        shard_serving(_engine(compiled), build_mesh(num_data=2, num_model=4))
    # and the cache has no contiguous form to fall back to
    module = served[0].clone(decode=True)
    cache = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["cache"]
    with pytest.raises(NotImplementedError, match="block table"):
        module.apply({"params": served[1], "cache": cache}, jnp.zeros((1, 1), jnp.int32),
                     mutable=["cache"])


# -- counters ride the step's one fetch ---------------------------------------


def test_routing_counters_ride_the_lanes_fetch(served, monkeypatch):
    from elephas_tpu.serving import host_sync

    _, _, compiled = served

    class Sink:
        rows = []

        def log(self, step, **fields):
            self.rows.append(fields)

    fetches = []
    fetch = host_sync.fetch
    monkeypatch.setattr(host_sync, "fetch", lambda v: fetches.append(v) or fetch(v))
    eng = _engine(compiled, sink=Sink())
    _serve(eng, _prompts([13, 19, 9]))
    steps = [r for r in Sink.rows if r.get("event") == "step" and "moe_assignments" in r]
    assert steps
    routed_layers = SMALL["num_hidden_layers"] - SMALL["first_k_dense_replace"]
    for s in steps:
        lanes = len(s["lane_lengths"]) or s["moe_assignments"] / (2 * routed_layers)
        assert s["moe_assignments"] == lanes * 2 * routed_layers
        assert s["moe_experts_held"] == 4 * routed_layers
        assert s["moe_assignments_held"] <= s["moe_assignments"]
        assert s["moe_rows_combined"] == s["moe_assignments"]  # a step's rows lie packed
        assert s["moe_experts_touched"] <= min(s["moe_experts_held"], s["moe_assignments_held"])
        assert s["moe_load_max"] * 4 >= s["moe_load_mean"] * 4 >= s["moe_assignments_held"] / routed_layers
    # every fetch that brought counters brought tokens with them: none of their own
    assert all(isinstance(v, tuple) and len(v) == 2 for v in fetches
               if not hasattr(v, "shape"))


# -- (i) the count --------------------------------------------------------------


def test_param_count_published_cut_and_drawn(family):
    published = {k: PUBLISHED[k] for k in ("num_hidden_layers", "n_routed_experts", "vocab_size")}
    cut = family.shape(dict(PUBLISHED, num_hidden_layers=5, n_routed_experts=40,
                            vocab_size=25600))
    assert family.param_count(cut, published) == 235_741_434_880
    assert family.param_count(cut) == 5_163_975_680
    assert family.kv_bytes_per_token(cut) == 5 * 576 * 2 == 5_760
    small = family.shape(SMALL)
    drawn = family.params(SEED, small, jnp.float32)
    assert family.param_count(small) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(drawn))
    # a decode step's routed bytes are the touched experts', and none without the counter
    base = family.decode_cost(cut, [4096] * 16)[1]
    assert family.decode_cost(cut, [4096] * 16, touched=18 * 4)[1] - base == \
        18 * 4 * 3 * 5120 * 1536 * 2
    assert family.chunk_cost(cut, 0, 2048)[0] > family.mla_chunk_attention_cost(cut, 0, 2048)[0] > 0


# -- (j) against the published implementation ---------------------------------


def test_reference_agrees_with_the_transformers_port(family, reference):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV2ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV2ForCausalLM")
    sizes = dict(SMALL, n_routed_experts=8, rope_scaling=None)  # the port's scale is plain
    cfg = family.shape(sizes)
    config = transformers.DeepseekV2Config(
        vocab_size=211, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        n_shared_experts=2, n_routed_experts=8, routed_scaling_factor=16.0, kv_lora_rank=8,
        q_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=4,
        topk_group=2, num_experts_per_tok=2, first_k_dense_replace=1, norm_topk_prob=False,
        topk_method="group_limited_greedy", rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
        max_position_embeddings=4096, attn_implementation="eager")
    model = transformers.DeepseekV2ForCausalLM(config).eval().to(torch.float32)

    def t(a):  # (in, out) -> torch's (out, in)
        return torch.tensor(np.asarray(a, np.float32).reshape(a.shape[0], -1).T.copy())

    top = family.top_at(SEED, cfg, jnp.float32)
    state = {"model.embed_tokens.weight": torch.tensor(np.asarray(top["tok_embed"]["embedding"])),
             "model.norm.weight": torch.tensor(np.asarray(top["final_norm"]["scale"])),
             "lm_head.weight": t(top["lm_head"]["kernel"])}
    for i in range(3):
        w, pre = family.block_at(SEED, i, cfg, jnp.float32), f"model.layers.{i}."
        a = w["attention"]
        state.update({
            pre + "input_layernorm.weight": torch.tensor(np.asarray(w["attn_norm"]["scale"])),
            pre + "post_attention_layernorm.weight": torch.tensor(np.asarray(w["ffn_norm"]["scale"])),
            pre + "self_attn.q_a_proj.weight": t(a["q_a"]["kernel"]),
            pre + "self_attn.q_a_layernorm.weight": torch.tensor(np.asarray(a["q_a_norm"]["scale"])),
            pre + "self_attn.q_b_proj.weight": t(a["q_b"]["kernel"]),
            pre + "self_attn.kv_a_proj_with_mqa.weight": t(a["kv_a"]["kernel"]),
            pre + "self_attn.kv_a_layernorm.weight": torch.tensor(np.asarray(a["kv_a_norm"]["scale"])),
            pre + "self_attn.kv_b_proj.weight": t(a["kv_b"]),
            pre + "self_attn.o_proj.weight": torch.tensor(
                np.asarray(a["out"]["kernel"]).reshape(-1, 64).T.copy())})
        gated = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
        if "experts" not in w:
            state.update({pre + f"mlp.{theirs}.weight": t(w[ours]["kernel"])
                          for ours, theirs in gated.items()})
            continue
        state[pre + "mlp.gate.weight"] = t(w["experts"]["router"]["kernel"])
        for ours, theirs in gated.items():
            state[pre + f"mlp.shared_experts.{theirs}.weight"] = t(w["shared"][ours]["kernel"])
            for e in range(8):
                state[pre + f"mlp.experts.{e}.{theirs}.weight"] = t(w["experts"][ours][e])
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not [m for m in missing if "rotary" not in m]
    tokens = np.asarray(_prompts([17, 17], seed=11))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens)).logits.numpy()
    ours = _reference_logits(reference, family, tokens, np.tile(np.arange(17), (2, 1)), sizes)
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=1e-4, rtol=1e-4)


def test_weight_passes_are_the_kernels_work_items(served):
    """``moe_weight_passes``: a group within a row tile is one pass over its
    expert's matrices, a larger one as many as it has tiles; on the ``step``
    event and the chunk's span it is the layers' sum."""
    from elephas_tpu.obs import Tracer

    # (a) the layer alone: every token the same, so two experts hold 300 rows each
    layer = latent_moe.RoutedExperts(
        n_routed_experts=8, experts_held=(0, 8), moe_d_ff=32, top_k=2, n_group=4,
        topk_group=2, routed_scaling_factor=1.0)
    one = jax.random.normal(jax.random.PRNGKey(0), (1, 64))
    for tokens, passes in ((300, 4), (256, 2), (128, 2), (129, 2)):
        y = jnp.tile(one, (tokens, 1))
        params = layer.init(jax.random.PRNGKey(1), y)["params"]
        sown = layer.apply({"params": params}, y, mutable=["counters"])[1]["counters"]
        assert float(sown["moe_experts_touched"][0]) == 2
        assert float(sown["moe_weight_passes"][0]) == passes, tokens
    sizes = jnp.asarray([0, 1, 256, 257, 700], jnp.int32)
    assert int(routed_experts.weight_passes(sizes, 4096)) == 0 + 1 + 1 + 2 + 3
    assert int(routed_experts.weight_passes(sizes[:3], 256)) == 2  # one tile: the touched
    group, tile, count = routed_experts._work_items(sizes, 4096)
    assert int(count) == 7
    assert np.asarray(group)[:7].tolist() == [1, 2, 3, 3, 4, 4, 4]
    assert np.asarray(tile)[:7].tolist() == [0, 0, 0, 1, 0, 1, 2]

    # (b) through the engine, chunks and steps of a tile at most: the touched experts
    _, _, compiled = served

    class Sink:
        rows = []

        def log(self, step, **fields):
            self.rows.append(fields)

    tracer = Tracer(annotate_device=False)
    eng = _engine(compiled, sink=Sink(), tracer=tracer)
    _serve(eng, _prompts([13, 19, 9]))
    steps = [r for r in Sink.rows if r.get("event") == "step" and "moe_weight_passes" in r]
    chunks = [e.args for e in tracer.events() if e.name == "step/prefill_chunk"]
    assert steps and len(chunks) == 2 + 3 + 2
    for counted in steps + chunks:
        assert counted["moe_weight_passes"] == counted["moe_experts_touched"] > 0


def test_a_chunks_span_carries_its_routing_counters(served):
    from elephas_tpu.obs import Tracer

    _, _, compiled = served
    tracer = Tracer(annotate_device=False)
    eng = _engine(compiled, tracer=tracer)
    prompts = _prompts([13, 19])
    _serve(eng, prompts)
    chunks = [e for e in tracer.events() if e.name == "step/prefill_chunk"]
    assert sorted((e.args["start"], e.args["valid"]) for e in chunks) == sorted(
        (start, min(8, n - start)) for n in (13, 19) for start in range(0, n, 8))
    routed_layers = SMALL["num_hidden_layers"] - SMALL["first_k_dense_replace"]
    for e in chunks:  # padding is routed nowhere: a ragged chunk counts its own tokens
        assert e.args["moe_assignments"] == e.args["valid"] * 2 * routed_layers
        assert 0 <= e.args["moe_assignments_held"] <= e.args["moe_assignments"]
        # the CPU's body: a row combined for every assignment that counts
        assert e.args["moe_rows_combined"] == e.args["moe_assignments"]
        assert e.begin_s <= e.end_s


@pytest.mark.parametrize("tokens", [200, 100])
def test_the_rows_combined_are_the_rows_the_way_back_reads(tokens, monkeypatch):
    """``moe_rows_combined`` with the kernels steered on and interpreted:
    over more than one row tile (200 tokens of 2, a chunk's case) the way
    back reads the held rows alone, ``moe_assignments_held``; over one (100,
    a decode step's) a row for every assignment, ``moe_assignments``. The
    sums are the XLA body's."""
    import functools

    monkeypatch.setattr(routed_experts, "_on_tpu", lambda: True)
    for kernel in ("pallas_grouped_matmul", "pallas_combine_rows"):
        monkeypatch.setattr(routed_experts, kernel, functools.partial(
            getattr(routed_experts, kernel), interpret=True))
    layer = latent_moe.RoutedExperts(
        n_routed_experts=8, experts_held=(0, 4), moe_d_ff=128, top_k=2, n_group=4,
        topk_group=2, routed_scaling_factor=1.0)
    y = jax.random.normal(jax.random.PRNGKey(0), (tokens, 128))
    params = layer.init(jax.random.PRNGKey(1), y)["params"]
    assert routed_experts.routed_body(tokens, 2, 128, 128, y.dtype) == "grouped_pallas"
    apply = jax.jit(functools.partial(layer.apply, mutable=["counters"]))
    out, sown = jax.block_until_ready(apply({"params": params}, y))
    count = {k: float(v[0]) for k, v in sown["counters"].items()}
    assert 0 < count["moe_assignments_held"] < count["moe_assignments"] == 2 * tokens
    assert count["moe_rows_combined"] == count[
        "moe_assignments_held" if 2 * tokens > routed_experts._ROW_TILE else "moe_assignments"]
    monkeypatch.setattr(routed_experts, "_on_tpu", lambda: False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.jit(layer.apply)(
        {"params": params}, y)), atol=1e-5, rtol=1e-5)
