"""Granite 4.0-H's block (`models/granite_hybrid.py`) through the serving
engine: Mamba-2 mixers whose matrix state decays by a step each token sets,
carried chunk to chunk, beside grouped attention without positions, with a
routed layer told which experts it holds and a shared expert on every layer.
The configuration's `rehearsal` sizes (6 layers, 5 mixers and the attention
layer, hidden 64, 8 mixer heads of 16 with a state of 16, 4 query heads on 2
K/V heads, 4 of 8 experts held, top-2), seeded weights from the benchmark's
family file, compared with the benchmark's plain reference
(`benchmark/references/granite_hybrid.py`: float32, `highest` precision, the
mixers as their token recurrence, nothing of the program), with plain numpy,
or with the published implementation in `transformers`."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_moe import _bench_module

from elephas_tpu import InferenceEngine, compile_model
from elephas_tpu.models import granite_hybrid, transformer
from elephas_tpu.models.decode_cache import STATE, leaves_of_kind
from elephas_tpu.ops import ssd

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SEED = 2147483659
with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as _f:
    CONFIG = json.load(_f)
SMALL = {**{k: v for k, v in CONFIG.items() if k != "rehearsal"},
         **{k: v for k, v in CONFIG["rehearsal"].items() if k != "serving"}}
LOGITS_TOL = 2e-5  # float32 throughout: the chunk and step forms against the recurrence


@pytest.fixture(scope="module")
def family():
    return _bench_module("models", "granite_hybrid")


@pytest.fixture(scope="module")
def reference():
    return _bench_module("references", "granite_hybrid")


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


def _reference_logits(reference, family, cfg, tokens, rows):
    return np.asarray(reference.logits_at(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
        family.top_at(SEED, cfg, jnp.float32),
        lambda layer: family.block_at(SEED, layer, cfg, jnp.float32), family.layers(cfg)))


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


def _served_gap(reference, family, cfg, compiled, captured, prompt, new=8):
    """Widest gap between the logits one request was served from (its last
    chunk's row, then each decode step's) and the reference's full forward."""
    eng = _engine(compiled)
    captured.clear()
    res = eng.result(eng.submit(prompt, max_new_tokens=new, stop_token=None), timeout_s=300)
    chunks = -(-len(prompt) // eng.prefill_chunk)
    logits = np.stack([captured[chunks - 1][0]] + [r[0] for r in captured[chunks:]][:new - 1])
    seq = prompt + res.tokens[:-1]
    want = _reference_logits(reference, family, cfg, [seq + [0] * (80 - len(seq))],
                             [np.arange(len(prompt) - 1, len(seq))])[0]
    return np.abs(logits - want).max(), want


# -- (a), (b) the SSD forms against the recurrence --------------------------------


def _recurrence(x, delta, A, B, C, S0):
    """y_t = S_t C_t, S_t = exp(delta_t a) S_t-1 + (delta_t x_t) B_t^T, float64."""
    S, ys = np.asarray(S0, np.float64), []
    for t in range(x.shape[0]):
        S = np.exp(delta[t] * A)[:, None, None] * S + \
            (delta[t][:, None] * x[t])[:, :, None] * B[t][None, None, :]
        ys.append(np.einsum("hpn,n->hp", S, C[t]))
    return np.stack(ys), S


def _ssd_inputs(rng, T, H=8, P=64, N=128):
    x = rng.normal(size=(T, H, P)).astype(np.float32) * 0.5
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(T, H))).astype(np.float32)
    A = -np.arange(1, H + 1, dtype=np.float32) * 0.5
    B, C = (rng.normal(size=(T, N)).astype(np.float32) * 0.3 for _ in range(2))
    return x, delta, A, B, C


@pytest.mark.parametrize("body", ["ssd_xla", "ssd_pallas"])
def test_the_chunk_form_carries_the_state_and_keeps_padding_out(body):
    """Two chunks of 512 (two sub-chunks of 256 each), the second ragged: 300
    real tokens, the rest padding that holds anything. The state goes in, on
    and out; past `valid` nothing moves it."""
    rng = np.random.default_rng(0)
    T, P = 512, 64
    x, delta, A, B, C = _ssd_inputs(rng, 2 * T)
    S0 = rng.normal(size=(8, P, 128)).astype(np.float32) * 0.1

    def chunk(xs, ds, bs, cs, state, valid):
        if body == "ssd_pallas":
            xdt, a = ssd._inputs(jnp.asarray(xs), jnp.asarray(ds), jnp.asarray(A), valid)
            y, s = ssd.pallas_ssd_chunk(xdt.reshape(T, -1), ssd._running_sums(a, 256),
                                        jnp.asarray(bs), jnp.asarray(cs), state,
                                        jnp.int32(valid), P, interpret=True)
            y, s = jax.block_until_ready((y, s))
            return np.asarray(y).reshape(T, 8, P), np.asarray(s)
        y, s = ssd.ssd_chunk(xs, ds, A, bs, cs, state, valid)
        return np.asarray(y), np.asarray(s)

    first = [v[:T] for v in (x, delta, B, C)]
    second = [v[T:] for v in (x, delta, B, C)]
    y1, s1 = chunk(*first, ssd.pack_state(jnp.asarray(S0)), T)
    y2, s2 = chunk(*second, s1, 300)
    want, state = _recurrence(x[:T + 300], delta[:T + 300], A, B[:T + 300], C[:T + 300], S0)
    got = np.concatenate([y1, y2[:300]])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    held = np.asarray(ssd.unpack_state(jnp.asarray(s2), P))
    assert np.abs(held - state).max() < 2e-5 * np.abs(state).max()
    # other padding, a hundred times larger: the same state and outputs, bit for bit
    noisy = [np.concatenate([v[:300], 100 * rng.normal(size=v[300:].shape).astype(np.float32)
                             + (1.0 if i == 1 else 0.0)]) for i, v in enumerate(second)]
    y3, s3 = chunk(*noisy, s1, 300)
    assert np.array_equal(s3, s2) and np.array_equal(y3[:300], y2[:300])


@pytest.mark.parametrize("body", ["ssd_xla", "ssd_pallas"])
@pytest.mark.parametrize("active", [[True, False, True, False], [False, False, False, False],
                                    [False, True, True, True]])
def test_the_step_form_steps_the_active_lanes_alone(body, active):
    rng = np.random.default_rng(1)
    S, H, P, N = 4, 8, 64, 128
    x, delta, A, B, C = _ssd_inputs(rng, S)
    before = rng.normal(size=(S, H, P, N)).astype(np.float32)
    packed = ssd.pack_state(jnp.asarray(before))
    mask = jnp.asarray(active)
    if body == "ssd_pallas":
        xdt = (x * delta[..., None]).reshape(S, H * P)
        decay = np.repeat(np.exp(delta * A), P, axis=1)
        y, after = jax.block_until_ready(ssd.pallas_ssd_step(
            jnp.asarray(xdt), jnp.asarray(decay), jnp.asarray(B), jnp.asarray(C), packed + 0,
            mask, P, interpret=True))
        y = np.asarray(y).reshape(S, H, P)
    else:
        y, after = ssd.ssd_step(x, delta, A, B, C, packed, mask)
    after = np.asarray(after)
    for lane, on in enumerate(active):
        if on:
            want_y, want_s = _recurrence(x[lane:lane + 1], delta[lane:lane + 1], A,
                                         B[lane:lane + 1], C[lane:lane + 1], before[lane])
            assert np.abs(np.asarray(y)[lane] - want_y[0]).max() < 1e-4 * np.abs(want_y).max()
            got = np.asarray(ssd.unpack_state(jnp.asarray(after[lane]), P))
            assert np.abs(got - want_s).max() < 1e-5 * np.abs(want_s).max()
        else:  # an idle lane's row, bit for bit
            assert np.array_equal(after[lane], np.asarray(packed)[lane])


# -- (c) the model and the serving path against the reference -------------------


def test_full_forward_matches_the_plain_reference(served, family, reference):
    module, params, _, cfg = served
    tokens = np.asarray(_prompts([48, 48], seed=3))
    got = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    want = _reference_logits(reference, family, cfg, tokens, np.tile(np.arange(48), (2, 1)))
    assert np.abs(got - want).max() < LOGITS_TOL
    assert want.std() > 0.3  # logits that spread: the embedding is drawn at its scale
    # the layers, not the input token, decide the next one for most tokens (at
    # this width its own logit still leads a third of the time; at 4,096, 64
    # times the logits' spread over sqrt(d), far less often)
    assert (want.argmax(-1) != tokens).mean() > 0.5


@pytest.mark.parametrize("length", [40, 37])
def test_chunked_prefill_and_decode_match_the_reference(served, family, reference, captured,
                                                        length):
    """Three chunks of 16, the last ragged, then decode steps: the served
    logits are the reference's full forward's."""
    _, _, compiled, cfg = served
    gap, _ = _served_gap(reference, family, cfg, compiled, captured,
                         _prompts([length], seed=length)[0])
    assert gap < LOGITS_TOL


def _forget_the_state(monkeypatch):
    chunk = ssd.ssd_chunk
    monkeypatch.setattr(ssd, "ssd_chunk", lambda x, d, a, b, c, s0, *r, **kw: chunk(
        x, d, a, b, c, jnp.zeros_like(s0), *r, **kw))


def _forget_the_taps(monkeypatch):
    rows = granite_hybrid._rows
    monkeypatch.setattr(granite_hybrid, "_rows", lambda x, at, size: (
        jnp.zeros_like(rows(x, at, size)) if size > 1 else rows(x, at, size)))


def _round_the_state(monkeypatch):
    step = ssd.ssd_step

    def rounded(*args, **kw):
        y, state = step(*args, **kw)
        # bfloat16's mantissa; a pair of converts may be folded away by XLA
        return y, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)

    monkeypatch.setattr(ssd, "ssd_step", rounded)


@pytest.mark.parametrize("fault,times", [(_forget_the_state, 100), (_forget_the_taps, 100),
                                         (_round_the_state, 5)],
                         ids=["state", "taps", "bfloat16"])
def test_a_program_that_mishandles_the_state_is_seen(served, family, reference, captured,
                                                    monkeypatch, fault, times):
    """A chunk that starts from a zero state, or from a convolution that has
    forgotten the last inputs before it, or a state kept in bfloat16 between
    decode steps, is caught by the comparison of logits."""
    _, _, compiled, cfg = served
    fault(monkeypatch)
    gap, _ = _served_gap(reference, family, cfg, compiled, captured, _prompts([40])[0])
    assert gap > times * LOGITS_TOL


def test_a_reused_slot_gives_what_it_gives_alone(served):
    """The state rows are cleared at release: the second request on the one
    slot is served as it is alone."""
    _, _, compiled, _ = served
    first, second = _prompts([45, 38], seed=5)
    eng = _engine(compiled)
    eng.result(eng.submit(first, max_new_tokens=6, stop_token=None), timeout_s=300)
    for _, leaf in leaves_of_kind(eng.pool.cache, STATE):
        assert not np.asarray(leaf).any()  # released: every state row zero
    after = eng.result(eng.submit(second, max_new_tokens=6, stop_token=None), timeout_s=300)
    alone = _engine(compiled)
    assert after.tokens == alone.result(alone.submit(second, max_new_tokens=6,
                                                     stop_token=None), timeout_s=300).tokens


# -- (d) the expert share --------------------------------------------------------


def test_the_shares_routed_parts_and_the_shared_expert_make_the_uncut_layer(family,
                                                                            reference):
    """Four chips' shares of one layer's experts, each through the program's
    own `RoutedExperts` told which it holds, plus the shared expert counted
    once, are the uncut reference layer."""
    from elephas_tpu.models.latent_moe import GatedFeedForward, RoutedExperts

    cfg = family.shape({**SMALL, "num_local_experts": SMALL["router_experts"]})
    total, share = cfg["router_experts"], cfg["router_experts"] // 4
    w = jax.tree_util.tree_map(np.asarray, family.block_at(SEED, 0, cfg, jnp.float32))
    b = np.random.default_rng(7).normal(size=(12, SMALL["hidden_size"])).astype(np.float32)
    hyper = {**family.hyper(cfg), "first": 0}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.experts(w, jnp.asarray(b), hyper, reference.identity))
        got = GatedFeedForward(SMALL["shared_intermediate_size"]).apply(
            {"params": w["shared"]}, b)
        for first in range(0, total, share):
            e = w["experts"]
            held = {"router": e["router"], **{k: e[k][first:first + share]
                                              for k in ("gate", "up", "down")}}
            layer = RoutedExperts(total, (first, share), SMALL["intermediate_size"],
                                  SMALL["num_experts_per_tok"], 1, 1, 1.0,
                                  norm_topk_prob=True)
            got = got + layer.apply({"params": held}, b, mutable=["counters"])[0]
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * np.abs(want).max()


# -- (e) the reference against the published implementation -----------------------


def test_the_reference_is_the_published_implementation(family, reference):
    """`GraniteMoeHybridForCausalLM` of `transformers`, its naive Mamba-2 path
    on the CPU, loaded with the family's seeded weights: one Mamba layer and
    one attention layer, 11 tokens through chunks of 4."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers import GraniteMoeHybridConfig

    sizes = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                 mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_expand=2,
                 num_local_experts=8, num_experts_per_tok=2, intermediate_size=32,
                 shared_intermediate_size=48, vocab_size=211,
                 attention_multiplier=0.0625)
    published = GraniteMoeHybridConfig(
        **sizes, num_hidden_layers=2, layer_types=["mamba", "attention"],
        mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=4, mamba_conv_bias=True,
        mamba_proj_bias=False, embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0, position_embedding_type="nope", tie_word_embeddings=True,
        rms_norm_eps=1e-5, attention_bias=False, hidden_act="silu")
    cfg = family.shape({**CONFIG, **sizes, "num_hidden_layers": 2, "router_experts": 8,
                        "layer_types": ["mamba", "attention"], "experts_first": 0})
    top = family.top_at(SEED, cfg, jnp.float32)
    blocks = [jax.tree_util.tree_map(np.asarray, family.block_at(SEED, i, cfg, jnp.float32))
              for i in range(2)]

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    state = {"model.embed_tokens.weight": t(top["tok_embed"]["embedding"]),
             "model.norm.weight": t(top["final_norm"]["scale"])}
    for i, w in enumerate(blocks):
        p = f"model.layers.{i}."
        state[p + "input_layernorm.weight"] = t(w["mixer_norm"]["scale"])
        state[p + "post_attention_layernorm.weight"] = t(w["mlp_norm"]["scale"])
        e, s = w["experts"], w["shared"]
        state[p + "block_sparse_moe.router.layer.weight"] = t(e["router"]["kernel"].T)
        state[p + "block_sparse_moe.input_linear.weight"] = t(np.concatenate(
            [e["gate"], e["up"]], -1).transpose(0, 2, 1))
        state[p + "block_sparse_moe.output_linear.weight"] = t(e["down"].transpose(0, 2, 1))
        state[p + "shared_mlp.input_linear.weight"] = t(np.concatenate(
            [s["gate"]["kernel"], s["up"]["kernel"]], -1).T)
        state[p + "shared_mlp.output_linear.weight"] = t(s["down"]["kernel"].T)
        if "mamba" in w:
            m = w["mamba"]
            state[p + "mamba.in_proj.weight"] = t(m["in_proj"]["kernel"].T)
            state[p + "mamba.conv1d.weight"] = t(m["conv_kernel"].T[:, None, :])
            state[p + "mamba.conv1d.bias"] = t(m["conv_bias"])
            for k in ("dt_bias", "A_log", "D"):
                state[p + f"mamba.{k}"] = t(m[k])
            state[p + "mamba.norm.weight"] = t(m["norm"])
            state[p + "mamba.out_proj.weight"] = t(m["out_proj"]["kernel"].T)
        else:
            a, d = w["attention"], sizes["hidden_size"]
            for k in ("q", "k", "v"):
                state[p + f"self_attn.{k}_proj.weight"] = t(a[k]["kernel"].reshape(d, -1).T)
            state[p + "self_attn.o_proj.weight"] = t(a["out"]["kernel"].reshape(-1, d).T)
    model = hf.GraniteMoeHybridForCausalLM(published).eval()
    model.load_state_dict(state, strict=False)
    model.tie_weights()
    tokens = np.random.default_rng(11).integers(0, 211, (1, 11))
    with torch.no_grad():
        want = model(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(reference.logits_at(jnp.asarray(tokens), jnp.arange(11)[None], top,
                                         lambda i: blocks[i], 2))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


# -- (f) the counters, the pool, the refusals --------------------------------------


def test_the_counters_ride_the_lanes_fetch_and_the_chunks_span(served, family):
    from elephas_tpu.obs import Tracer

    rows = []

    class Sink:
        def log(self, step, **fields):
            rows.append(fields)

    tracer = Tracer(annotate_device=False)
    eng = _engine(served[2], max_slots=2, sink=Sink(), tracer=tracer)
    prompts = _prompts([40, 21, 33])
    for i in [eng.submit(p, max_new_tokens=6, stop_token=None) for p in prompts]:
        eng.result(i, timeout_s=300)
    cfg = served[3]
    row = family.ssd_state_bytes(cfg)  # a slot's state in one mixer
    mixers = sum(t == "mamba" for t in cfg["layer_types"])
    steps = [r for r in rows if r.get("event") == "step"]
    for before, s in zip(steps, steps[1:]):
        # a step's event carries the counters of the decode it harvested: the
        # one the step before launched on its lanes
        if "ssd_state_bytes" in s and before["lane_lengths"]:
            assert s["ssd_state_bytes"] == 2 * row * mixers * len(before["lane_lengths"])
            assert s["ssd_subchunks"] == 0
    assert sum(s.get("ssd_state_bytes", 0) > 0 for s in steps) > 5
    chunks = [e for e in tracer.events() if e.name == "step/prefill_chunk"]
    assert len(chunks) == sum(-(-len(p) // 16) for p in prompts)
    for e in chunks:  # one slot's state read and written, one sub-chunk of 16
        assert e.args["ssd_state_bytes"] == 2 * row * mixers
        assert e.args["ssd_subchunks"] == mixers
        assert 0 < e.args["moe_assignments"] == 2 * e.args["valid"] * len(cfg["layer_types"])
    stats = eng.stats()
    assert eng.stateful and stats["prefix_cache"] == "off: per-slot state"
    assert stats["state_bytes"] == 2 * family.state_bytes_per_slot(cfg, 4)


def test_what_such_a_pool_cannot_do_is_refused_by_mechanism(served):
    _, _, compiled, _ = served
    with pytest.raises(NotImplementedError, match="per-slot state"):
        _engine(compiled, speculative=True)
    from elephas_tpu.parallel.mesh import build_mesh
    from elephas_tpu.serving import shard_serving

    with pytest.raises(NotImplementedError, match="per-slot state"):
        shard_serving(_engine(compiled), build_mesh(num_data=2, num_model=4))
    eng = _engine(compiled, max_slots=2)
    # a resident prefix says nothing of the states: nothing is adopted
    assert eng.pool.prefix is None
    slot = eng.pool.acquire()
    assert eng.pool.admit_prefix(slot, _prompts([40])[0]) == 0
    for call in (lambda: eng.pool.fork_slot(slot), lambda: eng.pool.export_blocks(slot),
                 lambda: eng.pool.import_blocks(slot, [1], [])):
        with pytest.raises(NotImplementedError, match="per-slot state"):
            call()


def test_the_count_of_parameters_is_the_cut_and_the_published_model(family):
    cfg = family.shape(CONFIG)
    whole = family.param_count(cfg, {**CONFIG["published"], "layer_types": CONFIG["layer_types"]})
    assert family.param_count(cfg) == 2_955_758_208  # the cut's count
    assert round(whole / 1e9, 2) == 32.21
    params = jax.eval_shape(lambda: family.params(0, cfg, jnp.bfloat16))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == family.param_count(cfg)
