"""Disaggregated prefill/decode serving: the KV-block handoff plane.

Four contracts, bottom-up:

- **Wire**: ``encode_kv_blocks``/``decode_kv_blocks`` round-trip block
  arrays bit-identically for every KV dtype the pool can hold, and
  every structural corruption raises ``WireFormatError`` — never a
  garbage decode.
- **Pool**: ``export_blocks`` → encode → decode → ``import_blocks`` is
  bit-identical end to end; refcounts conserve under seeded handoff
  churn; the export closes the block-seconds billing window on the
  prefill pool and the import's ``set_slot_owner`` opens the decode
  pool's, so cross-tier block-seconds sum to the occupancy a
  monolithic engine would have billed.
- **Engine**: prefill-tier (``submit_prefill``/``handoff``) plus
  decode-tier (``submit_handoff``) serving is token-identical to the
  monolithic engine — greedy AND sampled — and a corrupt frame rejects
  without wedging the decode slot.
- **Router**: a tiered fleet serves the monolithic fleet's exact
  streams; a poisoned handoff degrades to a local re-prefill (the
  ``tier_handoff_fail`` flight) with the request completing anyway;
  QoS throttles/preempts deterministically under a fake clock; the
  new vocabulary (flight kinds, alert rules, ``/tiers`` route) is
  registered.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from elephas_tpu import obs
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.models import get_model
from elephas_tpu.obs import flight as flight_mod
from elephas_tpu.obs.flight import FlightRecorder
from elephas_tpu.obs.tenancy import CostLedger
from elephas_tpu.parameter.wire import (
    WireFormatError,
    decode_kv_blocks,
    encode_kv_blocks,
)
from elephas_tpu.serving import InferenceEngine, ReplicaSet, Router
from elephas_tpu.serving.fleet import AdmissionThrottled, QoSPolicy
from elephas_tpu.serving.handoff import decode_handoff, encode_handoff
from tests.test_serving import FakeClock

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


def _engine(compiled, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_len", 24)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("kv_block_size", 4)
    return InferenceEngine(compiled, **kw)


def _disagg_serve(prefill_eng, decode_eng, prompt, max_new_tokens=6,
                  **kw):
    """One request through the two-engine handoff path; returns the
    decode-tier result."""
    rid = prefill_eng.submit_prefill(prompt, max_new_tokens=max_new_tokens,
                                     **kw)
    data = prefill_eng.handoff(rid, timeout_s=60.0)
    assert isinstance(data, dict), data
    frame = encode_handoff(data).tobytes()
    rid2 = decode_eng.submit_handoff(frame)
    return decode_eng.result(rid2, timeout_s=60.0)


# -- wire codec --------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32"])
def test_kv_codec_roundtrip_bit_identical(dtype):
    """Every KV dtype the pool can hold crosses the wire bit-exactly —
    blocks are state, not numbers; a single flipped mantissa bit would
    silently fork the decode stream."""
    import ml_dtypes

    np_dtype = (np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16"
                else np.dtype(dtype))
    rng = np.random.default_rng(3)
    arrays = [
        rng.standard_normal((2, 4, 4, 8)).astype(np_dtype),
        rng.standard_normal((2, 4, 4, 8)).astype(np_dtype),
    ]
    meta = {"req_id": 7, "first": 12, "tenant": None,
            "export": {"block_size": 4, "blocks": 2}}
    buf = encode_kv_blocks(meta, arrays).tobytes()
    meta2, arrays2 = decode_kv_blocks(buf)
    assert meta2 == meta
    assert len(arrays2) == len(arrays)
    for a, b in zip(arrays, arrays2):
        assert b.dtype == a.dtype and b.shape == a.shape
        assert b.tobytes() == a.tobytes()


def test_kv_codec_rejects_corruption():
    buf = bytearray(encode_kv_blocks(
        {"k": 1}, [np.zeros((1, 2, 2, 2), np.float32)]).tobytes())
    with pytest.raises(WireFormatError, match="magic"):
        decode_kv_blocks(b"XXXX" + bytes(buf[4:]))
    with pytest.raises(WireFormatError):
        decode_kv_blocks(bytes(buf[: len(buf) // 2]))  # truncated payload
    stomped = bytearray(buf)
    stomped[12] ^= 0xFF  # inside the JSON header
    with pytest.raises(WireFormatError):
        decode_kv_blocks(bytes(stomped))


# -- pool: export → wire → import --------------------------------------------


def test_export_wire_import_bit_identical(compiled):
    """The full transport: a prefill engine's exported blocks survive
    encode→decode bit-exactly, and the decode engine that imports them
    emits the monolithic engine's exact stream."""
    prompt = [5, 3, 9, 2, 6, 1]
    mono = _engine(compiled)
    want = mono.result(mono.submit(prompt, max_new_tokens=6),
                       timeout_s=60.0).tokens

    pre, dec = _engine(compiled), _engine(compiled)
    rid = pre.submit_prefill(prompt, max_new_tokens=6)
    data = pre.handoff(rid, timeout_s=60.0)
    frame = encode_handoff(data).tobytes()
    parked = decode_handoff(frame)
    for a, b in zip(data["export"]["arrays"], parked["export"]["arrays"]):
        assert b.tobytes() == a.tobytes()
    rid2 = dec.submit_handoff(frame)
    got = dec.result(rid2, timeout_s=60.0)
    assert got.status == "completed"
    assert list(got.tokens) == list(want)


def test_disagg_token_identity_greedy_and_sampled(compiled):
    """Tiered output is byte-equal to monolithic for greedy AND for
    sampled decoding — position-keyed sampling plus bit-exact KV
    transfer make the handoff invisible to the stream."""
    prompts = [[5, 3, 9], [1, 2, 3, 4, 5, 6, 7], [11, 12, 13, 14, 15]]
    for sample_kw in ({}, {"temperature": 0.8, "top_k": 12, "seed": 7}):
        mono = _engine(compiled, **sample_kw)
        pre = _engine(compiled, **sample_kw)
        dec = _engine(compiled, **sample_kw)
        for prompt in prompts:
            want = mono.result(mono.submit(prompt, max_new_tokens=6),
                               timeout_s=60.0).tokens
            got = _disagg_serve(pre, dec, prompt)
            assert got.status == "completed", sample_kw
            assert list(got.tokens) == list(want), sample_kw


def test_refcount_conservation_under_handoff_churn(compiled):
    """Seeded churn over the handoff path — shared system prefixes (the
    incref import arm) mixed with cold prompts (the upload arm) — must
    leave both pools' refcounts conserved: every block is either free,
    held by a slot row, or held by the prefix cache, never leaked."""
    pre, dec = _engine(compiled), _engine(compiled)
    rng = np.random.default_rng(29)
    sys_prefix = [7, 3, 2, 9]  # one full block at kv_block_size=4
    for round_ in range(12):
        if rng.integers(2) == 0:
            plen = int(rng.integers(1, 5))
            prompt = sys_prefix + rng.integers(1, VOCAB, plen).tolist()
        else:
            plen = int(rng.integers(1, 9))
            prompt = rng.integers(1, VOCAB, plen).tolist()
        res = _disagg_serve(pre, dec, prompt,
                            max_new_tokens=int(rng.integers(2, 7)))
        assert res.status == "completed"
        pre.pool.assert_block_invariants()
        dec.pool.assert_block_invariants()
    assert pre.pool.active_count == 0 and dec.pool.active_count == 0


def test_corrupt_frame_rejects_without_wedging_slot(compiled):
    """A corrupt frame must reject loudly at ``submit_handoff`` and
    leave the decode engine fully serviceable — pool invariants intact,
    the same slot admitting the next valid handoff."""
    pre, dec = _engine(compiled), _engine(compiled)
    prompt = [4, 8, 15, 16, 23, 42]
    rid = pre.submit_prefill(prompt, max_new_tokens=5)
    frame = bytearray(encode_handoff(
        pre.handoff(rid, timeout_s=60.0)).tobytes())
    frame[10] ^= 0xFF  # stomp the JSON header mid-frame
    with pytest.raises(WireFormatError):
        dec.submit_handoff(bytes(frame))
    dec.pool.assert_block_invariants()
    assert dec.pool.active_count == 0
    # The engine (and its slots) still serve both paths.
    oracle = _engine(compiled)
    mono_want = oracle.result(oracle.submit(prompt, max_new_tokens=5),
                              timeout_s=60.0).tokens
    res = _disagg_serve(pre, dec, prompt, max_new_tokens=5)
    assert res.status == "completed"
    assert list(res.tokens) == list(mono_want)
    local = dec.result(dec.submit(prompt, max_new_tokens=5),
                       timeout_s=60.0)
    assert local.status == "completed"


def test_export_transfers_billing_window(compiled):
    """Satellite-6 conservation: block-seconds for one request split
    across tiers must sum to the occupancy a single pool would have
    billed — export closes the prefill-side window (release bills
    nothing more), import's ``set_slot_owner`` opens the decode-side
    one."""
    clock = FakeClock()

    def pool_with_ledger(eng):
        ledger = CostLedger(clock=clock)
        eng.pool.attach_cost_ledger(ledger, clock=clock)
        return ledger

    pre, dec = _engine(compiled), _engine(compiled)
    led_pre, led_dec = pool_with_ledger(pre), pool_with_ledger(dec)

    slot = pre.pool.acquire()
    pre.pool.set_slot_owner(slot, "t0")
    pre.pool.ensure_cols(slot, 8)  # 2 blocks resident
    clock.advance(5.0)
    export = pre.pool.export_blocks(slot)  # bills 5 s x 2 blocks, closes
    clock.advance(7.0)
    pre.pool.release(slot)  # window closed: bills nothing further
    pre_s = led_pre.snapshot()["tenants"]["t0"]["kv_block_seconds"]
    assert pre_s == pytest.approx(10.0)

    slot2 = dec.pool.acquire()
    matched = dec.pool.import_blocks(
        slot2, [5, 3, 9, 2, 6, 1, 4, 8], export["arrays"],
        leaf_names=export["leaves"])
    assert matched == 0  # cold decode pool: nothing resident to match
    dec.pool.set_slot_owner(slot2, "t0")  # opens the decode-side window
    clock.advance(3.0)
    dec.pool.release(slot2)
    dec_s = led_dec.snapshot()["tenants"]["t0"]["kv_block_seconds"]
    assert dec_s == pytest.approx(6.0)
    # 5 s on the prefill tier + 3 s on the decode tier at 2 blocks:
    # exactly the 8 s x 2 blocks one pool would have integrated.
    assert pre_s + dec_s == pytest.approx(16.0)


def test_cross_tier_billing_token_conservation(compiled):
    """Cross-tier token accounting: prefill tokens bill on the prefill
    engine, the first decode token there too (it is sampled by the
    prefill), the rest on the decode engine — summed, exactly the
    monolithic engine's ledger."""
    prompt = [5, 3, 9, 2, 6]
    mono = _engine(compiled)
    mono.result(mono.submit(prompt, max_new_tokens=6, tenant="t"),
                timeout_s=60.0)
    m = mono.costs.snapshot()["tenants"]["t"]

    pre, dec = _engine(compiled), _engine(compiled)
    res = _disagg_serve(pre, dec, prompt, max_new_tokens=6, tenant="t")
    assert res.status == "completed"
    p = pre.costs.snapshot()["tenants"]["t"]
    d = dec.costs.snapshot()["tenants"]["t"]
    for key in ("prefill_tokens", "decode_tokens", "submitted",
                "completed"):
        assert p[key] + d[key] == m[key], key


# -- router orchestration ----------------------------------------------------


def _routed_streams(router, prompts, **kw):
    rids = [router.submit(p, max_new_tokens=6, **kw) for p in prompts]
    return [list(router.result(r, timeout_s=120.0).tokens) for r in rids]


def test_router_disagg_token_identity(compiled, flight):
    """A 1-prefill + 1-decode tiered fleet serves the 2-replica
    monolithic fleet's exact streams, with every request crossing the
    handoff (``kv_handoff`` flights, router counters)."""
    prompts = [[5, 3, 9], [1, 2, 3, 4, 5, 6, 7], [11, 12], [8, 8, 8, 8]]

    rs_mono = ReplicaSet(lambda: _engine(compiled), initial=2)
    router_mono = Router(rs_mono)
    want = _routed_streams(router_mono, prompts)
    router_mono.close()

    rs = ReplicaSet(lambda: _engine(compiled),
                    tiers={"prefill": 1, "decode": 1})
    router = Router(rs)
    got = _routed_streams(router, prompts)
    assert got == want
    assert router.handoffs == len(prompts)
    assert router.handoff_fails == 0
    evs = flight.events(kind="kv_handoff")
    assert len(evs) == len(prompts)
    assert all(e.detail["blocks"] >= 1 for e in evs)
    doc = router.tiers_doc()
    assert doc["disagg_active"] is True
    assert set(doc["tiers"]) == {"prefill", "decode"}
    assert doc["handoffs"]["count"] == len(prompts)
    assert doc["handoffs"]["p99_ms"] is not None
    router.close()


def test_router_degrades_to_local_reprefill_on_poisoned_handoff(
        compiled, flight):
    """A structurally-broken handoff (the decode tier rejects the
    frame) must degrade to a local re-prefill: the client still gets
    the monolithic stream, the failure is a ``tier_handoff_fail``
    flight, and the fleet keeps handing off once the poison clears."""
    prompt = [5, 3, 9, 2]
    oracle = _engine(compiled)
    want = list(oracle.result(oracle.submit(prompt, max_new_tokens=6),
                              timeout_s=60.0).tokens)

    rs = ReplicaSet(lambda: _engine(compiled),
                    tiers={"prefill": 1, "decode": 1})
    router = Router(rs)
    dec_eng = rs.serving("decode")[0].engine
    real = dec_eng.submit_handoff

    def poisoned(frame, canary=False):
        raise WireFormatError("poisoned transport (test)")

    dec_eng.submit_handoff = poisoned
    try:
        got = _routed_streams(router, [prompt])
    finally:
        dec_eng.submit_handoff = real
    assert got == [want]
    assert router.handoff_fails == 1
    fails = flight.events(kind="tier_handoff_fail")
    assert len(fails) == 1 and "poisoned" in fails[0].detail["reason"]
    # Poison cleared: the next request hands off normally — the decode
    # slot the reject touched is not wedged.
    assert _routed_streams(router, [prompt]) == [want]
    assert router.handoffs == 1
    router.close()


# -- QoS ---------------------------------------------------------------------


def test_qos_bucket_throttle_is_deterministic(flight):
    clock = FakeClock()
    qos = QoSPolicy(buckets={"t": (10.0, 20.0)}, clock=clock)
    assert qos.try_admit("t", 20.0) is None  # burst covers it
    with pytest.raises(AdmissionThrottled) as exc:
        qos.try_admit("t", 5.0)
    assert exc.value.reason == "bucket"
    assert exc.value.retry_after == pytest.approx(0.5)  # 5 units @ 10/s
    clock.advance(0.5)
    assert qos.try_admit("t", 5.0) is None  # refilled exactly
    evs = flight.events(kind="admission_throttle")
    assert len(evs) == 1 and evs[0].detail["tenant"] == "t"
    snap = qos.snapshot()["tenants"]["t"]
    assert snap["admitted"] == 2 and snap["throttled"] == 1


def test_qos_fair_share_window_and_priority_bypass(flight):
    clock = FakeClock()
    qos = QoSPolicy(weights={"hog": 1.0, "meek": 1.0},
                    priorities={"vip": 0},
                    fairness_window=100.0, clock=clock)
    qos.try_admit("meek", 10.0)  # floor at vtime 10
    qos.try_admit("hog", 150.0)  # hog joins at the floor, runs to 160
    with pytest.raises(AdmissionThrottled) as exc:
        qos.try_admit("hog", 1.0)  # 160 - 10 > 100: overdraft
    assert exc.value.reason == "fair_share"
    # Priority class 0 bypasses the fairness window entirely.
    for _ in range(5):
        assert qos.try_admit("vip", 500.0) is None
    qos.note_preempted("hog")
    assert qos.snapshot()["tenants"]["hog"]["preempted"] == 1


def test_router_preempts_queued_lower_priority_for_class0(
        compiled, flight):
    """With a full mono replica, a class-0 submit cancels one QUEUED
    lower-priority request (``tenant_preempted`` flight); the victim
    redispatches and still completes."""
    qos = QoSPolicy(priorities={"vip": 0, "bulk": 2})
    rs = ReplicaSet(
        lambda: _engine(compiled, max_slots=1, queue_depth=1), initial=1)
    router = Router(rs, qos=qos)
    rid_a = router.submit([5, 3, 9], max_new_tokens=6, tenant="bulk")
    rid_b = router.submit([9, 9], max_new_tokens=6, tenant="vip")
    assert router.preemptions == 1
    evs = flight.events(kind="tenant_preempted")
    assert len(evs) == 1 and evs[0].detail["beneficiary"] == "vip"
    for rid in (rid_a, rid_b):  # the victim redispatches and completes
        res = router.result(rid, timeout_s=120.0)
        assert res.status == "completed"
    router.close()


# -- vocabulary + ops plane --------------------------------------------------


def test_disagg_vocabulary_is_registered():
    from elephas_tpu.obs import alerts
    from elephas_tpu.obs.opsd import ROUTES

    for kind in ("kv_handoff", "tier_handoff_fail", "admission_throttle",
                 "tenant_preempted", "tier_imbalance", "handoff_slow"):
        assert kind in flight_mod.KINDS, kind
    assert "tier_imbalance" in alerts.RULE_NAMES
    assert "handoff_slow" in alerts.RULE_NAMES
    by_name = {r.name: r for r in alerts.default_rules()}
    assert by_name["tier_imbalance"].metric == "fleet_tier_imbalance"
    assert by_name["handoff_slow"].metric == "fleet_handoff_seconds_p99"
    assert "/tiers" in ROUTES


def test_tiers_route_serves_default_doc():
    from elephas_tpu.obs.opsd import OpsServer
    import urllib.request
    import json as _json

    server = OpsServer(port=0)
    server.start()
    try:
        with urllib.request.urlopen(f"{server.url}/tiers",
                                    timeout=5.0) as resp:
            doc = _json.loads(resp.read())
    finally:
        server.stop()
    assert doc == {"disagg_active": False, "tiers": {}, "imbalance": 0.0,
                   "handoffs": {"count": 0, "fails": 0, "p50_ms": None,
                                "p99_ms": None},
                   "preemptions": 0, "qos": None}
