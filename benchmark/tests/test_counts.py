"""Operation and byte counts against hand arithmetic written here, and
the GPT-2 family's cost functions (moved from `lib/counts.py` to
`models/gpt2.py`) against what they gave before the move, to the digit."""

import json
import os
from types import SimpleNamespace

import pytest

from lib import counts, serve
from lib.cells import Cell

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = Cell("gpt2-xl.chat-steady")
gpt2 = CELL.module("models", CELL.config["model"])
XL = gpt2.shape(CELL.config)
RESNET = json.load(open(os.path.join(HERE, "configs", "resnet18-cifar10.json")))


def recorded(name):
    """Events, spans and marks of one CPU rehearsal, and beside them what
    the parent's wrappers (`ChunkCounter`, `DecodeCounter`) and its
    `lib/counts.py` made of the same run (`data/events_*.json`)."""
    rec = json.load(open(os.path.join(HERE, "tests", "data", name)))
    run = SimpleNamespace(sink=SimpleNamespace(steps=rec["steps"]),
                          chunk_spans=[(t, start, valid) for t, _, start, valid in rec["chunk_spans"]])
    return rec, run


@pytest.mark.parametrize("name", ["events_chat.json", "events_batch.json"])
@pytest.mark.parametrize("part", ["window", "traced"])
def test_the_moved_cost_functions_give_the_recorded_numbers(name, part):
    rec, run = recorded(name)
    t0, t1 = (rec["t_open"], rec["t_close"]) if part == "window" else rec["marks"]
    steps, chunks = serve.lengths_before_each_step(run, t0, t1)
    decode = [gpt2.decode_cost(rec["cfg"], lengths) for lengths in steps]
    chunk = [gpt2.chunk_cost(rec["cfg"], start, valid) for start, valid in chunks]
    was = rec["parent"][part]
    assert (len(steps), len(chunks)) == (was["steps"], was["chunks"])
    assert sum(f for f, _ in decode) + sum(f for f, _ in chunk) == was["flops"]
    for costs, key in ((decode, "decode_needed_s"), (chunk, "chunk_needed_s")):
        assert sum(counts.roofline_seconds(f, b, rec["peak"])[0] for f, b in costs) == was[key]


def test_gpt2_xl_sizes():
    d, L, V = 1600, 48, 50257
    block = 3 * d * d + d * d + 8 * d * d + (3 * d + d + 4 * d + d) + 4 * d
    weights = L * block + 2 * d + d * V + V
    assert gpt2.weight_bytes(XL, 1) == weights
    assert gpt2.param_count(XL) == weights + V * d + 1024 * d == 1_638_072_657
    assert gpt2.kv_bytes_per_token(XL) == 2 * 48 * 1600 * 2 == 307_200


def test_gpt2_xl_chunk_of_128_tokens_from_column_256():
    d, L, V = 1600, 48, 50257
    matmul = 128 * L * 24 * d * d                        # 377.5 GFLOP
    attention = 4 * d * L * sum(range(257, 385))         # keys seen: 257 .. 384
    head = 2 * d * V
    flops, nbytes = gpt2.chunk_cost(XL, 256, 128)
    assert flops == pytest.approx(matmul + attention + head, rel=1e-12)
    assert matmul == 377_487_360_000
    assert nbytes == gpt2.weight_bytes(XL) + 307_200 * 384 + 2 * d * 128


def test_gpt2_xl_decode_step_counts_live_kv_only():
    flops, nbytes = gpt2.decode_cost(XL, [100, 500])
    d, L, V = 1600, 48, 50257
    assert flops == pytest.approx(2 * (L * 24 * d * d + 2 * d * V) + 4 * d * L * (101 + 501))
    assert nbytes == gpt2.weight_bytes(XL) + 307_200 * (600 + 2)


def test_resnet18_by_layer_shapes():
    cfg = RESNET
    # forward multiply-adds of one 32x32x3 row, layer by layer
    macs = 3 * 3 * 3 * 64 * 32 * 32                          # stem
    macs += 4 * (3 * 3 * 64 * 64 * 32 * 32)                  # stage 0: four 3x3 64->64
    macs += 3 * 3 * 64 * 128 * 16 * 16 + 3 * (3 * 3 * 128 * 128 * 16 * 16) + 64 * 128 * 16 * 16
    macs += 3 * 3 * 128 * 256 * 8 * 8 + 3 * (3 * 3 * 256 * 256 * 8 * 8) + 128 * 256 * 8 * 8
    macs += 3 * 3 * 256 * 512 * 4 * 4 + 3 * (3 * 3 * 512 * 512 * 4 * 4) + 256 * 512 * 4 * 4
    macs += 512 * 10
    stem = 3 * 3 * 3 * 64 * 32 * 32
    assert macs == 555_422_720
    # backward: twice the forward, less the stem's input gradient
    assert counts.resnet18_train_flops_per_row(cfg) == 2 * (3 * macs - stem)
    assert counts.resnet18_param_count(cfg) == 11_173_962
    assert len(counts.resnet18_layers(cfg)) == 21  # 20 convolutions and the head
    flops, nbytes = counts.resnet18_step_cost(cfg, 2048)
    assert flops == 2048 * counts.resnet18_train_flops_per_row(cfg)
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "flops" and seconds == pytest.approx(flops / 197e12)
