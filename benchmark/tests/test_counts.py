"""Operation and byte counts against hand arithmetic written here."""

import json
import os

import pytest

from lib import counts

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XL = {k: v for k, v in json.load(open(os.path.join(HERE, "configs", "gpt2-xl.json"))).items()
      if k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_size")}
RESNET = json.load(open(os.path.join(HERE, "configs", "resnet18-cifar10.json")))


def test_gpt2_xl_sizes():
    d, L, V = 1600, 48, 50257
    block = 3 * d * d + d * d + 8 * d * d + (3 * d + d + 4 * d + d) + 4 * d
    weights = L * block + 2 * d + d * V + V
    assert counts.gpt2_weight_bytes(XL, 1) == weights
    assert counts.gpt2_param_count(XL) == weights + V * d + 1024 * d == 1_638_072_657
    assert counts.gpt2_kv_bytes_per_token(XL) == 2 * 48 * 1600 * 2 == 307_200


def test_gpt2_xl_chunk_of_128_tokens_from_column_256():
    d, L, V = 1600, 48, 50257
    matmul = 128 * L * 24 * d * d                        # 377.5 GFLOP
    attention = 4 * d * L * sum(range(257, 385))         # keys seen: 257 .. 384
    head = 2 * d * V
    flops, nbytes = counts.gpt2_chunk_cost(XL, 256, 128)
    assert flops == pytest.approx(matmul + attention + head, rel=1e-12)
    assert matmul == 377_487_360_000
    assert nbytes == counts.gpt2_weight_bytes(XL) + 307_200 * 384 + 2 * d * 128


def test_gpt2_xl_decode_step_counts_live_kv_only():
    flops, nbytes = counts.gpt2_decode_cost(XL, [100, 500])
    d, L, V = 1600, 48, 50257
    assert flops == pytest.approx(2 * (L * 24 * d * d + 2 * d * V) + 4 * d * L * (101 + 501))
    assert nbytes == counts.gpt2_weight_bytes(XL) + 307_200 * (600 + 2)


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
