"""Operations and bytes that the work *needs*, from shapes.

Every share the benchmark reports is of needed work: live KV columns,
valid prompt tokens, unpadded rows. Padding, gathered-but-dead cache
columns and recomputation count for nothing, so a later change that
removes waste raises a share and none can pass 100%. A language model's
counts are its family's (`models/<family>.py`: `chunk_cost`, `decode_cost`).
"""

from __future__ import annotations

from typing import List, Tuple

# -- ResNet-18 at 32x32, by layer shapes (never the 6N rule) ---------------


def resnet18_layers(cfg: dict) -> List[dict]:
    """Every convolution and the head of the CIFAR-style ResNet-18:
    kernel, channels in and out, output side."""
    w, side = cfg["width"], cfg["image_size"]
    layers = [dict(k=3, cin=cfg["channels"], cout=w, out=side, first=True)]
    cin = w
    for stage, blocks in enumerate(cfg["stage_sizes"]):
        cout = w * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            side //= stride
            layers.append(dict(k=3, cin=cin, cout=cout, out=side))
            layers.append(dict(k=3, cin=cout, cout=cout, out=side))
            if cin != cout or stride != 1:
                layers.append(dict(k=1, cin=cin, cout=cout, out=side))
            cin = cout
    layers.append(dict(k=1, cin=cin, cout=cfg["num_classes"], out=1, dense=True))
    return layers


def resnet18_param_count(cfg: dict) -> int:
    n = 0
    for l in resnet18_layers(cfg):
        n += l["k"] * l["k"] * l["cin"] * l["cout"]
        n += l["cout"] if l.get("dense") else 2 * l["cout"]  # bias | BN scale, bias
    return n


def resnet18_train_flops_per_row(cfg: dict) -> float:
    """Forward + backward FLOPs of one row: each layer's forward product,
    the same again for the gradient of its weights and for the gradient of
    its input, which the first layer does not need."""
    total = 0.0
    for l in resnet18_layers(cfg):
        fwd = 2.0 * l["k"] * l["k"] * l["cin"] * l["cout"] * l["out"] * l["out"]
        total += fwd * (2 if l.get("first") else 3)
    return total


def resnet18_step_cost(cfg: dict, batch: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one training step of `batch` rows needs. Bytes: the
    rows read as stored (float32), weights and momentum read and written
    (float32), and every layer's output written once in the forward pass
    and read once in the backward pass, in bfloat16."""
    flops = resnet18_train_flops_per_row(cfg) * batch
    rows = 4.0 * cfg["channels"] * cfg["image_size"] ** 2 * batch
    state = 4.0 * 4 * resnet18_param_count(cfg)
    acts = sum(2.0 * 2 * l["cout"] * l["out"] * l["out"]
               for l in resnet18_layers(cfg)) * batch
    return flops, rows + state + acts


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_flops, by_bytes = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
