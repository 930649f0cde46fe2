"""The GPT-2 family: `TransformerLM` (LayerNorm, GELU MLP, full multi-head
attention, learned positions, an untied head), as a configuration file's
`"model": "gpt2"` names it.

What a family gives the serving harness, from the sized configuration and
nothing else (`lib/serve.py` asks for no more, and names no family):

- `shape(config)`: the sizes the readers get as `run.cfg`, with `vocab_size`;
- `flax_module(cfg, dtype)`: what `compile_model` compiles;
- `params`, `top_at`, `block_at`, `layers`: the weights from `--seed`, drawn
  a layer at a time by one jitted function, so that the program and the
  plain reference draw from the same functions and neither takes anything
  the other has made;
- `REFERENCE`: the plain reference under `references/`;
- `chunk_cost`, `decode_cost`: (FLOPs, bytes) that the work *needs*: live
  KV columns and valid prompt tokens. Padding, gathered-but-dead cache
  columns and recomputation count for nothing, so a later change that
  removes waste raises a share and none can pass 100%.

d = n_embd, L = n_layer, V = vocab_size.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "gpt2"


def shape(config: dict) -> dict:
    return {k: config[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_size")}


def layers(cfg: dict) -> int:
    return cfg["n_layer"]


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    return get_model("transformer_lm", dtype=dtype, vocab_size=cfg["vocab_size"],
                     d_model=cfg["n_embd"], num_heads=cfg["n_head"],
                     num_layers=cfg["n_layer"], max_seq_len=cfg["n_positions"])


# -- weights (flax `TransformerLM` layout) ---------------------------------


@functools.partial(jax.jit, static_argnames=("d", "heads", "depth", "dtype"))
def draw_block(key, d: int, heads: int, depth: int, dtype):
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    hd, out_std = d // heads, 0.02 / math.sqrt(2 * depth)
    return {
        "LayerNorm_0": {"scale": normal((d,), 0.02, 1.0), "bias": normal((d,), 0.02)},
        "SelfAttention_0": {
            "qkv": {"kernel": normal((d, 3, heads, hd), 0.02),
                    "bias": normal((3, heads, hd), 0.02)},
            "out": {"kernel": normal((d, d), out_std), "bias": normal((d,), 0.02)},
        },
        "LayerNorm_1": {"scale": normal((d,), 0.02, 1.0), "bias": normal((d,), 0.02)},
        "Dense_0": {"kernel": normal((d, 4 * d), 0.02), "bias": normal((4 * d,), 0.02)},
        "Dense_1": {"kernel": normal((4 * d, d), out_std), "bias": normal((d,), 0.02)},
    }


@functools.partial(jax.jit, static_argnames=("d", "vocab", "positions", "dtype"))
def draw_top(key, d: int, vocab: int, positions: int, dtype):
    ks = iter(jax.random.split(key, 6))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    return {
        "tok_embed": {"embedding": normal((vocab, d), 0.02)},
        "pos_embed": normal((positions, d), 0.01),
        "LayerNorm_0": {"scale": normal((d,), 0.02, 1.0), "bias": normal((d,), 0.02)},
        "lm_head": {"kernel": normal((d, vocab), 0.02), "bias": normal((vocab,), 0.02)},
    }


def block_at(seed: int, layer: int, cfg: dict, dtype):
    return draw_block(jax.random.fold_in(seed_key(seed), layer + 1), cfg["n_embd"],
                      cfg["n_head"], cfg["n_layer"], dtype)


def top_at(seed: int, cfg: dict, dtype):
    return draw_top(seed_key(seed), cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"], dtype)


def params(seed: int, cfg: dict, dtype) -> dict:
    out = dict(top_at(seed, cfg, dtype))
    for layer in range(cfg["n_layer"]):
        out[f"Block_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


# -- needed work ------------------------------------------------------------


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes of every weight one forward step reads: the blocks, the final
    LayerNorm and the (untied) head. The embedding tables are read a row
    per token and are counted with the tokens."""
    d, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_block = 12 * d * d + 13 * d  # qkv, out, two MLP matrices; biases, LayerNorms
    return (L * per_block + 2 * d + d * V + V) * bytes_per_param


def param_count(cfg: dict) -> int:
    d, V, P = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    return weight_bytes(cfg, 1) + V * d + P * d


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def token_flops(cfg: dict, position: int, head: bool) -> float:
    """Forward FLOPs of one token at `position` (0-based; it attends
    `position + 1` keys): 24·d² of matrix products and 4·d·(position+1) of
    attention per layer, and 2·d·V for the head where a token is sampled."""
    d, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    flops = L * (24.0 * d * d + 4.0 * d * (position + 1))
    return flops + (2.0 * d * V if head else 0.0)


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position, the weights read once, the slot's live
    KV read and the chunk's KV written."""
    d, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    positions = valid * start + valid * (valid + 1) / 2.0  # sum of (p + 1)
    flops = L * (24.0 * d * d * valid + 4.0 * d * positions) + 2.0 * d * V
    kv = kv_bytes_per_token(cfg)
    nbytes = weight_bytes(cfg) + kv * (start + valid) + 2 * d * valid
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step: the weights once, every lane's live
    KV, one new column each."""
    lengths = list(lengths)
    flops = sum(token_flops(cfg, c, head=True) for c in lengths)
    kv = kv_bytes_per_token(cfg)
    nbytes = weight_bytes(cfg) + kv * (sum(lengths) + len(lengths))
    return flops, float(nbytes)
