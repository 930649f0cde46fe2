"""The MiniCPM-SALA family: `MiniCPMSALA` (block-sparse attention layers,
`"minicpm4"`, beside lightning linear-attention layers, `"lightning-attn"`,
by `mixer_types`; gated feed-forwards; MiniCPM's scalings; an untied head),
as a configuration file's `"model": "minicpm_sala"` names it. The keys are
the published `config.json`'s, and `sparse_config` MiniCPM4's sparse
attention's, which the row names and does not give.

What a family gives the serving harness is set out in `models/gpt2.py`.
Here besides:

- **A layer's tree differs by its kind**: `block_at` draws a sparse layer's
  `attention` (q, k, v of its K/V heads, the q and k norms, the output gate
  and `o`) or a lightning layer's `lightning` (q, k, v, the norms, the
  output norm's scale `o_norm` over every head's values, the gate and `o`).
- **Weights from `--seed`**: every matrix normal(0.02 sqrt(4096 / d)), `o`
  and `down` scaled by 1/sqrt(2 L), norm scales normal(1, 0.02), the head
  at 0.02 sqrt(4096 / d) times `d / dim_model_base`: the logits are divided
  by that factor, and so spread as an unscaled head's would.
- **The published depth** (`published.num_hidden_layers`) sets the residual
  factor `scale_depth / sqrt(depth)` and each lightning layer's decay (its
  published index over `depth - 1`): the cut is the model's first stage.
- **Needed work** counts the matrix products, lightning at its chunk form's
  FLOPs (a sub-chunk's triangle of scores and values, the inter-chunk
  product, the state update), the compressed-key scores of a query that
  selects over the keys that exist at its column, and attention over
  `min(t + 1, top_k * block)` columns, every column while `t < dense_len`.

d = hidden_size, L = num_hidden_layers, f = intermediate_size, V =
vocab_size, H = lightning heads of D, Hq / Hkv = the sparse layers' heads.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib.weights import seed_key

REFERENCE = "minicpm_sala"
LIGHTNING = "lightning-attn"

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "lightning_nh", "lightning_head_dim",
         "vocab_size", "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
         "dim_model_base", "max_position_embeddings")
_SPARSE = ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
           "window_size", "dense_len")


def shape(config: dict) -> dict:
    from elephas_tpu.models import registered_models

    if "minicpm_sala_lm" not in registered_models():
        # before any weight is drawn: an earlier program has no such layers
        raise ValueError("this program has no 'minicpm_sala_lm': no lightning layer, "
                         "no selection by blocks; it cannot serve the family")
    if (config.get("attn_use_rope") or not config.get("lightning_use_rope")
            or not config.get("qk_norm") or not config.get("use_output_gate")
            or not config.get("use_output_norm") or not config.get("attn_use_output_gate")
            or config.get("tie_word_embeddings") or config.get("attention_bias")
            or config.get("lightning_nkv") != config.get("lightning_nh")
            or config.get("lightning_scale") != "1/sqrt(d)"):
        raise ValueError("this family's sparse layers have no positions and its lightning "
                         "layers rotary ones, q/k norms and output gates on both, an output "
                         "norm on lightning, as many lightning K/V heads as query heads, "
                         "an untied head and no bias")
    cfg = {k: config[k] for k in _KEYS}
    cfg["mixer_types"] = tuple(config["mixer_types"][:cfg["num_hidden_layers"]])
    cfg["sparse"] = tuple(config["sparse_config"][k] for k in _SPARSE)
    cfg["depth"] = config.get("published", {}).get("num_hidden_layers",
                                                   cfg["num_hidden_layers"])
    return cfg


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def is_lightning(cfg: dict, layer: int) -> bool:
    return cfg["mixer_types"][layer] == LIGHTNING


def _kinds(cfg: dict, types=None) -> Tuple[int, int]:
    """(sparse, lightning) layers of `types` (the configuration's own where None)."""
    types = cfg["mixer_types"] if types is None else types
    light = sum(t == LIGHTNING for t in types)
    return len(types) - light, light


def hyper(cfg: dict) -> dict:
    """What the plain reference cannot read from the shapes of the weights."""
    kernel, stride, block, topk, init, window, dense = cfg["sparse"]
    return {"mixer_types": cfg["mixer_types"], "depth": cfg["depth"],
            "eps": float(cfg["rms_norm_eps"]), "rope_theta": float(cfg["rope_theta"]),
            "scale_emb": float(cfg["scale_emb"]), "scale_depth": float(cfg["scale_depth"]),
            "logit_divisor": cfg["hidden_size"] / cfg["dim_model_base"],
            "kernel": kernel, "stride": stride, "block": block, "topk": topk,
            "init_blocks": init, "window": window, "dense_len": dense}


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    return get_model(
        "minicpm_sala_lm", dtype=dtype, vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"], mixer_types=cfg["mixer_types"],
        layer_ids=tuple(range(layers(cfg))), depth=cfg["depth"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"], d_ff=cfg["intermediate_size"],
        selection=cfg["sparse"], scale_emb=float(cfg["scale_emb"]),
        scale_depth=float(cfg["scale_depth"]), dim_model_base=cfg["dim_model_base"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"])


# -- weights (flax `MiniCPMSALA` layout) ---------------------------------------


def _sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                Dq=cfg["head_dim"], H=cfg["lightning_nh"], D=cfg["lightning_head_dim"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def _std(d: int) -> float:
    return 0.02 * math.sqrt(4096 / d)


def _drawer(key, dtype, count: int):
    ks = iter(jax.random.split(key, count))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    return normal


@functools.partial(jax.jit, static_argnames=("lightning", "sizes", "dtype"))
def draw_block(key, lightning: bool, sizes: tuple, dtype):
    s = dict(sizes)
    d, f = s["d"], s["f"]
    normal = _drawer(key, dtype, 16)
    std = _std(d)
    out_std = std / math.sqrt(2 * s["L"])
    heads, kv, D = (s["H"], s["H"], s["D"]) if lightning else (s["Hq"], s["Hkv"], s["Dq"])
    mixer = {
        "q": {"kernel": normal((d, heads, D), std)},
        "k": {"kernel": normal((d, kv, D), std)},
        "v": {"kernel": normal((d, kv, D), std)},
        "q_norm": {"scale": normal((D,), 0.02, 1.0)},
        "k_norm": {"scale": normal((D,), 0.02, 1.0)},
        "gate": {"kernel": normal((d, heads * D), std)},
        "o": {"kernel": normal((heads * D, d), out_std)},
    }
    if lightning:
        mixer["o_norm"] = normal((heads * D,), 0.02, 1.0)
    return {"mixer_norm": {"scale": normal((d,), 0.02, 1.0)},
            "mlp_norm": {"scale": normal((d,), 0.02, 1.0)},
            "gate": {"kernel": normal((d, f), std)},
            "up": {"kernel": normal((d, f), std)},
            "down": {"kernel": normal((f, d), out_std)},
            "lightning" if lightning else "attention": mixer}


@functools.partial(jax.jit, static_argnames=("d", "vocab", "head_factor", "dtype"))
def draw_top(key, d: int, vocab: int, head_factor: float, dtype):
    normal = _drawer(key, dtype, 4)
    return {"tok_embed": {"embedding": normal((vocab, d), _std(d))},
            "final_norm": {"scale": normal((d,), 0.02, 1.0)},
            "lm_head": normal((d, vocab), _std(d) * head_factor)}


def block_at(seed: int, layer: int, cfg: dict, dtype):
    return draw_block(jax.random.fold_in(seed_key(seed), layer + 1), is_lightning(cfg, layer),
                      tuple(sorted(_sizes(cfg).items())), dtype)


def top_at(seed: int, cfg: dict, dtype):
    """The embedding, the final norm and the head, and beside them, for the
    plain reference alone, `hyper`: plain numbers, no weight."""
    return {**draw_top(seed_key(seed), cfg["hidden_size"], cfg["vocab_size"],
                       cfg["hidden_size"] / cfg["dim_model_base"], dtype),
            "hyper": hyper(cfg)}


def params(seed: int, cfg: dict, dtype) -> dict:
    out = {k: v for k, v in top_at(seed, cfg, dtype).items() if k != "hyper"}
    for layer in range(layers(cfg)):
        out[f"Layer_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


# -- sizes and needed work ------------------------------------------------------


def _mixer_matrices(cfg: dict, lightning: bool) -> int:
    s = _sizes(cfg)
    if lightning:
        return 5 * s["d"] * s["H"] * s["D"]
    return 3 * s["d"] * s["Hq"] * s["Dq"] + 2 * s["d"] * s["Hkv"] * s["Dq"]


def _layer_params(cfg: dict, lightning: bool) -> int:
    """A layer's matrices and norms."""
    s = _sizes(cfg)
    norms = 2 * s["d"] + (2 * s["D"] + s["H"] * s["D"] if lightning else 2 * s["Dq"])
    return _mixer_matrices(cfg, lightning) + 3 * s["d"] * s["f"] + norms


def param_count(cfg: dict, published: dict = None) -> int:
    """As the configuration is cut; with `published` (its `num_hidden_layers`
    and `mixer_types` before the cut), the whole model."""
    types = cfg["mixer_types"] if published is None else \
        tuple(published["mixer_types"][:published["num_hidden_layers"]])
    sparse, light = _kinds(cfg, types)
    return (sparse * _layer_params(cfg, False) + light * _layer_params(cfg, True)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return param_count(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """What a token holds in the paged pool on every sparse layer: its key
    and value of each K/V head, and a 1/stride share of a compressed key."""
    sparse, _ = _kinds(cfg)
    s = _sizes(cfg)
    stride = cfg["sparse"][1]
    return sparse * s["Hkv"] * s["Dq"] * bytes_per_value * (2 * stride + 1) // stride


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's lightning states: (H, D, D) float32 a layer."""
    _, light = _kinds(cfg)
    return light * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * 4


def _token_flops(cfg: dict) -> float:
    """Matrix-product FLOPs one token needs in the layers, two a weight."""
    sparse, light = _kinds(cfg)
    s = _sizes(cfg)
    return 2.0 * (sparse * _mixer_matrices(cfg, False) + light * _mixer_matrices(cfg, True)
                  + layers(cfg) * 3 * s["d"] * s["f"])


def _lightning_token_flops(cfg: dict) -> float:
    """A token's FLOPs in one lightning layer at the chunk form: its row of
    a sub-chunk's triangle of scores and of values (on average (c + 1) / 2
    columns), the inter-chunk product and the state update."""
    from elephas_tpu.ops.lightning import SUB_CHUNK  # the chunk form's

    H, D, c = cfg["lightning_nh"], cfg["lightning_head_dim"], SUB_CHUNK
    return H * (2.0 * 2 * D * (c + 1) / 2 + 2.0 * 2 * D * D)


def _keys_at(cfg: dict, t):
    """Compressed keys that exist at column t (an int, or an array of them)."""
    kernel, stride = cfg["sparse"][:2]
    return np.maximum(0, (np.asarray(t) - kernel + 1) // stride + 1)


def _columns_at(cfg: dict, t):
    """Columns query t attends: every one before `dense_len`, else its
    blocks' (`top_k * block` at the most)."""
    _, _, block, topk, _, _, dense = cfg["sparse"]
    t = np.asarray(t)
    return np.where(t < dense, t + 1, np.minimum(t + 1, topk * block))


def _sparse_query(cfg: dict, t) -> Tuple[float, float]:
    """(score FLOPs, attention FLOPs) of queries `t` of one sparse layer,
    summed: the compressed keys' scores of a query that selects, and the
    scores and values of the columns it attends, every query head."""
    Hq, Dq = cfg["num_attention_heads"], cfg["head_dim"]
    t = np.asarray(t)
    scores = np.where(t >= cfg["sparse"][6], 2.0 * Hq * Dq * _keys_at(cfg, t), 0.0)
    return float(np.sum(scores)), float(np.sum(4.0 * Hq * Dq * _columns_at(cfg, t)))


def _chunk_queries(start: int, valid: int):
    return np.arange(start, start + valid)


def lightning_chunk_cost(cfg: dict, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chunk's lightning layers at their chunk form:
    q, k, v read and the output written in the served type (2 bytes), a
    head's state read and written once in float32. The program hands the
    kernel float32 operands, and XLA may keep them in the chip's VMEM
    (`S(1)` in the compiled program): counted at four bytes against HBM's
    bandwidth, the kernel read 128 % (PERF.md, Findings, PR 40)."""
    _, light = _kinds(cfg)
    H, D = cfg["lightning_nh"], cfg["lightning_head_dim"]
    flops = light * _lightning_token_flops(cfg) * valid
    nbytes = light * (2 * 4 * valid * H * D + 4 * 2 * H * D * D)
    return flops, float(nbytes)


def block_scores_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chunk's compressed-key scores on the sparse
    layers: each query that selects against the keys that exist at its
    column, every query head; the slot's keys and the queries read once,
    the block scores written in float32."""
    sparse, _ = _kinds(cfg)
    Hq, Hkv, Dq = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    block = cfg["sparse"][2]
    end = start + valid - 1
    t = _chunk_queries(start, valid)
    selecting = int(np.sum(t >= cfg["sparse"][6]))
    nbytes = 2 * (int(_keys_at(cfg, end)) * Hkv * Dq + selecting * Hq * Dq) + \
        4 * selecting * Hkv * (end // block + 1)
    return sparse * _sparse_query(cfg, t)[0], float(sparse * nbytes)


def block_sparse_chunk_attention_cost(cfg: dict, start: int, valid: int,
                                      selected: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chunk's attention on the sparse layers over the
    SELECTED columns alone: `selected` (query, K/V head, column) triples
    summed over the sparse layers (the program's `sparse_columns_selected`),
    scores and values of the group's heads; the slot's live keys and values
    read once, the queries read and the result written."""
    sparse, _ = _kinds(cfg)
    Hq, Hkv, Dq = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 4.0 * (Hq // Hkv) * Dq * selected
    nbytes = sparse * 2 * (2 * (start + valid) * Hkv * Dq + 2 * valid * Hq * Dq)
    return flops, float(nbytes)


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position through the head; every weight read once,
    the slot's live keys and values and compressed keys read, the chunk's
    written, each lightning layer's state read and written."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    sparse, _ = _kinds(cfg)
    flops = (_token_flops(cfg) * valid + lightning_chunk_cost(cfg, valid)[0]
             + sparse * sum(_sparse_query(cfg, _chunk_queries(start, valid)))
             + 2.0 * d * V)
    nbytes = (weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + valid)
              + 2 * state_bytes_per_slot(cfg) + 2 * d * valid)
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step. Bytes: every weight once (the
    embedding's rows but for the lanes'), each lane's lightning states read
    and written, its compressed keys, the keys and values of the columns it
    attends, and one new column."""
    lengths = list(lengths)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    sparse, light = _kinds(cfg)
    H, D = cfg["lightning_nh"], cfg["lightning_head_dim"]
    Hkv, Dq = cfg["num_key_value_heads"], cfg["head_dim"]
    flops = len(lengths) * (_token_flops(cfg) + light * 4.0 * H * D * D + 2.0 * d * V) + \
        sparse * sum(_sparse_query(cfg, lengths))
    cache = sparse * 2 * Hkv * Dq * float(np.sum(
        _keys_at(cfg, lengths) + 2 * (_columns_at(cfg, lengths) + 1)))
    nbytes = (2 * (param_count(cfg) - V * d + len(lengths) * d) + cache
              + len(lengths) * 2 * state_bytes_per_slot(cfg))
    return flops, float(nbytes)
