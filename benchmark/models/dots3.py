"""The dots3-note family's language model: `LatentMoELM` with FULL layers
(latent attention, a learned sparse attention's indexer, top-2,048 columns a
query) and WINDOW layers (a second latent geometry, 513 columns a query) by
`layer_types`, a gate a head, rescaled latents, and a router that scores by
a sigmoid and selects with a bias; as a configuration file's `"model":
"dots3"` names it. The keys are the published `config.json`'s.

What a family gives the serving harness is set out in `models/gpt2.py`, the
share (`n_routed_experts` HELD of `router_experts`, `experts_first`, a
slice of the vocabulary) in `models/deepseek_v2.py`. Here besides:

- **A layer's tree differs by its kind twice**: `block_at` draws a full
  layer's attention with `gate` and the indexer's `index_q`, `index_k`,
  `index_k_norm`, `index_w`, a window layer's at the `swa_` sizes with
  `gate` alone; a dense feed-forward for the first `first_k_dense_replace`
  layers and `shared` + `experts` (the router and its selection `bias`
  with them) for the rest.
- **Weights from `--seed`**: as `deepseek_v2.py` draws them (normal(0.02) at
  the published width, `out` and every `down` scaled by 1/sqrt(2 L), norm
  scales normal(1, 0.02)); the router's selection bias normal(0, 0.02), so
  that the experts chosen (by `s + b`) are not always the eight that weigh
  most (by `s`).
- **Needed work** counts, on a full layer, the index scores over EVERY live
  column (`2 * index_n_heads * index_head_dim` FLOPs a query and column)
  and attention at the expanded form over the SELECTED columns alone,
  `min(t + 1, index_topk)` a query; on a window layer attention over
  `min(t + 1, sliding_window)` columns; the routed experts at the
  expectation where the caller has no counter. A body that does dense
  arithmetic over every live column and masks reads low against this
  count, which is the truth about it.

d = hidden_size, L = num_hidden_layers, f = moe_intermediate_size, V =
vocab_size.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "dots3"

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "vocab_size", "first_k_dense_replace", "n_routed_experts",
         "router_experts", "experts_first", "n_shared_experts", "moe_intermediate_size",
         "num_experts_per_tok", "routed_scaling_factor", "rope_theta", "rms_norm_eps",
         "max_position_embeddings", "layer_types", "sliding_window_size",
         "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
         "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
         "swa_rope_theta", "index_n_heads", "index_head_dim", "index_topk")


def shape(config: dict) -> dict:
    from elephas_tpu.models import registered_models
    from elephas_tpu.models.latent_moe import LatentMoELM

    if "latent_moe_lm" not in registered_models() or \
            "layer_types" not in LatentMoELM.__dataclass_fields__:
        # before any weight is drawn: an earlier program has no such layers
        raise ValueError("this program's 'latent_moe_lm' has no window layers, "
                         "no indexer: it cannot serve the family")
    cfg = {k: config[k] for k in _KEYS}
    cfg["layer_types"] = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if (config.get("scoring_func") != "sigmoid" or not config.get("norm_topk_prob")
            or config.get("topk_method") != "noaux_tc" or config.get("n_group", 1) != 1
            or config.get("moe_layer_freq") != 1 or config.get("tie_word_embeddings")
            or config.get("attention_bias") or config.get("rope_scaling")
            or not config.get("apply_mla_qkv_lora_rescale")
            or config.get("attention_gate_type") != "headwise"
            or config.get("swa_attention_gate_type") != "headwise"):
        raise ValueError("this family routes by a sigmoid with a selection bias over "
                         "one group, renormalised, every layer past the dense ones; "
                         "gates its heads, rescales its latents; untied, no bias, no "
                         "rope scaling")
    return cfg


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def is_routed(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def is_window(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "sliding_attention"


def _plain_rope(theta: float) -> tuple:
    """`latent_moe`'s rotary parameters for the plain rotary at `theta`: a
    YaRN of factor 1."""
    return (float(theta), 1.0, 0.0, 0.0, 0.0, 1.0, 1.0)


def hyper(cfg: dict) -> dict:
    """What the plain reference cannot read from the shapes of the weights."""
    return {"top_k": cfg["num_experts_per_tok"],
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
            "first": cfg["experts_first"], "layer_types": cfg["layer_types"],
            "window": cfg["sliding_window_size"], "index_topk": cfg["index_topk"],
            "rope_theta": float(cfg["rope_theta"]),
            "swa_rope_theta": float(cfg["swa_rope_theta"]),
            "rope_width": cfg["qk_rope_head_dim"],
            "swa_rope_width": cfg["swa_qk_rope_head_dim"]}


def _sizes(cfg: dict, window: bool = False) -> dict:
    """One layer kind's sizes: `H`, `qr`, `r`, `nope`, `pe`, `v` are the
    `swa_` ones for a window layer."""
    p = "swa_" if window else ""
    return dict(d=cfg["hidden_size"], H=cfg[p + "num_attention_heads"],
                qr=cfg[p + "q_lora_rank"], r=cfg[p + "kv_lora_rank"],
                nope=cfg[p + "qk_nope_head_dim"], pe=cfg[p + "qk_rope_head_dim"],
                v=cfg[p + "v_head_dim"], ff=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
                total=cfg["router_experts"], shared=cfg["n_shared_experts"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                Hi=cfg["index_n_heads"], Di=cfg["index_head_dim"],
                window=int(window))


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    s, w = _sizes(cfg), _sizes(cfg, True)
    return get_model(
        "latent_moe_lm", dtype=dtype, vocab_size=s["V"], d_model=s["d"],
        num_layers=s["L"], num_heads=s["H"], q_lora_rank=s["qr"], kv_lora_rank=s["r"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["pe"], v_head_dim=s["v"],
        d_ff=s["ff"], first_dense=cfg["first_k_dense_replace"],
        n_routed_experts=s["total"], experts_held=(cfg["experts_first"], s["held"]),
        n_shared_experts=s["shared"], moe_d_ff=s["f"], top_k=cfg["num_experts_per_tok"],
        n_group=1, topk_group=1,
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rope=_plain_rope(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"], layer_types=cfg["layer_types"],
        sliding_window=cfg["sliding_window_size"],
        window_geometry=(w["H"], w["qr"], w["r"], w["nope"], w["pe"], w["v"],
                         _plain_rope(cfg["swa_rope_theta"])),
        indexer=(s["Hi"], s["Di"], cfg["index_topk"]), head_gate=True,
        latent_rescale=True, scoring="sigmoid", norm_topk_prob=True,
        selection_bias=True)


# -- weights (flax `LatentMoELM` layout) -------------------------------------


def _std(d: int) -> float:
    return 0.02 * math.sqrt(5120 / d)


def _drawer(key, dtype, count: int):
    ks = iter(jax.random.split(key, count))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    return normal


def _gated(normal, d: int, f: int, std: float, out_std: float) -> dict:
    return {"gate": {"kernel": normal((d, f), std)}, "up": {"kernel": normal((d, f), std)},
            "down": {"kernel": normal((f, d), out_std)}}


@functools.partial(jax.jit, static_argnames=("routed", "sizes", "dtype"))
def draw_block(key, routed: bool, sizes: tuple, dtype):
    s = dict(sizes)
    d, H, f = s["d"], s["H"], s["f"]
    normal = _drawer(key, dtype, 32)
    std = _std(d)
    out_std = std / math.sqrt(2 * s["L"])
    attention = {
        "q_a": {"kernel": normal((d, s["qr"]), std)},
        "q_a_norm": {"scale": normal((s["qr"],), 0.02, 1.0)},
        "q_b": {"kernel": normal((s["qr"], H, s["nope"] + s["pe"]), std)},
        "kv_a": {"kernel": normal((d, s["r"] + s["pe"]), std)},
        "kv_a_norm": {"scale": normal((s["r"],), 0.02, 1.0)},
        "kv_b": normal((s["r"], H, s["nope"] + s["v"]), std),
        "out": {"kernel": normal((H, s["v"], d), out_std)},
        "gate": {"kernel": normal((d, H), std)},
    }
    if not s["window"]:
        attention.update({
            "index_q": {"kernel": normal((s["qr"], s["Hi"], s["Di"]), std)},
            "index_k": {"kernel": normal((d, s["Di"]), std)},
            "index_k_norm": {"scale": normal((s["Di"],), 0.02, 1.0)},
            "index_w": {"kernel": normal((d, s["Hi"]), std)},
        })
    block = {"attn_norm": {"scale": normal((d,), 0.02, 1.0)},
             "ffn_norm": {"scale": normal((d,), 0.02, 1.0)}, "attention": attention}
    if not routed:
        return {**block, **_gated(normal, d, s["ff"], std, out_std)}
    block["shared"] = _gated(normal, d, s["shared"] * f, std, out_std)
    block["experts"] = {
        "router": {"kernel": normal((d, s["total"]), std)},
        # float32 whatever the served type: it is added to float32 scores
        "bias": normal((s["total"],), 0.02).astype(jnp.float32),
        "gate": normal((s["held"], d, f), std),
        "up": normal((s["held"], d, f), std),
        "down": normal((s["held"], f, d), out_std),
    }
    return block


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def draw_top(key, d: int, vocab: int, dtype):
    normal = _drawer(key, dtype, 4)
    return {"tok_embed": {"embedding": normal((vocab, d), _std(d))},
            "final_norm": {"scale": normal((d,), 0.02, 1.0)},
            "lm_head": {"kernel": normal((d, vocab), _std(d))}}


def block_at(seed: int, layer: int, cfg: dict, dtype):
    return draw_block(jax.random.fold_in(seed_key(seed), layer + 1), is_routed(cfg, layer),
                      tuple(sorted(_sizes(cfg, is_window(cfg, layer)).items())), dtype)


def top_at(seed: int, cfg: dict, dtype):
    """The embedding, the final norm and the head, and beside them, for the
    plain reference alone, `hyper`: plain numbers, no weight."""
    return {**draw_top(seed_key(seed), cfg["hidden_size"], cfg["vocab_size"], dtype),
            "hyper": hyper(cfg)}


def params(seed: int, cfg: dict, dtype) -> dict:
    out = {k: v for k, v in top_at(seed, cfg, dtype).items() if k != "hyper"}
    for layer in range(layers(cfg)):
        out[f"Layer_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


# -- needed work -------------------------------------------------------------


def _attention_params(cfg: dict, window: bool) -> int:
    """A layer's attention matrices, its gate and, on a full layer, its
    indexer's three."""
    s = _sizes(cfg, window)
    n = (s["d"] * s["qr"] + s["qr"] * s["H"] * (s["nope"] + s["pe"])
         + s["d"] * (s["r"] + s["pe"]) + s["r"] * s["H"] * (s["nope"] + s["v"])
         + s["H"] * s["v"] * s["d"] + s["d"] * s["H"])
    if not window:
        n += s["qr"] * s["Hi"] * s["Di"] + s["d"] * s["Di"] + s["d"] * s["Hi"]
    return n


def _attention_norms(cfg: dict, window: bool) -> int:
    s = _sizes(cfg, window)
    return s["qr"] + s["r"] + (0 if window else s["Di"])


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _kinds(cfg: dict, types=None) -> Tuple[int, int]:
    """(full, window) layers of `types` (the configuration's own where None)."""
    types = cfg["layer_types"] if types is None else types
    window = sum(t == "sliding_attention" for t in types)
    return len(types) - window, window


def _count(cfg: dict, experts: int, types, vocab: int) -> int:
    """Parameters with `experts` routed experts a routed layer, layers of
    the kinds `types` of which `first_k_dense_replace` are dense, and `vocab`
    rows."""
    s = _sizes(cfg)
    d, depth = s["d"], len(types)
    full, window = _kinds(cfg, types)
    attention = (full * (_attention_params(cfg, False) + _attention_norms(cfg, False))
                 + window * (_attention_params(cfg, True) + _attention_norms(cfg, True)))
    first = min(cfg["first_k_dense_replace"], depth)
    routed = (s["shared"] * _expert_params(cfg) + d * s["total"] + s["total"]
              + experts * _expert_params(cfg))
    return (attention + depth * 2 * d + first * 3 * d * s["ff"]
            + (depth - first) * routed + 2 * vocab * d + d)


def param_count(cfg: dict, published: Optional[dict] = None) -> int:
    """As the configuration is cut; with `published` (its `num_hidden_layers`,
    `n_routed_experts`, `vocab_size` and `layer_types` before the cut), the
    whole model."""
    if published is not None:
        return _count(cfg, published["n_routed_experts"],
                      tuple(published["layer_types"]), published["vocab_size"])
    return _count(cfg, cfg["n_routed_experts"], cfg["layer_types"], cfg["vocab_size"])


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return param_count(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """What a token holds in the paged pool: on every FULL layer its latent
    (`kv_lora_rank + qk_rope_head_dim`) and its index key
    (`index_head_dim`). A window layer's ring is bounded by the window, not
    by the tokens."""
    full, _ = _kinds(cfg)
    return full * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
                   + cfg["index_head_dim"]) * bytes_per_value


def window_bytes_per_slot(cfg: dict, prefill_chunk: int, block: int,
                          bytes_per_value: int = 2) -> int:
    """A slot's rings: `sliding_window_size - 1 + prefill_chunk` columns,
    rounded up to blocks, of every window layer's latent."""
    _, window = _kinds(cfg)
    columns = -(-(cfg["sliding_window_size"] - 1 + prefill_chunk) // block) * block
    return window * columns * (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) \
        * bytes_per_value


def _score_flops(cfg: dict, window: bool) -> float:
    """FLOPs a query spends on one attended column in one layer, keys and
    values a head: the form the model is published in."""
    s = _sizes(cfg, window)
    return 2.0 * s["H"] * (s["nope"] + s["pe"] + s["v"])


def _index_flops(cfg: dict) -> float:
    """FLOPs a query spends on one live column's index score in one full
    layer."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def _token_flops(cfg: dict, routed_share: Optional[float] = None) -> float:
    """Matrix-product FLOPs one token needs in every layer, two a weight:
    attention's projections (the gate and the indexer's with them), the
    dense feed-forward or the shared expert and the router, and the routed
    experts at `routed_share` experts a token a layer (the expectation
    `top_k * held / total` where None)."""
    s = _sizes(cfg)
    full, window = _kinds(cfg)
    dense = min(cfg["first_k_dense_replace"], layers(cfg))
    routed = layers(cfg) - dense
    if routed_share is None:
        routed_share = cfg["num_experts_per_tok"] * s["held"] / s["total"]
    per_routed = (s["shared"] + routed_share) * _expert_params(cfg) + s["d"] * s["total"]
    return 2.0 * (full * _attention_params(cfg, False) + window * _attention_params(cfg, True)
                  + dense * 3 * s["d"] * s["ff"] + routed * per_routed)


def _positions(start: int, valid: int, cap: Optional[int] = None) -> float:
    """Sum over the queries `start .. start + valid - 1` of the columns each
    sees: `t + 1`, or `min(t + 1, cap)`."""
    if cap is None or start + valid <= cap:
        return valid * start + valid * (valid + 1) / 2.0
    under = max(0, min(valid, cap - start))  # queries that still see t + 1 < cap
    return under * start + under * (under + 1) / 2.0 + (valid - under) * float(cap)


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position through the head; on a full layer the
    index scores over every live column and attention over the selected
    ones, on a window layer attention over the window; every weight read
    once, the slot's live latents and index keys read, the chunk's written,
    the window layers' rings read as far as the window reaches."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    full, window = _kinds(cfg)
    live = _positions(start, valid)
    flops = (_token_flops(cfg) * valid
             + full * (_index_flops(cfg) * live
                       + _score_flops(cfg, False) * _positions(start, valid, cfg["index_topk"]))
             + window * _score_flops(cfg, True)
             * _positions(start, valid, cfg["sliding_window_size"])
             + 2.0 * d * V)
    ring = min(start + valid, cfg["sliding_window_size"] - 1 + valid)
    nbytes = (weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + valid)
              + window * 2 * ring * (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"])
              + 2 * d * valid)
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int],
                touched: Optional[float] = None) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step. Bytes: every weight but the routed
    experts' once (the embedding's rows but for the lanes'), the weights of
    the `touched` experts (the program's `moe_experts_touched`; none where it
    is not given), every lane's live index keys, its SELECTED latents
    (`min(t + 1, index_topk)`), its window's columns of the rings, and one
    new column each."""
    lengths = list(lengths)
    s = _sizes(cfg)
    d, V = s["d"], s["V"]
    full, window = _kinds(cfg)
    k, w = cfg["index_topk"], cfg["sliding_window_size"]
    flops = sum(_token_flops(cfg)
                + full * (_index_flops(cfg) * (c + 1) + _score_flops(cfg, False) * min(c + 1, k))
                + window * _score_flops(cfg, True) * min(c + 1, w)
                + 2.0 * d * V for c in lengths)
    routed_layers = layers(cfg) - min(cfg["first_k_dense_replace"], layers(cfg))
    fixed = param_count(cfg) - routed_layers * s["held"] * _expert_params(cfg) - V * d
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ring = cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    cache = sum(full * (cfg["index_head_dim"] * (c + 1) + latent * (min(c + 1, k) + 1))
                + window * ring * (min(c + 1, w) + 1) for c in lengths)
    nbytes = 2 * (fixed + (touched or 0.0) * _expert_params(cfg) + len(lengths) * d + cache)
    return flops, float(nbytes)


def mla_chunk_attention_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the WINDOW layers' attention of one chunk needs, the
    projections apart (the `latent_chunk_attention` kernel runs those layers
    alone here): `min(t + 1, sliding_window)` columns a query, keys and
    values a head; the queries read and the result written a head, the
    window's reach of the ring read once."""
    s = _sizes(cfg, True)
    _, window = _kinds(cfg)
    flops = window * _score_flops(cfg, True) * _positions(
        start, valid, cfg["sliding_window_size"])
    ring = min(start + valid, cfg["sliding_window_size"] - 1 + valid)
    nbytes = window * 2 * (ring * (s["r"] + s["pe"])
                           + valid * s["H"] * (s["nope"] + s["pe"] + s["v"]))
    return flops, float(nbytes)


def index_chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the index scores of one chunk need over the full
    layers: every query against every live column; the index queries and
    weights read, the slot's live index keys read once, the scores written
    in float32."""
    full, _ = _kinds(cfg)
    live = _positions(start, valid)
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    nbytes = full * (2 * (valid * Hi * Di + (start + valid) * Di) + 4 * valid * Hi
                     + 4 * live)
    return full * _index_flops(cfg) * live, float(nbytes)


def sparse_chunk_attention_cost(cfg: dict, start: int, valid: int,
                                selected: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the full layers' attention of one chunk needs over the
    SELECTED columns alone: `selected` (query, column) pairs summed over the
    full layers (the program's `sparse_columns_selected`), keys and values a
    head; the queries read and the result written a head, the slot's live
    latents read once (the queries' sets differ, and together they cover
    most of them)."""
    s = _sizes(cfg)
    full, _ = _kinds(cfg)
    flops = _score_flops(cfg, False) * selected
    nbytes = full * 2 * ((start + valid) * (s["r"] + s["pe"])
                         + valid * s["H"] * (s["nope"] + s["pe"] + s["v"]))
    return flops, float(nbytes)


def sparse_decode_cost(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) the full layers' index scores and attention of one
    decode step need: a lane's live index keys and its selected latents read
    once, its scores and weighted values over the selection in the absorbed
    form's own width a head (the one form a single query can run)."""
    full, _ = _kinds(cfg)
    k = cfg["index_topk"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    H = cfg["num_attention_heads"]
    flops = nbytes = 0.0
    for c in lengths:
        chosen = min(c + 1, k)
        flops += full * (_index_flops(cfg) * (c + 1)
                         + 2.0 * H * (latent + cfg["kv_lora_rank"]) * chosen)
        nbytes += full * 2 * (cfg["index_head_dim"] * (c + 1) + latent * chosen)
    return flops, nbytes


def routed_chunk_cost(cfg: dict, valid: int, held_assignments: float) -> Tuple[float, float]:
    """As `deepseek_v2.routed_chunk_cost`: `held_assignments` rows through
    three matrices each, the held experts' weights once."""
    s = _sizes(cfg)
    routed_layers = layers(cfg) - min(cfg["first_k_dense_replace"], layers(cfg))
    flops = 2.0 * held_assignments * _expert_params(cfg)
    experts = min(routed_layers * s["held"], held_assignments)
    nbytes = 2 * (experts * _expert_params(cfg) + held_assignments * (2 * s["d"] + 3 * s["f"]))
    del valid  # the rows are the counter's: padding is routed nowhere
    return flops, float(nbytes)
