"""The DeepSeek-V2 family: `LatentMoELM` (latent attention with a decoupled
rotary key, YaRN positions, a dense first layer, then routed experts beside
shared ones, RMSNorm, an untied head), as a configuration file's `"model":
"deepseek_v2"` names it. The keys are the published `config.json`'s.

What a family gives the serving harness is set out in `models/gpt2.py`.
Here besides:

- **The share.** `n_routed_experts` in the file is what this chip HOLDS
  (40, `reduced`); the router keeps the published width, `router_experts`
  (160), its 8 groups, top-3 groups and top-6, and `experts_first` says
  where the held range begins. `vocab_size` is the chip's slice of the
  vocabulary (embedding rows and head columns alike).
- **A layer's tree differs by its kind**: `block_at` draws a dense
  feed-forward for the first `first_k_dense_replace` layers and `shared` +
  `experts` (the router with them) for the rest.
- **Weights from `--seed`**: every matrix normal(0.02), the projections
  back into the residual stream (`out`, every `down`) scaled by 1/sqrt(2 L),
  as `gpt2.py` draws them (0.02 at the published width of 5,120; 0.02
  sqrt(5120 / d) at another, so that a rehearsal's layers weigh against its
  embedding what the real ones do); norm scales normal(1, 0.02). The router
  is drawn like the rest, normal(0.02): a token's scores then have a
  standard deviation of 1.4 over the 160, its first choice weighs about
  1.3 and its sixth 0.27 (`routed_scaling_factor` 16 times `p`), and the gap
  between its sixth and seventh score is a tenth of that deviation. That
  gap over the error of the router's input is the same at every scale of
  the router (both grow with it), so no scale buys agreement: what the scale
  sets is how much a flipped sixth expert weighs, and 0.27 of one expert
  among six beside the shared ones is a step in the logits that the limit
  of `correct` is set knowing (PERF.md has the share of (token, layer)
  pairs that flip).
- **Needed work** counts attention at the EXPANDED form, keys and values a
  head (`(qk_nope + qk_rope + v_head) * 2` FLOPs a head, a query and a
  live column: 81,920 a layer at the published sizes), whichever form the
  program runs: the absorbed form the serving path runs does 278,528, and
  reads so much lower against this count. The routed experts' FLOPs are at
  the expectation `top_k * held / total` experts a token (1.5) where the
  caller has no counter, and a decode step's routed bytes are the weights of
  the experts that got a token, `touched`, from the program's own counter
  (`moe_experts_touched`); without it, of none: the fewest a step could
  touch.

d = hidden_size, H = num_attention_heads, L = num_hidden_layers, f =
moe_intermediate_size, V = vocab_size.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "deepseek_v2"

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "vocab_size", "first_k_dense_replace", "n_routed_experts",
         "router_experts", "experts_first", "n_shared_experts", "moe_intermediate_size",
         "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
         "rope_theta", "rope_scaling", "rms_norm_eps", "max_position_embeddings")


def shape(config: dict) -> dict:
    from elephas_tpu.models import registered_models

    if "latent_moe_lm" not in registered_models():  # before any weight is drawn
        raise ValueError("this program has no 'latent_moe_lm' to serve the family with")
    cfg = {k: config[k] for k in _KEYS}
    if (config.get("topk_method") != "group_limited_greedy"
            or config.get("scoring_func") != "softmax" or config.get("norm_topk_prob")
            or config.get("moe_layer_freq") != 1 or config.get("tie_word_embeddings")
            or config.get("attention_bias")):
        raise ValueError("this family routes group_limited_greedy over a softmax, "
                         "not renormalised, every layer past the dense ones; untied, "
                         "no bias")
    return cfg


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def is_routed(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def rope(cfg: dict) -> Optional[tuple]:
    s = cfg["rope_scaling"]
    if s is None:
        return None
    return (float(cfg["rope_theta"]), float(s["factor"]),
            float(s["original_max_position_embeddings"]), float(s["beta_fast"]),
            float(s["beta_slow"]), float(s["mscale"]), float(s["mscale_all_dim"]))


def hyper(cfg: dict) -> dict:
    """What the plain reference cannot read from the shapes of the weights."""
    return {"n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
            "top_k": cfg["num_experts_per_tok"],
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
            "first": cfg["experts_first"], "rope": rope(cfg)}


def _sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                qr=cfg["q_lora_rank"], r=cfg["kv_lora_rank"],
                nope=cfg["qk_nope_head_dim"], pe=cfg["qk_rope_head_dim"],
                v=cfg["v_head_dim"], ff=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
                total=cfg["router_experts"], shared=cfg["n_shared_experts"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    s = _sizes(cfg)
    return get_model(
        "latent_moe_lm", dtype=dtype, vocab_size=s["V"], d_model=s["d"],
        num_layers=s["L"], num_heads=s["H"], q_lora_rank=s["qr"], kv_lora_rank=s["r"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["pe"], v_head_dim=s["v"],
        d_ff=s["ff"], first_dense=cfg["first_k_dense_replace"],
        n_routed_experts=s["total"], experts_held=(cfg["experts_first"], s["held"]),
        n_shared_experts=s["shared"], moe_d_ff=s["f"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), rope=rope(cfg),
        rms_eps=cfg["rms_norm_eps"], max_seq_len=cfg["max_position_embeddings"])


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
    normal = _drawer(key, dtype, 24)
    std = _std(d)
    out_std = std / math.sqrt(2 * s["L"])
    block = {
        "attn_norm": {"scale": normal((d,), 0.02, 1.0)},
        "ffn_norm": {"scale": normal((d,), 0.02, 1.0)},
        "attention": {
            "q_a": {"kernel": normal((d, s["qr"]), std)},
            "q_a_norm": {"scale": normal((s["qr"],), 0.02, 1.0)},
            "q_b": {"kernel": normal((s["qr"], H, s["nope"] + s["pe"]), std)},
            "kv_a": {"kernel": normal((d, s["r"] + s["pe"]), std)},
            "kv_a_norm": {"scale": normal((s["r"],), 0.02, 1.0)},
            "kv_b": normal((s["r"], H, s["nope"] + s["v"]), std),
            "out": {"kernel": normal((H, s["v"], d), out_std)},
        },
    }
    if not routed:
        return {**block, **_gated(normal, d, s["ff"], std, out_std)}
    block["shared"] = _gated(normal, d, s["shared"] * f, std, out_std)
    block["experts"] = {
        "router": {"kernel": normal((d, s["total"]), std)},
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
                      tuple(sorted(_sizes(cfg).items())), dtype)


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


def _attention_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return (s["d"] * s["qr"] + s["qr"] * s["H"] * (s["nope"] + s["pe"])
            + s["d"] * (s["r"] + s["pe"]) + s["r"] * s["H"] * (s["nope"] + s["v"])
            + s["H"] * s["v"] * s["d"])


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_counts(cfg: dict) -> Tuple[int, int]:
    routed = sum(is_routed(cfg, i) for i in range(layers(cfg)))
    return layers(cfg) - routed, routed


def _count(cfg: dict, experts: int, depth: int, vocab: int) -> int:
    """Parameters with `experts` routed experts a routed layer, `depth`
    layers of which `first_k_dense_replace` are dense, and `vocab` rows."""
    s = _sizes(cfg)
    d = s["d"]
    shell = _attention_params(cfg) + s["qr"] + s["r"] + 2 * d  # four norms
    dense = shell + 3 * d * s["ff"]
    routed = (shell + s["shared"] * _expert_params(cfg) + d * s["total"]
              + experts * _expert_params(cfg))
    first = min(cfg["first_k_dense_replace"], depth)
    return first * dense + (depth - first) * routed + 2 * vocab * d + d


def param_count(cfg: dict, published: Optional[dict] = None) -> int:
    """As the configuration is cut; with `published` (its `num_hidden_layers`,
    `n_routed_experts` and `vocab_size` before the cut), the whole model."""
    if published is not None:
        return _count(cfg, published["n_routed_experts"], published["num_hidden_layers"],
                      published["vocab_size"])
    return _count(cfg, cfg["n_routed_experts"], layers(cfg), cfg["vocab_size"])


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    return param_count(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """The latent of every layer: `kv_lora_rank + qk_rope_head_dim` values."""
    return layers(cfg) * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def _score_flops(cfg: dict) -> float:
    """FLOPs a query spends on one live column in one layer, keys and
    values a head: the form the model is published in."""
    s = _sizes(cfg)
    return 2.0 * s["H"] * (s["nope"] + s["pe"] + s["v"])


def _token_flops(cfg: dict, routed_share: Optional[float] = None) -> float:
    """Matrix-product FLOPs one token needs in every layer, two a weight:
    attention's five projections, the dense feed-forward or the shared
    experts and the router, and the routed experts at `routed_share` experts
    a token a layer (the expectation `top_k * held / total` where None)."""
    s = _sizes(cfg)
    dense, routed = _layer_counts(cfg)
    if routed_share is None:
        routed_share = cfg["num_experts_per_tok"] * s["held"] / s["total"]
    per_routed = (s["shared"] + routed_share) * _expert_params(cfg) + s["d"] * s["total"]
    return 2.0 * (layers(cfg) * _attention_params(cfg) + dense * 3 * s["d"] * s["ff"]
                  + routed * per_routed)


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position through the head, every weight read once
    (a chunk of a thousand tokens leaves no held expert without one), the
    slot's live latent columns read and the chunk's written."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    positions = valid * start + valid * (valid + 1) / 2.0  # sum of (p + 1)
    flops = (_token_flops(cfg) * valid + layers(cfg) * _score_flops(cfg) * positions
             + 2.0 * d * V)
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + valid) + 2 * d * valid
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int],
                touched: Optional[float] = None) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step. Bytes: every weight but the routed
    experts' once (the embedding's rows but for the lanes'), the weights of
    the `touched` experts (summed over the routed layers: the program's
    `moe_experts_touched`; none where it is not given, the fewest a step
    could touch), every lane's live latent and one new column each."""
    lengths = list(lengths)
    s = _sizes(cfg)
    d, V = s["d"], s["V"]
    flops = sum(_token_flops(cfg) + layers(cfg) * _score_flops(cfg) * (c + 1)
                + 2.0 * d * V for c in lengths)
    routed_layers = _layer_counts(cfg)[1]
    fixed = param_count(cfg) - routed_layers * s["held"] * _expert_params(cfg) - V * d
    nbytes = (2 * (fixed + (touched or 0.0) * _expert_params(cfg) + len(lengths) * d)
              + kv_bytes_per_token(cfg) * (sum(lengths) + len(lengths)))
    return flops, float(nbytes)


def mla_chunk_attention_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the attention of one chunk needs over all layers, the
    projections apart: the scores and weighted values of `valid` queries
    from column `start`, keys and values a head; the queries read and the
    result written a head, the slot's live latent columns read once."""
    s = _sizes(cfg)
    positions = valid * start + valid * (valid + 1) / 2.0
    flops = layers(cfg) * _score_flops(cfg) * positions
    nbytes = layers(cfg) * 2 * (
        (start + valid) * (s["r"] + s["pe"])
        + valid * s["H"] * (s["nope"] + s["pe"] + s["v"]))
    return flops, float(nbytes)


def routed_chunk_cost(cfg: dict, valid: int, held_assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the grouped products of one chunk need over all routed
    layers: `held_assignments` rows (the program's `moe_assignments_held`,
    summed over the layers) through three matrices each; the weights of the
    held experts once (of as many as there are rows, where those are fewer),
    every row read and written."""
    s = _sizes(cfg)
    routed_layers = _layer_counts(cfg)[1]
    flops = 2.0 * held_assignments * _expert_params(cfg)
    experts = min(routed_layers * s["held"], held_assignments)
    nbytes = 2 * (experts * _expert_params(cfg) + held_assignments * (2 * s["d"] + 3 * s["f"]))
    del valid  # the rows are the counter's: padding is routed nowhere
    return flops, float(nbytes)
