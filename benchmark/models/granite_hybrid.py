"""The Granite 4.0-H family: `GraniteHybridLM` (Mamba-2 mixers and grouped
attention without positions by `layer_types`, a routed layer and a shared
expert on every layer, Granite's multipliers, a tied head), as a
configuration file's `"model": "granite_hybrid"` names it. The keys are the
published `config.json`'s.

What a family gives the serving harness is set out in `models/gpt2.py`.
Here besides:

- **The share.** `num_local_experts` in the file is what this chip HOLDS
  (18, `reduced`); the router keeps the published width, `router_experts`
  (72), and its top-10, and `experts_first` says where the held range
  begins. `vocab_size` is the chip's slice of the vocabulary, which the tied
  head reads whole.
- **A layer's tree differs by its kind**: `block_at` draws a `mamba` or an
  `attention` mixer; every layer has `shared` and `experts`.
- **Weights from `--seed`**, so that the logits spread, the layers and not
  the input token decide them, and the state remembers:
  - the embedding at normal(8 / sqrt(d)): the tied head's logits, divided by
    `logits_scaling` (16), spread by 0.5 over the slice;
  - every projection into a branch at normal(1 / sqrt(d_in)), but q and k at
    `(attention_multiplier sqrt(head_dim) d)^-1/2`, so that the published
    multiplier (1/128) gives scores of spread 1;
  - every projection back into the residual stream (`out_proj`, `out`, every
    `down`) at normal(g / sqrt(d_in)), g = 48 sqrt(10 / L): the branches,
    times `residual_multiplier`, outweigh `embedding_multiplier` times the
    embedding some thirty times over, as layers do in a trained stack; the
    input token then lifts its own logit by about twice the logits' spread,
    where an embedding left to dominate the stream would put it first always;
  - the mixer so that its state carries what a chunk boundary would lose:
    `a = -exp(A_log)` uniform in [1, 16] a head (Mamba-2's own range), `D =
    1`, `dt_bias` the inverse softplus of a step log-uniform in [1e-4, 1e-2]
    a head (a tenth of Mamba-2's), the convolution's taps uniform in
    +-1/sqrt(d_conv), and the columns of `in_proj` that make B and C at three
    times the others' spread: the state's read-out then outweighs the skip
    `D x`, and a mixer's output 64-128 tokens past a dropped state moves by
    28 % (17 % at 192-256; float32, one layer at the published widths, on
    the CPU) where with Mamba-2's steps, `a = -(1 .. heads)` and unit B and
    C it moved by 1.5 % (0.9 %);
  - norm scales normal(1, 0.02).
- **Needed work** counts the matrix products at two FLOPs a weight, the routed
  experts at the expectation `top_k * held / total` a token where no counter
  is given, the SSD at its chunk form's FLOPs (`ssd_token_flops`) in a chunk
  and its recurrence's in a step, attention over every live column. A
  decode step's routed bytes are the experts it touched, from the program's
  own counter (`moe_experts_touched`, summed over the layers), or without it
  the number a step's assignments touch in expectation, spread evenly over
  the router's experts.

d = hidden_size, H = mamba_n_heads of P = mamba_d_head, N = mamba_d_state,
f = intermediate_size (one expert), sf = shared_intermediate_size, V =
vocab_size, L = num_hidden_layers.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "granite_hybrid"
MAMBA, ATTENTION = "mamba", "attention"

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size", "shared_intermediate_size",
         "num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
         "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
         "num_local_experts", "router_experts", "experts_first", "num_experts_per_tok",
         "vocab_size", "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "attention_multiplier", "rms_norm_eps", "max_position_embeddings")


def shape(config: dict) -> dict:
    from elephas_tpu.models import registered_models

    if "granite_hybrid_lm" not in registered_models():
        # before any weight is drawn: an earlier program has no such layers
        raise ValueError("this program has no 'granite_hybrid_lm': no Mamba-2 mixer, "
                         "it cannot serve the family")
    if (config.get("mamba_n_groups") != 1 or config.get("position_embedding_type") != "nope"
            or not config.get("tie_word_embeddings") or config.get("attention_bias")
            or config.get("mamba_proj_bias") or not config.get("mamba_conv_bias")
            or config.get("hidden_act") != "silu"
            or config["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("this family's mixers have one group of B and C, a convolution "
                         "with a bias and no projection bias, heads that tile the inner "
                         "width; its attention no positions and no bias; its head is tied")
    cfg = {k: config[k] for k in _KEYS}
    cfg["layer_types"] = tuple(config["layer_types"][:cfg["num_hidden_layers"]])
    return cfg


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def is_attention(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == ATTENTION


def _kinds(cfg: dict) -> Tuple[int, int]:
    """(Mamba, attention) layers."""
    attn = sum(t == ATTENTION for t in cfg["layer_types"])
    return len(cfg["layer_types"]) - attn, attn


def hyper(cfg: dict) -> dict:
    """What the plain reference cannot read from the shapes of the weights."""
    return {"layer_types": cfg["layer_types"], "eps": float(cfg["rms_norm_eps"]),
            "embedding_multiplier": float(cfg["embedding_multiplier"]),
            "residual_multiplier": float(cfg["residual_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]),
            "attention_multiplier": float(cfg["attention_multiplier"]),
            "heads": cfg["mamba_n_heads"], "d_state": cfg["mamba_d_state"],
            "top_k": cfg["num_experts_per_tok"], "first": cfg["experts_first"]}


def _sizes(cfg: dict) -> dict:
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, H=H, P=P, N=N, inner=H * P, channels=H * P + 2 * N,
                taps=cfg["mamba_d_conv"], Hq=Hq, Hkv=cfg["num_key_value_heads"], hd=d // Hq,
                f=cfg["intermediate_size"], sf=cfg["shared_intermediate_size"],
                held=cfg["num_local_experts"], total=cfg["router_experts"],
                top_k=cfg["num_experts_per_tok"], V=cfg["vocab_size"], L=layers(cfg),
                m=float(cfg["attention_multiplier"]))


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    s = _sizes(cfg)
    return get_model(
        "granite_hybrid_lm", dtype=dtype, vocab_size=s["V"], d_model=s["d"],
        layer_types=cfg["layer_types"], num_heads=s["Hq"], num_kv_heads=s["Hkv"],
        mamba_heads=s["H"], mamba_head_dim=s["P"], d_state=s["N"], d_conv=s["taps"],
        n_experts=s["total"], experts_held=(cfg["experts_first"], s["held"]),
        moe_d_ff=s["f"], shared_d_ff=s["sf"], top_k=s["top_k"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attention_multiplier=s["m"], rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"])


# -- weights (flax `GraniteHybridLM` layout) ------------------------------------


def _drawer(key, dtype, count: int):
    ks = iter(jax.random.split(key, count))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    return normal, uniform


def _gain(L: int) -> float:
    return 48.0 * math.sqrt(10.0 / L)


@functools.partial(jax.jit, static_argnames=("attention", "sizes", "dtype"))
def draw_block(key, attention: bool, sizes: tuple, dtype):
    s = dict(sizes)
    d, f, sf = s["d"], s["f"], s["sf"]
    normal, uniform = _drawer(key, dtype, 24)
    gain = _gain(s["L"])

    def into(d_in):
        return 1.0 / math.sqrt(d_in)

    def back(d_in):
        return gain / math.sqrt(d_in)

    block = {
        "mixer_norm": {"scale": normal((d,), 0.02, 1.0)},
        "mlp_norm": {"scale": normal((d,), 0.02, 1.0)},
        "shared": {"gate": {"kernel": normal((d, sf), into(d))},
                   "up": {"kernel": normal((d, sf), into(d))},
                   "down": {"kernel": normal((sf, d), back(sf))}},
        "experts": {"router": {"kernel": normal((d, s["total"]), into(d))},
                    "gate": normal((s["held"], d, f), into(d)),
                    "up": normal((s["held"], d, f), into(d)),
                    "down": normal((s["held"], f, d), back(f))},
    }
    if attention:
        qk = (s["m"] * math.sqrt(s["hd"]) * d) ** -0.5
        block["attention"] = {
            "q": {"kernel": normal((d, s["Hq"], s["hd"]), qk)},
            "k": {"kernel": normal((d, s["Hkv"], s["hd"]), qk)},
            "v": {"kernel": normal((d, s["Hkv"], s["hd"]), into(d))},
            "out": {"kernel": normal((s["Hq"], s["hd"], d), back(s["Hq"] * s["hd"]))},
        }
        return block
    H, inner, channels = s["H"], s["inner"], s["channels"]
    step = jnp.exp(uniform((H,), math.log(1e-4), math.log(1e-2)))
    bound = 1.0 / math.sqrt(s["taps"])
    # the columns that make B and C three times the others': the state's
    # read-out outweighs the skip
    widths = jnp.where((jnp.arange(inner + channels + H) >= 2 * inner)
                       & (jnp.arange(inner + channels + H) < inner + channels), 3.0, 1.0)
    block["mamba"] = {
        "in_proj": {"kernel": (normal((d, inner + channels + H), into(d)) * widths
                               ).astype(dtype)},
        "conv_kernel": uniform((s["taps"], channels), -bound, bound).astype(dtype),
        "conv_bias": normal((channels,), 0.02),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "A_log": jnp.log(uniform((H,), 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": normal((inner,), 0.02, 1.0),
        "out_proj": {"kernel": normal((inner, d), back(inner))},
    }
    return block


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def draw_top(key, d: int, vocab: int, dtype):
    normal, _ = _drawer(key, dtype, 4)
    return {"tok_embed": {"embedding": normal((vocab, d), 8.0 / math.sqrt(d))},
            "final_norm": {"scale": normal((d,), 0.02, 1.0)}}


def block_at(seed: int, layer: int, cfg: dict, dtype):
    return draw_block(jax.random.fold_in(seed_key(seed), layer + 1), is_attention(cfg, layer),
                      tuple(sorted(_sizes(cfg).items())), dtype)


def top_at(seed: int, cfg: dict, dtype):
    """The embedding and the final norm, and beside them, for the plain
    reference alone, `hyper`: plain numbers, no weight."""
    return {**draw_top(seed_key(seed), cfg["hidden_size"], cfg["vocab_size"], dtype),
            "hyper": hyper(cfg)}


def params(seed: int, cfg: dict, dtype) -> dict:
    out = {k: v for k, v in top_at(seed, cfg, dtype).items() if k != "hyper"}
    for layer in range(layers(cfg)):
        out[f"Layer_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


# -- sizes and needed work ------------------------------------------------------


def _mixer_params(cfg: dict) -> Tuple[int, int]:
    """(matrix, other) parameters of one Mamba-2 mixer."""
    s = _sizes(cfg)
    matrices = s["d"] * (s["inner"] + s["channels"] + s["H"]) + s["inner"] * s["d"]
    return matrices, (s["taps"] + 1) * s["channels"] + 3 * s["H"] + s["inner"]


def _attention_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return 2 * s["d"] * s["Hq"] * s["hd"] + 2 * s["d"] * s["Hkv"] * s["hd"]


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _count(cfg: dict, experts: int, depth: int, vocab: int, types=None) -> int:
    s = _sizes(cfg)
    types = cfg["layer_types"] if types is None else types
    attn = sum(t == ATTENTION for t in types[:depth])
    mixer = sum(_mixer_params(cfg))
    ffn = 3 * s["d"] * s["sf"] + s["d"] * s["total"] + experts * _expert_params(cfg) + 2 * s["d"]
    return (depth - attn) * mixer + attn * _attention_params(cfg) + depth * ffn + \
        vocab * s["d"] + s["d"]


def param_count(cfg: dict, published: Optional[dict] = None) -> int:
    """As the configuration is cut; with `published` (its `num_hidden_layers`,
    `num_local_experts`, `vocab_size` and `layer_types` before the cut), the
    whole model."""
    if published is not None:
        return _count(cfg, published["num_local_experts"], published["num_hidden_layers"],
                      published["vocab_size"], published["layer_types"])
    return _count(cfg, cfg["num_local_experts"], layers(cfg), cfg["vocab_size"])


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Every weight one forward step reads: the layers, the final norm and
    the embedding, which the tied head reads whole."""
    return param_count(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = _sizes(cfg)
    return 2 * _kinds(cfg)[1] * s["Hkv"] * s["hd"] * bytes_per_value


def ssd_state_bytes(cfg: dict) -> int:
    """One slot's SSD state in one mixer: (H, P, N) float32."""
    s = _sizes(cfg)
    return s["H"] * s["P"] * s["N"] * 4


def state_bytes_per_slot(cfg: dict, bytes_per_value: int = 2) -> int:
    """A slot's SSD states (float32) and convolution states (as served,
    every channel of xBC) over the Mamba layers."""
    s = _sizes(cfg)
    return _kinds(cfg)[0] * (ssd_state_bytes(cfg)
                             + (s["taps"] - 1) * s["channels"] * bytes_per_value)


def ssd_token_flops(cfg: dict, step: bool = False) -> float:
    """A token's FLOPs in one mixer's SSD. The chunk form: its row of a
    sub-chunk's C B^T (shared by every head) and, a head, of the masked
    product with delta x (on average (c + 1) / 2 columns), the carried
    state's product with C and the state's update. A step (`step`): the
    state decayed and added to (three a state element) and read by C (two)."""
    s = _sizes(cfg)
    H, P, N, c = s["H"], s["P"], s["N"], cfg["mamba_chunk_size"]
    if step:
        return 5.0 * H * P * N
    return N * (c + 1.0) + H * (P * (c + 1.0) + 4.0 * N * P)


def _token_flops(cfg: dict, routed_share: Optional[float] = None) -> float:
    """Matrix-product FLOPs one token needs in every layer, two a weight:
    the mixers' or attention's projections, the shared expert, the router,
    and the routed experts at `routed_share` experts a token a layer (the
    expectation `top_k * held / total` where None)."""
    s = _sizes(cfg)
    mamba, attn = _kinds(cfg)
    if routed_share is None:
        routed_share = s["top_k"] * s["held"] / s["total"]
    ffn = 3 * s["d"] * s["sf"] + s["d"] * s["total"] + routed_share * _expert_params(cfg)
    return 2.0 * (mamba * _mixer_params(cfg)[0] + attn * _attention_params(cfg)
                  + layers(cfg) * ffn)


def _expected_touched(cfg: dict, lanes: int) -> float:
    """Held experts a decode step of `lanes` tokens touches in expectation,
    summed over the layers, with the assignments spread evenly."""
    s = _sizes(cfg)
    miss = (1.0 - 1.0 / s["total"]) ** (lanes * s["top_k"])
    return layers(cfg) * s["held"] * (1.0 - miss)


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position through the tied head; every weight read
    once (a chunk of hundreds of tokens leaves no held expert without one),
    the slot's live K/V read and the chunk's written, each mixer's states
    read and written."""
    s = _sizes(cfg)
    mamba, attn = _kinds(cfg)
    positions = valid * start + valid * (valid + 1) / 2.0  # sum of (p + 1)
    flops = (_token_flops(cfg) * valid + mamba * ssd_token_flops(cfg) * valid
             + attn * 4.0 * s["Hq"] * s["hd"] * positions + 2.0 * s["d"] * s["V"])
    nbytes = (weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + valid)
              + 2 * state_bytes_per_slot(cfg) + 2 * s["d"] * valid)
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int],
                touched: Optional[float] = None) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step. Bytes: every weight but the routed
    experts' once, the weights of the `touched` experts (summed over the
    layers: the program's `moe_experts_touched`; where it is not given, the
    expectation for as many lanes), every lane's states read and written,
    its live K/V and one new column."""
    lengths = list(lengths)
    s = _sizes(cfg)
    mamba, attn = _kinds(cfg)
    per_lane = (_token_flops(cfg) + mamba * ssd_token_flops(cfg, step=True)
                + 2.0 * s["d"] * s["V"])
    flops = sum(per_lane + attn * 4.0 * s["Hq"] * s["hd"] * (c + 1) for c in lengths)
    if touched is None:
        touched = _expected_touched(cfg, len(lengths))
    fixed = param_count(cfg) - layers(cfg) * s["held"] * _expert_params(cfg)
    nbytes = (2 * (fixed + touched * _expert_params(cfg))
              + kv_bytes_per_token(cfg) * (sum(lengths) + len(lengths))
              + 2 * state_bytes_per_slot(cfg) * len(lengths))
    return flops, float(nbytes)


def ssd_chunk_cost(cfg: dict, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the SSD of one chunk of `valid` tokens needs, every
    mixer: the chunk form's FLOPs; delta x read and y written in float32,
    B, C and the running sums of delta a (in both layouts) read, the state
    read and written once."""
    s = _sizes(cfg)
    mamba = _kinds(cfg)[0]
    flops = mamba * ssd_token_flops(cfg) * valid
    nbytes = mamba * (4 * valid * (2 * s["inner"] + 2 * s["N"] + 2 * s["H"])
                      + 2 * ssd_state_bytes(cfg))
    return flops, float(nbytes)


def ssd_step_cost(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) the SSD of one decode step needs, every mixer: each
    lane's state read and written once, its delta x, decay, B and C read
    and y written in float32."""
    s = _sizes(cfg)
    mamba, lanes = _kinds(cfg)[0], len(list(lengths))
    flops = mamba * lanes * ssd_token_flops(cfg, step=True)
    nbytes = mamba * lanes * (2 * ssd_state_bytes(cfg) + 4 * (3 * s["inner"] + 2 * s["N"]))
    return flops, float(nbytes)


def routed_chunk_cost(cfg: dict, valid: int, held_assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the grouped products of one chunk need over every
    layer: `held_assignments` rows (the program's `moe_assignments_held`,
    summed over the layers) through three matrices each; the weights of the
    held experts once (of as many as there are rows, where those are fewer),
    every row read and written."""
    s = _sizes(cfg)
    flops = 2.0 * held_assignments * _expert_params(cfg)
    experts = min(layers(cfg) * s["held"], held_assignments)
    nbytes = 2 * (experts * _expert_params(cfg) + held_assignments * (2 * s["d"] + 3 * s["f"]))
    del valid  # the rows are the counter's: padding is routed nowhere
    return flops, float(nbytes)
