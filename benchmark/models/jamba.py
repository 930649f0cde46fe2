"""The Jamba family: `JambaLM` (Mamba-1 mixers with an attention layer where
`i % attn_layer_period == attn_layer_offset`, RMSNorm, gated feed-forwards,
grouped K/V heads, no positions, a tied head), as a configuration file's
`"model": "jamba"` names it. The keys are the published `config.json`'s.

What a family gives the serving harness is set out in `models/gpt2.py`.
Here besides: a layer's tree differs by its kind (`block_at` draws a `mamba`
or an `attention` tree), and the weights from `--seed` leave the recurrence
a memory: `A_log = log(1 .. d_state)` for every channel, `D = 1`, `b_dt` the
inverse softplus of a step drawn log-uniformly between 1e-3 and 1e-1 and
the convolution's taps uniform in +-1/sqrt(d_conv) (Mamba's own
initialisation), every matrix normal(0.02) with the projections back into
the residual stream scaled by 1/sqrt(2 L), as `gpt2.py` draws them. (0.02
at the published width of 2,560; 0.02 sqrt(2560 / d) at another, so that a
rehearsal's layers weigh against its embedding what the real ones do: at
width 32 and 0.02 the tied head would read the input token back.) With
`A_log` near 0 the state would forget in ten tokens, and a chunk program
that dropped the state it was handed would still pass `correct`.

d = hidden_size, di = mamba_expand * d, n = mamba_d_state, r = mamba_dt_rank,
f = intermediate_size, V = vocab_size, L = num_hidden_layers.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "jamba"

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "vocab_size", "mamba_d_state", "mamba_d_conv",
         "mamba_dt_rank", "mamba_expand", "attn_layer_period", "attn_layer_offset",
         "rms_norm_eps", "max_position_embeddings")


def shape(config: dict) -> dict:
    cfg = {k: config[k] for k in _KEYS}
    if config.get("num_experts", 1) != 1 or not config.get("tie_word_embeddings", True):
        raise ValueError("this family is the dense, tied one: num_experts 1")
    return cfg


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return dict(d=d, di=cfg["mamba_expand"] * d, n=cfg["mamba_d_state"],
                taps=cfg["mamba_d_conv"], r=cfg["mamba_dt_rank"],
                f=cfg["intermediate_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=d // cfg["num_attention_heads"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def flax_module(cfg: dict, dtype: str):
    from elephas_tpu.models import get_model

    s = _sizes(cfg)
    return get_model("jamba_lm", dtype=dtype, vocab_size=s["V"], d_model=s["d"],
                     num_layers=s["L"], num_heads=s["heads"], num_kv_heads=s["kv"],
                     d_ff=s["f"], d_state=s["n"], d_conv=s["taps"], dt_rank=s["r"],
                     expand=cfg["mamba_expand"], attn_period=cfg["attn_layer_period"],
                     attn_offset=cfg["attn_layer_offset"], rms_eps=cfg["rms_norm_eps"],
                     max_seq_len=cfg["max_position_embeddings"])


# -- weights (flax `JambaLM` layout) ----------------------------------------


def _std(d: int) -> float:
    return 0.02 * math.sqrt(2560 / d)


def _drawer(key, dtype):
    ks = iter(jax.random.split(key, 24))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    return normal, uniform


@functools.partial(jax.jit, static_argnames=("attention", "sizes", "dtype"))
def draw_block(key, attention: bool, sizes: tuple, dtype):
    s = dict(sizes)
    d, di, n, f = s["d"], s["di"], s["n"], s["f"]
    normal, uniform = _drawer(key, dtype)
    std = _std(d)
    out_std = std / math.sqrt(2 * s["L"])
    block = {
        "mixer_norm": {"scale": normal((d,), 0.02, 1.0)},
        "mlp_norm": {"scale": normal((d,), 0.02, 1.0)},
        "gate": {"kernel": normal((d, f), std)},
        "up": {"kernel": normal((d, f), std)},
        "down": {"kernel": normal((f, d), out_std)},
    }
    if attention:
        block["attention"] = {
            "q": {"kernel": normal((d, s["heads"], s["hd"]), std)},
            "k": {"kernel": normal((d, s["kv"], s["hd"]), std)},
            "v": {"kernel": normal((d, s["kv"], s["hd"]), std)},
            "out": {"kernel": normal((s["heads"], s["hd"], d), out_std)},
        }
        return block
    step = jnp.exp(uniform((di,), math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(s["taps"])
    block["mamba"] = {
        "in_proj": {"kernel": normal((d, 2 * di), std)},
        "conv_kernel": uniform((s["taps"], di), -bound, bound).astype(dtype),
        "conv_bias": normal((di,), 0.02),
        "x_proj": {"kernel": normal((di, s["r"] + 2 * n), std)},
        "dt_norm": {"scale": normal((s["r"],), 0.02, 1.0)},
        "b_norm": {"scale": normal((n,), 0.02, 1.0)},
        "c_norm": {"scale": normal((n,), 0.02, 1.0)},
        "dt_proj": {"kernel": normal((s["r"], di), std),
                    # softplus(bias) = step
                    "bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype)},
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, di)).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": {"kernel": normal((di, d), out_std)},
    }
    return block


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def draw_top(key, d: int, vocab: int, dtype):
    normal, _ = _drawer(key, dtype)
    return {"tok_embed": {"embedding": normal((vocab, d), _std(d))},
            "final_norm": {"scale": normal((d,), 0.02, 1.0)}}


def block_at(seed: int, layer: int, cfg: dict, dtype):
    return draw_block(jax.random.fold_in(seed_key(seed), layer + 1),
                      is_attention(cfg, layer), tuple(sorted(_sizes(cfg).items())), dtype)


def top_at(seed: int, cfg: dict, dtype):
    return draw_top(seed_key(seed), cfg["hidden_size"], cfg["vocab_size"], dtype)


def params(seed: int, cfg: dict, dtype) -> dict:
    out = dict(top_at(seed, cfg, dtype))
    for layer in range(layers(cfg)):
        out[f"Layer_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


# -- needed work ------------------------------------------------------------


def _matrix_params(cfg: dict) -> Tuple[int, int]:
    """Weights of the matrix products of (a Mamba layer, an attention
    layer), the feed-forward's among them."""
    s = _sizes(cfg)
    d, di, n, r = s["d"], s["di"], s["n"], s["r"]
    mixer = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    attention = 2 * d * s["heads"] * s["hd"] + 2 * d * s["kv"] * s["hd"]
    return mixer + 3 * d * s["f"], attention + 3 * d * s["f"]


def _layer_params(cfg: dict) -> Tuple[int, int]:
    """Parameters of (a Mamba layer, an attention layer): the matrices, the
    two norms, and the mixer's vectors (the convolution's taps and bias,
    the step's bias, `A_log`, `D`, the three inner norms)."""
    s = _sizes(cfg)
    di, n = s["di"], s["n"]
    mamba, attention = _matrix_params(cfg)
    vectors = s["taps"] * di + di + di + n * di + di + s["r"] + 2 * n
    return mamba + vectors + 2 * s["d"], attention + 2 * s["d"]


def _layer_counts(cfg: dict) -> Tuple[int, int]:
    attn = sum(is_attention(cfg, i) for i in range(layers(cfg)))
    return layers(cfg) - attn, attn


def param_count(cfg: dict) -> int:
    (mamba, attn), (n_mamba, n_attn) = _layer_params(cfg), _layer_counts(cfg)
    return n_mamba * mamba + n_attn * attn + cfg["vocab_size"] * cfg["hidden_size"] + \
        cfg["hidden_size"]


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Every weight one forward step reads: the layers, the final norm and
    the embedding, which the tied head reads whole."""
    return param_count(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = _sizes(cfg)
    return 2 * _layer_counts(cfg)[1] * s["kv"] * s["hd"] * bytes_per_value


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's recurrent state (float32) and convolution state (as served,
    2 bytes) over the Mamba layers."""
    s = _sizes(cfg)
    return _layer_counts(cfg)[0] * (s["di"] * s["n"] * 4 + s["di"] * (s["taps"] - 1) * 2)


def _token_flops(cfg: dict) -> Tuple[float, float]:
    """FLOPs one token needs in every layer, attention's scores apart, and
    the factor of (position + 1) that those scores add: matrix products at
    two a weight; the scan at seven a (channel, state) (`delta A`, `exp`,
    `. h`, `delta u . B`, the sum, `C . h`, its sum) and four a channel
    (`delta u`, `D u`, two sums); the convolution at two a tap."""
    s = _sizes(cfg)
    (mamba, attn), (n_mamba, n_attn) = _matrix_params(cfg), _layer_counts(cfg)
    di, n = s["di"], s["n"]
    scan = 7.0 * di * n + 4.0 * di + 2.0 * s["taps"] * di
    flat = n_mamba * (2.0 * mamba + scan) + n_attn * 2.0 * attn
    return flat, n_attn * 4.0 * s["heads"] * s["hd"]


def chunk_cost(cfg: dict, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one prefill chunk needs: `valid` tokens from column
    `start`, one sampled position through the tied head, the weights read
    once, the slot's live K/V read and the chunk's written, the slot's
    state read and written."""
    flat, per_key = _token_flops(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    positions = valid * start + valid * (valid + 1) / 2.0  # sum of (p + 1)
    flops = flat * valid + per_key * positions + 2.0 * d * V
    nbytes = (weight_bytes(cfg) + kv_bytes_per_token(cfg) * (start + valid)
              + 2 * d * valid + 2 * state_bytes_per_slot(cfg))
    return flops, float(nbytes)


def decode_cost(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for lanes whose caches hold
    `lengths` columns before the step: the weights once, every lane's live
    K/V and one new column each, every lane's state read and written."""
    lengths = list(lengths)
    flat, per_key = _token_flops(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    flops = sum(flat + per_key * (c + 1) + 2.0 * d * V for c in lengths)
    nbytes = (weight_bytes(cfg) + kv_bytes_per_token(cfg) * (sum(lengths) + len(lengths))
              + 2 * state_bytes_per_slot(cfg) * len(lengths))
    return flops, float(nbytes)


def scan_cost(cfg: dict, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the selective scans of one chunk of `valid` tokens
    need, all Mamba layers: the arithmetic above; `u`, `delta` read and `y`
    written in float32, `B` and `C` read, the state read and written."""
    s = _sizes(cfg)
    di, n = s["di"], s["n"]
    n_mamba = _layer_counts(cfg)[0]
    flops = n_mamba * valid * (7.0 * di * n + 4.0 * di)
    nbytes = n_mamba * (valid * (3 * di + 2 * n) * 4 + 2 * di * n * 4)
    return flops, float(nbytes)
