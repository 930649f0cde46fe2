"""Weights and rows made on the device from `--seed`.

The same functions feed the program and the plain reference, so neither
takes anything the other has made. A model's weights are drawn layer by
layer by one jitted function (one compile, one dispatch a layer), in the
type they are served or trained in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole number up to 2**62: `PRNGKey` alone takes 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- GPT-2 (flax `TransformerLM` layout) -----------------------------------


@functools.partial(jax.jit, static_argnames=("d", "heads", "layers", "dtype"))
def gpt2_block(key, d: int, heads: int, layers: int, dtype):
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    hd, out_std = d // heads, 0.02 / math.sqrt(2 * layers)
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
def gpt2_top(key, d: int, vocab: int, positions: int, dtype):
    ks = iter(jax.random.split(key, 6))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    return {
        "tok_embed": {"embedding": normal((vocab, d), 0.02)},
        "pos_embed": normal((positions, d), 0.01),
        "LayerNorm_0": {"scale": normal((d,), 0.02, 1.0), "bias": normal((d,), 0.02)},
        "lm_head": {"kernel": normal((d, vocab), 0.02), "bias": normal((vocab,), 0.02)},
    }


def gpt2_block_at(seed: int, layer: int, cfg: dict, dtype):
    return gpt2_block(jax.random.fold_in(seed_key(seed), layer + 1), cfg["n_embd"],
                      cfg["n_head"], cfg["n_layer"], dtype)


def gpt2_top_at(seed: int, cfg: dict, dtype):
    return gpt2_top(seed_key(seed), cfg["n_embd"], cfg["vocab_size"],
                    cfg["n_positions"], dtype)


def gpt2_params(seed: int, cfg: dict, dtype) -> dict:
    params = dict(gpt2_top_at(seed, cfg, dtype))
    for layer in range(cfg["n_layer"]):
        params[f"Block_{layer}"] = gpt2_block_at(seed, layer, cfg, dtype)
    return params


# -- ResNet-18 (flax `ResNet` layout) and its rows --------------------------


def resnet18_variables(seed: int, cfg: dict):
    """(`params`, `batch_stats`) in float32, one jitted call: He-normal
    convolutions, BatchNorm scale near 1 and bias near 0 (drawn, so that no
    leaf is blind to its gradient), a small dense head."""
    from . import counts

    layers = counts.resnet18_layers(cfg)

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 4 * len(layers)))

        def normal(shape, std, mean=0.0):
            return mean + std * jax.random.normal(next(ks), shape, jnp.float32)

        def conv(l):
            fan_in = l["k"] * l["k"] * l["cin"]
            return {"kernel": normal((l["k"], l["k"], l["cin"], l["cout"]),
                                     math.sqrt(2.0 / fan_in))}

        def norm(l):
            return {"scale": normal((l["cout"],), 0.05, 1.0), "bias": normal((l["cout"],), 0.05)}

        def stats(l):
            return {"mean": jnp.zeros((l["cout"],)), "var": jnp.ones((l["cout"],))}

        params = {"Conv_0": conv(layers[0]), "BatchNorm_0": norm(layers[0])}
        batch_stats = {"BatchNorm_0": stats(layers[0])}
        i, block = 1, 0
        for blocks in cfg["stage_sizes"]:
            for _ in range(blocks):
                name, p, s, n = f"ResidualBlock_{block}", {}, {}, 0
                while i < len(layers) - 1 and n < 3:
                    if n == 2 and layers[i]["k"] != 1:
                        break
                    p[f"Conv_{n}"], p[f"BatchNorm_{n}"] = conv(layers[i]), norm(layers[i])
                    s[f"BatchNorm_{n}"] = stats(layers[i])
                    i, n = i + 1, n + 1
                params[name], batch_stats[name] = p, s
                block += 1
        head = layers[-1]
        params["Dense_0"] = {"kernel": normal((head["cin"], head["cout"]),
                                              1.0 / math.sqrt(head["cin"])),
                             "bias": normal((head["cout"],), 0.01)}
        return params, batch_stats

    return make(seed_key(seed))


def separable_rows(seed: int, cfg: dict):
    """`rows` images and one-hot labels, made on the device in one call:
    a pattern a class plus noise, so that a loss can fall; no two rows alike."""
    n, side, ch, classes = cfg["rows"], cfg["image_size"], cfg["channels"], cfg["num_classes"]

    @jax.jit
    def make(key):
        kp, kl, kn = jax.random.split(key, 3)
        patterns = jax.random.normal(kp, (classes, side, side, ch), jnp.float32)
        labels = jax.random.randint(kl, (n,), 0, classes)
        x = patterns[labels] + 0.5 * jax.random.normal(kn, (n, side, side, ch), jnp.float32)
        return x, jax.nn.one_hot(labels, classes, dtype=jnp.float32)

    return make(jax.random.fold_in(seed_key(seed), 7))
