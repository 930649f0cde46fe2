"""Weights and rows made on the device from `--seed`.

The same functions feed the program and the plain reference, so neither
takes anything the other has made. A language model's weights are its
family's (`models/<family>.py`), drawn layer by layer by one jitted
function (one compile, one dispatch a layer), in the type they are served
in; here are the key every family starts from and the training cell's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole number up to 2**62: `PRNGKey` alone takes 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


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
