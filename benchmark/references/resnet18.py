"""Plain reference: CIFAR-style ResNet-18 (3x3 stem, basic blocks of two
3x3 convolutions, a 1x1 projection where the shape changes, BatchNorm with
batch statistics, global mean pool, dense head), its softmax cross-entropy
and SGD with momentum, in `jax.numpy` and float32 with products at
`highest` precision. Nothing of the program.

`quant` is the control's hook, applied to both operands of every
convolution and of the head's product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def identity(x):
    return x


def fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def conv(x, kernel, stride, quant):
    return jax.lax.conv_general_dilated(
        quant(x), quant(kernel), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")


def batch_norm(x, p, s, momentum=0.9, eps=1e-5):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    new = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
           "var": momentum * s["var"] + (1 - momentum) * var}
    return y, new


def forward(params, stats, x, stage_sizes, quant=identity):
    """Training-mode forward: logits and the new running statistics."""
    new_stats = {}
    x = conv(x, params["Conv_0"]["kernel"], 1, quant)
    x, new_stats["BatchNorm_0"] = batch_norm(x, params["BatchNorm_0"], stats["BatchNorm_0"])
    x = jax.nn.relu(x)
    block = 0
    for stage, blocks in enumerate(stage_sizes):
        for b in range(blocks):
            name = f"ResidualBlock_{block}"
            p, s, ns = params[name], stats[name], {}
            stride = 2 if stage > 0 and b == 0 else 1
            y = conv(x, p["Conv_0"]["kernel"], stride, quant)
            y, ns["BatchNorm_0"] = batch_norm(y, p["BatchNorm_0"], s["BatchNorm_0"])
            y = jax.nn.relu(y)
            y = conv(y, p["Conv_1"]["kernel"], 1, quant)
            y, ns["BatchNorm_1"] = batch_norm(y, p["BatchNorm_1"], s["BatchNorm_1"])
            if "Conv_2" in p:
                x = conv(x, p["Conv_2"]["kernel"], stride, quant)
                x, ns["BatchNorm_2"] = batch_norm(x, p["BatchNorm_2"], s["BatchNorm_2"])
            x = jax.nn.relu(x + y)
            new_stats[name] = ns
            block += 1
    x = x.mean((1, 2))
    logits = jnp.matmul(quant(x), quant(params["Dense_0"]["kernel"]),
                        precision="highest") + params["Dense_0"]["bias"]
    return logits, new_stats


def loss_fn(params, stats, x, y, stage_sizes, quant):
    logits, new_stats = forward(params, stats, x, stage_sizes, quant)
    loss = -(y * jax.nn.log_softmax(logits)).sum(-1).mean()
    return loss, new_stats


@functools.partial(jax.jit, static_argnames=("stage_sizes", "quant", "lr", "momentum"),
                   donate_argnums=(0, 1, 2))
def sgd_step(params, stats, trace, x, y, stage_sizes, lr, momentum, quant=identity):
    """One step of SGD with momentum: trace = g + momentum * trace;
    params -= lr * trace. Returns the loss before the step."""
    (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, stats, x, y, stage_sizes, quant)
    trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
    return params, stats, trace, loss


def shuffled_epoch(x, y, epoch: int, steps: int, batch: int, shard: int = 0):
    """The epoch's batches in the order the trainer feeds them: the first
    `steps * batch` rows, permuted by a key folded from PRNGKey(0), the
    epoch and the shard (the synchronous trainer's documented per-worker
    shuffle)."""
    base = jax.random.PRNGKey(0)
    shard_key = jax.random.fold_in(jax.random.fold_in(base, epoch), shard)
    data_key, _ = jax.random.split(shard_key)
    perm = jax.random.permutation(data_key, steps * batch)
    return x[: steps * batch][perm].reshape(steps, batch, *x.shape[1:]), \
        y[: steps * batch][perm].reshape(steps, batch, *y.shape[1:])


def train_epoch(params, stats, x, y, epoch, steps, batch, stage_sizes, lr, momentum,
                quant=identity, rows_used=None):
    """(`params`, `stats`, `trace`, per-step losses) after one epoch from a
    fresh optimizer. `rows_used` plants a fault for the control's readings:
    only so many rows of each batch are trained, the mean taken over them."""
    xs, ys = shuffled_epoch(x, y, epoch, steps, batch)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for i in range(steps):
        params, stats, trace, loss = sgd_step(params, stats, trace, xs[i][:rows_used],
                                              ys[i][:rows_used],
                                              stage_sizes, lr, momentum, quant)
        losses.append(loss)
    return params, stats, trace, jnp.stack(losses)
