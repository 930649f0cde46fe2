"""Plain reference: GPT-2's forward pass in `jax.numpy`, float32, matrix
products at `highest` precision, no cache, no batching tricks, nothing of
the program.

One block: x + Attn(LN(x)), then x + MLP(LN(x)); LayerNorm with epsilon
1e-6 and GELU in its tanh form, as `TransformerLM` has them (GPT-2 itself:
1e-5; noted in the configuration file). Learned absolute positions; an
untied head after a final LayerNorm.

`quant` is the control's hook: it is applied to both operands of every
matrix product. The reference proper passes the identity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def identity(x):
    return x


def fp8(x):
    """Round to float8 (e4m3) with one scale a tensor: the nearest
    precision below the bfloat16 the configuration states."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("quant",))
def block(w, x, quant=identity):
    """x: (rows, T, d) float32; causal attention over T."""
    w = _f32(w)
    q_ = quant
    with jax.default_matmul_precision("highest"):
        y = layer_norm(x, w["LayerNorm_0"])
        qkv = jnp.einsum("btd,dchf->cbhtf", q_(y), q_(w["SelfAttention_0"]["qkv"]["kernel"]))
        qkv = qkv + w["SelfAttention_0"]["qkv"]["bias"][:, None, :, None, :]
        q, k, v = qkv[0], qkv[1], qkv[2]
        scores = jnp.einsum("bhqf,bhkf->bhqk", q_(q), q_(k)) / math.sqrt(q.shape[-1])
        T = x.shape[1]
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -1e30)
        attn = jnp.einsum("bhqk,bhkf->bqhf", q_(jax.nn.softmax(scores, -1)), q_(v))
        attn = attn.reshape(x.shape)
        x = x + q_(attn) @ q_(w["SelfAttention_0"]["out"]["kernel"]) + \
            w["SelfAttention_0"]["out"]["bias"]
        y = layer_norm(x, w["LayerNorm_1"])
        h = gelu_tanh(q_(y) @ q_(w["Dense_0"]["kernel"]) + w["Dense_0"]["bias"])
        return x + q_(h) @ q_(w["Dense_1"]["kernel"]) + w["Dense_1"]["bias"]


@jax.jit
def embed(top, tokens):
    top = _f32(top)
    return top["tok_embed"]["embedding"][tokens] + top["pos_embed"][: tokens.shape[1]]


@functools.partial(jax.jit, static_argnames=("quant",))
def head(top, x, rows, quant=identity):
    """Logits at positions `rows` (batch, R) only."""
    top = _f32(top)
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        y = layer_norm(x, top["LayerNorm_0"])
        return quant(y) @ quant(top["lm_head"]["kernel"]) + top["lm_head"]["bias"]


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity):
    """Full forward over `tokens` (batch, T), layer by layer so that one
    layer's weights are alive at a time; logits at `rows` (batch, R)."""
    x = embed(top, tokens)
    for layer in range(layers):
        x = block(block_at(layer), x, quant=quant)
    return head(top, x, rows, quant=quant)
