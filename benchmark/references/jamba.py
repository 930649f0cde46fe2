"""Plain reference: the forward pass of AI21's Jamba block (Mamba-1 mixers,
an attention layer every few, gated feed-forwards) in `jax.numpy`, float32,
matrix products at `highest` precision, the recurrence as a sequential
`lax.scan` over tokens, no cache, no chunking, nothing of the program.

A layer is `x + mixer(rms(x))` then `x + W_down(silu(W_gate y) * (W_up y))`,
`y = rms(x)`, RMSNorm with the configuration's epsilon. Which mixer a layer
has is read from its weights: a tree with `mamba` or one with `attention`.

Mamba mixer: `[u, z] = W_in y`; `u = silu(conv(u) + b_conv)`, the convolution
causal, depthwise, over `d_conv` taps; `[dt, B, C] = W_x u`; each of the three
through its own RMSNorm; `delta = softplus(W_dt dt + b_dt)`;
`h_t = exp(delta_t A) h_{t-1} + delta_t B_t u_t` with `A = -exp(A_log)`;
`y_t = C_t . h_t + D u_t`; `out = W_out (y * silu(z))`.

Attention: `num_heads` query heads on `num_kv_heads` K/V heads, scale
1/sqrt(head width), causal, no bias, no positions of any kind. It is computed
a request and a block of queries at a time, so that the scores of eight
requests of 4,224 tokens are never alive together.

Final RMSNorm; the logits through the tied embedding.

Departures from the published description, all of layout and none of
arithmetic: `A_log` is kept `(d_state, d_inner)` and the convolution's
kernel `(d_conv, d_inner)`, channels minor (the published tensors are
`(d_inner, d_state)` and `(d_inner, 1, d_conv)`); the query, key and value
projections are kept by head, `(d_model, heads, head width)`.

`quant` is the control's hook: it is applied to both operands of every
matrix product (the scan's elementwise arithmetic is no matrix product).
The reference proper passes the identity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # queries scored at a time: the largest divisor of T up to this


def identity(x):
    return x


def fp8(x):
    """Round to float8 (e4m3) with one scale a tensor: the nearest
    precision below the bfloat16 the configuration states."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def mamba(w, y, eps, q_):
    """y: (rows, T, d_model), normed. The recurrence runs token by token
    over all rows at once, from a zero state."""
    taps, d = w["conv_kernel"].shape
    n = w["A_log"].shape[0]
    T = y.shape[1]
    uz = q_(y) @ q_(w["in_proj"]["kernel"])
    u, z = uz[..., :d], uz[..., d:]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = silu(sum(w["conv_kernel"][k] * padded[:, k:k + T] for k in range(taps))
             + w["conv_bias"])
    dbc = q_(u) @ q_(w["x_proj"]["kernel"])
    r = dbc.shape[-1] - 2 * n
    dt = rms_norm(dbc[..., :r], w["dt_norm"], eps)
    B = rms_norm(dbc[..., r:r + n], w["b_norm"], eps)
    C = rms_norm(dbc[..., r + n:], w["c_norm"], eps)
    delta = jax.nn.softplus(q_(dt) @ q_(w["dt_proj"]["kernel"]) + w["dt_proj"]["bias"])
    A = -jnp.exp(w["A_log"])  # (n, d)

    def step(h, xs):
        u_t, delta_t, b_t, c_t = xs  # (rows, d), (rows, d), (rows, n), (rows, n)
        h = jnp.exp(delta_t[:, None, :] * A) * h + \
            (delta_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.einsum("rn,rnd->rd", c_t, h) + w["D"] * u_t

    h0 = jnp.zeros((y.shape[0], n, d), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.swapaxes(a, 0, 1)
                                         for a in (u, delta, B, C)))
    out = jnp.swapaxes(ys, 0, 1) * silu(z)
    return q_(out) @ q_(w["out_proj"]["kernel"])


def attention(w, y, q_):
    """y: (rows, T, d_model), normed. Causal, grouped heads, no positions."""
    T = y.shape[1]
    block = max(b for b in range(1, min(T, QUERY_BLOCK) + 1) if T % b == 0)

    def one_request(y1):  # (T, d_model)
        q = jnp.einsum("td,dhf->htf", q_(y1), q_(w["q"]["kernel"]))
        k = jnp.einsum("td,dhf->htf", q_(y1), q_(w["k"]["kernel"]))
        v = jnp.einsum("td,dhf->htf", q_(y1), q_(w["v"]["kernel"]))
        heads, kv_heads, f = q.shape[0], k.shape[0], q.shape[-1]
        qg = q.reshape(kv_heads, heads // kv_heads, T, f)

        def one_block(i):
            rows = i * block + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(qg, i * block, block, axis=2)
            s = jnp.einsum("kgqf,klf->kgql", q_(qb), q_(k)) / math.sqrt(f)
            s = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], s, -1e30)
            return jnp.einsum("kgql,klf->kgqf", q_(jax.nn.softmax(s, -1)), q_(v))

        out = jax.lax.map(one_block, jnp.arange(T // block))  # (T/block, k, g, block, f)
        out = jnp.moveaxis(out, 0, 2).reshape(heads, T, f)
        return jnp.einsum("htf,hfd->td", q_(out), q_(w["out"]["kernel"]))

    return jax.lax.map(one_request, y)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def block(w, x, eps, quant=identity):
    """x: (rows, T, d_model) float32."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, w["mixer_norm"], eps)
        if "mamba" in w:
            x = x + mamba(w["mamba"], y, eps, quant)
        else:
            x = x + attention(w["attention"], y, quant)
        y = rms_norm(x, w["mlp_norm"], eps)
        h = silu(quant(y) @ quant(w["gate"]["kernel"])) * \
            (quant(y) @ quant(w["up"]["kernel"]))
        return x + quant(h) @ quant(w["down"]["kernel"])


@jax.jit
def embed(top, tokens):
    return top["tok_embed"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(top, x, rows, eps, quant=identity):
    """Logits at positions `rows` (batch, R) only, through the tied embedding."""
    top = _f32(top)
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, top["final_norm"], eps)
        return quant(y) @ quant(top["tok_embed"]["embedding"]).T


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity, eps: float = 1e-6):
    """Full forward over `tokens` (batch, T), layer by layer so that one
    layer's weights are alive at a time; logits at `rows` (batch, R)."""
    x = embed(top, tokens)
    for layer in range(layers):
        x = block(block_at(layer), x, eps, quant=quant)
    return head(top, x, rows, eps, quant=quant)
