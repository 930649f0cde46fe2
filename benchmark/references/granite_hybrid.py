"""Plain reference: the forward pass of the Granite 4.0-H language model in
`jax.numpy`, float32, matrix products at `highest` precision, the Mamba-2
mixers as their token recurrence, no cache, no kernels, nothing of the
program. It holds the same share as the program: the experts of the range
`hyper["first"]` on that the weights hold, and the vocabulary the embedding
has.

`rms(x)` is RMSNorm with a learned scale (eps from `hyper`); no projection
has a bias; the head is the tied embedding. With e, r, s the embedding,
residual and logits multipliers:

    h_0    = e * E[token]
    h'     = h + r * mixer_l(rms(h))
    h_next = h' + r * (shared(b) + routed(b)),  b = rms(h')
    logits = rms(h_L) E^T / s

Mamba-2 mixer (`layer_types[l] == "mamba"`; H heads of P, a state of N,
one group):

    [z, xBC, dt] = W_in y
    xBC          = silu(sum_k w_k xBC_{t-3+k} + b_conv)     4 taps, zeros before the first
    [x, B, C]    = split(xBC)
    delta        = softplus(dt + dt_bias);  a = -exp(A_log)
    S_t,h        = exp(delta_t,h a_h) S_t-1,h + (delta_t,h x_t,h) B_t^T   S_-1 = 0
    y_t,h        = S_t,h C_t + D_h x_t,h
    out          = W_out(w_n * rms(y * silu(z)))

Attention (`"attention"`): q, k, v = W_q y, W_k y, W_v y, Hq query heads on
Hkv K/V heads, no positions; softmax over s <= t of q_t . k_s *
attention_multiplier; out = W_o concat_h(o_h).

Expert layer: r = W_r b over every expert, in float32; I = the top_k largest;
p_i = softmax over I of r_i; routed = sum over the held i in I of p_i *
W_down,i(silu(W_gate,i b) * W_up,i b); shared = W_down(silu(W_gate b) *
W_up b).

A request is run alone. The products go a block of `TOKEN_BLOCK` tokens at a
time and attention a block of `QUERY_BLOCK` queries at a time, so that
3,072 columns fit; the recurrence walks every token.

`quant` is the control's hook, applied to both operands of every matrix
product (the recurrence's outer product and read-out among them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 512
QUERY_BLOCK = 256


def identity(x):
    return x


def fp8(x):
    """Round to float8 (e4m3) with one scale a tensor: the nearest
    precision below the bfloat16 the configuration states."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _divisor(n: int, most: int) -> int:
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _blocks(fn, x, size: int):
    """`fn` over `x` (T, ...) in blocks of rows, the results concatenated."""
    T = x.shape[0]
    b = _divisor(T, size)
    out = jax.lax.map(fn, x.reshape(T // b, b, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def _proj(y, kernel, q_):
    """y (T, d) through a (d, ...) kernel."""
    return jnp.tensordot(q_(y), q_(kernel), axes=1)


def mamba(w, y, hyper: dict, q_):
    """One Mamba-2 mixer over a request's normed inputs y (T, d)."""
    H, N, eps = hyper["heads"], hyper["d_state"], hyper["eps"]
    T = y.shape[0]
    taps, channels = w["conv_kernel"].shape
    inner = channels - 2 * N
    P = inner // H
    zxd = _blocks(lambda yb: _proj(yb, w["in_proj"]["kernel"], q_), y, TOKEN_BLOCK)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + channels], zxd[:, inner + channels:]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = silu(sum(w["conv_kernel"][k] * padded[k:k + T] for k in range(taps)) + w["conv_bias"])
    x, B, C = xbc[:, :inner].reshape(T, H, P), xbc[:, inner:inner + N], xbc[:, inner + N:]
    delta = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def token(S, xs):
        x_t, d_t, b_t, c_t = xs  # (H, P), (H,), (N,), (N,)
        S = jnp.exp(d_t * a)[:, None, None] * S + \
            q_(d_t[:, None] * x_t)[:, :, None] * q_(b_t)[None, None, :]
        return S, jnp.einsum("hpn,n->hp", q_(S), q_(c_t))

    _, ys = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (x, delta, B, C))
    ys = (ys + w["D"][:, None] * x).reshape(T, inner)
    gated = rms(ys * silu(z), w["norm"], eps)
    return _blocks(lambda g: _proj(g, w["out_proj"]["kernel"], q_), gated, TOKEN_BLOCK)


def attention(w, y, hyper: dict, q_):
    """One attention layer over a request's normed inputs y (T, d)."""
    T = y.shape[0]
    _, Hq, D = w["q"]["kernel"].shape
    Hkv = w["k"]["kernel"].shape[1]
    k = _proj(y, w["k"]["kernel"], q_)  # (T, Hkv, D)
    v = _proj(y, w["v"]["kernel"], q_)
    heads_of = jnp.arange(Hq) // (Hq // Hkv)

    def one_block(args):
        yb, t = args  # (QB, d), (QB,)
        q = _proj(yb, w["q"]["kernel"], q_)  # (QB, Hq, D)
        s = jnp.einsum("thd,shd->hts", q_(q), q_(k[:, heads_of])) * \
            hyper["attention_multiplier"]
        s = jnp.where(jnp.arange(T)[None, None] <= t[None, :, None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", q_(jax.nn.softmax(s, -1)), q_(v[:, heads_of]))
        return jnp.tensordot(q_(o), q_(w["out"]["kernel"]), axes=2)

    qb = _divisor(T, QUERY_BLOCK)
    out = jax.lax.map(one_block, (y.reshape(T // qb, qb, -1), jnp.arange(T).reshape(T // qb, qb)))
    return out.reshape(T, -1)


def experts(w, b, hyper: dict, q_):
    """The shared expert and the held experts' part of the routed layer, for
    normed inputs b (T, d)."""
    def gated(x, gate, up, down):
        return _proj(silu(_proj(x, gate, q_)) * _proj(x, up, q_), down, q_)

    e = w["experts"]
    held = e["gate"].shape[0]
    logits = _proj(b, e["router"]["kernel"], q_)  # (T, experts), float32
    top, ids = jax.lax.top_k(logits, hyper["top_k"])
    p = jax.nn.softmax(top, -1)
    weight = jnp.zeros_like(logits).at[jnp.arange(b.shape[0])[:, None], ids].set(p)
    weight = jax.lax.dynamic_slice_in_dim(weight, hyper["first"], held, axis=1)  # (T, held)
    routed = sum(weight[:, i:i + 1] * gated(b, e["gate"][i], e["up"][i], e["down"][i])
                 for i in range(held))
    s = w["shared"]
    return gated(b, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"]) + routed


@functools.partial(jax.jit, static_argnames=("hyper", "layer", "quant"))
def _layer(w, x, hyper, layer: int, quant):
    """One layer over one request's residual stream x (T, d)."""
    hyper = dict(hyper)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        eps, r = hyper["eps"], hyper["residual_multiplier"]
        y = rms(x, w["mixer_norm"]["scale"], eps)
        if hyper["layer_types"][layer] == "attention":
            mixed = attention(w["attention"], y, hyper, quant)
        else:
            mixed = mamba(w["mamba"], y, hyper, quant)
        x = x + r * mixed
        ff = _blocks(lambda xb: experts(w, rms(xb, w["mlp_norm"]["scale"], eps), hyper, quant),
                     x, TOKEN_BLOCK)
        return x + r * ff


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "quant"))
def _head(top, x, rows, eps, scaling, quant):
    with jax.default_matmul_precision("highest"):
        top = _f32(top)
        y = rms(x[rows], top["final_norm"]["scale"], eps)
        return quant(y) @ quant(top["tok_embed"]["embedding"]).T / scaling


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity):
    """Logits (R, W, vocab) at positions `rows` (R, W) of each request's
    `tokens` (R, T), every request run alone."""
    hyper = top["hyper"]
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                          for k, v in hyper.items()))
    arrays = {k: v for k, v in top.items() if k != "hyper"}
    xs = [jnp.asarray(arrays["tok_embed"]["embedding"], jnp.float32)[t]
          * hyper["embedding_multiplier"] for t in tokens]
    for layer in range(layers):
        w = block_at(layer)
        xs = [_layer(w, x, frozen, layer, quant) for x in xs]
    return jnp.stack([_head(arrays, x, r, hyper["eps"], hyper["logits_scaling"], quant)
                      for x, r in zip(xs, rows)])
