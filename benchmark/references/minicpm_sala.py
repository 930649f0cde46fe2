"""Plain reference: the forward pass of the MiniCPM-SALA language model in
`jax.numpy`, float32, matrix products at `highest` precision, the lightning
layers as their token recurrence, the selection by a full sort, no cache,
no kernels, nothing of the program.

`rms(x)` is RMSNorm with a learned scale (eps from `hyper`); no bias
anywhere; an untied head. With `r = scale_depth / sqrt(depth)`, `depth` the
published number of layers:

    h_0    = scale_emb * E[token]
    h'     = h + r * mixer_l(rms(h))
    h_next = h' + r * W_down(silu(W_gate y) * W_up y),  y = rms(h')
    logits = W_head rms(h_L) / logit_divisor

Lightning layer (`mixer_types[l] == "lightning-attn"`; H heads of D; head
`j` decays by `lambda_j = exp(-2^(-8 (j+1) / H) (1 - l / (depth - 1) + 1e-5))`):

    q, k, v = W_q y, W_k y, W_v y;  q, k = rms_head(q), rms_head(k)
    q, k    = rope(q), rope(k)           pairs (2i, 2i+1), the token's own position
    S_t     = lambda_j S_{t-1} + k_t^T v_t,  S_{-1} = 0      a scan over the tokens
    o_t     = (q_t * D^-0.5) S_t
    out     = W_o (rms_head(o) * sigmoid(W_g y))

Sparse layer (`"minicpm4"`: Hq query heads on Hkv K/V heads, group g(h); no
positions; scale D^-0.5):

    q, k, v  = W_q y, W_k y, W_v y;  q, k = rms_head(q), rms_head(k)
    c_i      = mean(k_{stride i .. stride i + kernel - 1})    exists once stride i + kernel - 1 <= t
    p_h[t,i] = softmax over the c_i that exist at t of (q_h,t . c_g(h),i * scale)
    P_g[t,i] = sum over the heads h of group g of p_h[t, i]
    B_g[t,b] = max over the keys i that start in block b of P_g[t, i]
    Sel_g(t) = blocks 0 .. init_blocks - 1, the blocks that hold a column of
               t - window + 1 .. t, then the largest B_g[t, .] among the rest until
               topk blocks (a stable sort: the lower block wins a tie);
               every column s <= t while t < dense_len
    o_h,t    = softmax over s <= t in the blocks of Sel_g(h)(t) of (q_h,t . k_g,s * scale) v_g,s
    out      = W_o (concat_h o_h * sigmoid(W_g' y))

A request is run alone; its tokens go through the products in blocks of
`TOKEN_BLOCK`, the lightning recurrence one token at a time with its state
carried from block to block, and the sparse layer's queries in blocks of
`QUERY_BLOCK`, each gathering the columns of its selected blocks, so that
99k columns fit.

`collect`, a dict, is filled on request with each sparse layer's selection,
`(requests, T, Hkv, blocks)` bool by layer, under `"selected"`.

`quant` is the control's hook, applied to both operands of every matrix
product (the recurrence's and the compressed keys' scores among them).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_BLOCK = 1024
QUERY_BLOCK = 64


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


def rotate(x, positions, theta: float):
    """x: (T, heads, D), pairs (2i, 2i+1); positions: (T,)."""
    D = x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _proj(y, kernel, q_):
    """y (T, d) through a (d, ...) kernel."""
    return jnp.tensordot(q_(y), q_(kernel), axes=1)


# -- lightning -----------------------------------------------------------------


def lightning(w, y, hyper: dict, layer: int, q_):
    """One lightning layer's mixer over a request's normed inputs y (T, d):
    the products a block of tokens at a time, the recurrence a token at a
    time with its state carried across the blocks."""
    eps, theta = hyper["eps"], hyper["rope_theta"]
    T, d = y.shape
    _, H, D = w["q"]["kernel"].shape
    slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    lam = jnp.asarray(np.exp(-slopes * (1.0 - layer / (hyper["depth"] - 1) + 1e-5)),
                      jnp.float32)

    def token(S, qkv):
        q_t, k_t, v_t = qkv  # (H, D) each
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hd,hde->he", q_t, S)

    def one_block(S, args):
        yb, first = args
        at = first + jnp.arange(yb.shape[0])
        q = rotate(rms(_proj(yb, w["q"]["kernel"], q_), w["q_norm"]["scale"], eps), at,
                   theta) * D ** -0.5
        k = rotate(rms(_proj(yb, w["k"]["kernel"], q_), w["k_norm"]["scale"], eps), at, theta)
        v = _proj(yb, w["v"]["kernel"], q_)
        S, o = jax.lax.scan(token, S, (q_(q), q_(k), q_(v)))
        o = rms(o, 1.0, eps).reshape(o.shape[0], H * D) * w["o_norm"]
        g = jax.nn.sigmoid(_proj(yb, w["gate"]["kernel"], q_))
        return S, _proj(o * g, w["o"]["kernel"], q_)

    tb = _divisor(T, TOKEN_BLOCK)
    _, out = jax.lax.scan(one_block, jnp.zeros((H, D, D), jnp.float32),
                          (y.reshape(T // tb, tb, d), jnp.arange(T // tb) * tb))
    return out.reshape(T, d)


# -- sparse --------------------------------------------------------------------


def _selection(scores, t, hyper: dict):
    """The blocks query t attends, by a stable full sort: (width,) block
    numbers and (width,) whether each is one. `scores`: (blocks,)."""
    block, topk = hyper["block"], hyper["topk"]
    width = max(topk, -(-hyper["dense_len"] // block))
    scores = jnp.pad(scores, (0, max(0, width - scores.shape[0])))
    b = jnp.arange(scores.shape[0])
    live = b <= t // block
    forced = (b < hyper["init_blocks"]) | ((b + 1) * block - 1 >= t - hyper["window"] + 1)
    key = jnp.where(live, jnp.where(forced, -jnp.inf, -scores), jnp.inf)
    order = jnp.argsort(key, stable=True)[:topk]
    picked = jnp.pad(order, (0, width - topk))
    ok = jnp.pad(live[order], (0, width - topk))
    dense = t < hyper["dense_len"]
    everyone = jnp.arange(width)
    return (jnp.where(dense, everyone, picked),
            jnp.where(dense, everyone <= t // block, ok))


def sparse(w, y, hyper: dict, q_, want_selected: bool):
    """One sparse layer's mixer over a request's normed inputs y (T, d), and
    its selection (T, Hkv, blocks) where asked for."""
    eps = hyper["eps"]
    T = y.shape[0]
    _, Hq, D = w["q"]["kernel"].shape
    Hkv = w["k"]["kernel"].shape[1]
    group, scale = Hq // Hkv, D ** -0.5
    K, s, block = hyper["kernel"], hyper["stride"], hyper["block"]
    nb = -(-T // block)
    n_keys = nb * (block // s)
    k = rms(_proj(y, w["k"]["kernel"], q_), w["k_norm"]["scale"], eps)  # (T, Hkv, D)
    v = _proj(y, w["v"]["kernel"], q_)
    padded = jnp.pad(k, ((0, n_keys * s + K - T), (0, 0), (0, 0)))
    windows = (np.arange(n_keys) * s)[:, None] + np.arange(K)
    c = padded[windows].mean(1)  # (n_keys, Hkv, D)
    key_end = jnp.arange(n_keys) * s + K - 1
    heads_of = np.arange(Hq) // group

    def one_block(args):
        yb, t = args  # (QB, d), (QB,)
        q = rms(_proj(yb, w["q"]["kernel"], q_), w["q_norm"]["scale"], eps)  # (QB, Hq, D)
        sc = jnp.einsum("thd,khd->thk", q_(q), q_(c[:, heads_of])) * scale
        exists = key_end[None, None] <= t[:, None, None]
        p = jax.nn.softmax(jnp.where(exists, sc, -jnp.inf), -1)
        p = jnp.where(exists, p, 0.0)
        P = p.reshape(len(t), Hkv, group, n_keys).sum(2)
        B = P.reshape(len(t), Hkv, nb, block // s).max(-1)  # (QB, Hkv, nb)
        select = functools.partial(_selection, hyper=hyper)
        blocks, ok = jax.vmap(jax.vmap(select, (0, None)))(B, t)  # (QB, Hkv, width)
        cols = blocks[..., None] * block + jnp.arange(block)  # (QB, Hkv, width, block)
        seen = ok[..., None] & (cols <= t[:, None, None, None])
        cols = jnp.clip(cols.reshape(len(t), Hkv, -1), 0, T - 1)
        kv_heads = jnp.arange(Hkv)[None, :, None]
        ks, vs = k[cols, kv_heads], v[cols, kv_heads]  # (QB, Hkv, C, D)
        qg = q.reshape(len(t), Hkv, group, D)
        a = jnp.einsum("tkgd,tkcd->tkgc", q_(qg), q_(ks)) * scale
        a = jnp.where(seen.reshape(len(t), Hkv, 1, -1), a, -jnp.inf)
        o = jnp.einsum("tkgc,tkcd->tkgd", q_(jax.nn.softmax(a, -1)), q_(vs))
        chosen = jnp.zeros((len(t), Hkv, nb), bool)
        if want_selected:
            rows = jnp.arange(len(t))[:, None, None]
            chosen = chosen.at[rows, kv_heads, jnp.clip(blocks, 0, nb - 1)].max(ok)
        g = jax.nn.sigmoid(_proj(yb, w["gate"]["kernel"], q_))
        return _proj(o.reshape(len(t), Hq * D) * g, w["o"]["kernel"], q_), chosen

    qb = _divisor(T, QUERY_BLOCK)
    out, chosen = jax.lax.map(one_block, (y.reshape(T // qb, qb, -1),
                                          jnp.arange(T).reshape(T // qb, qb)))
    return out.reshape(T, -1), chosen.reshape(T, Hkv, nb)


# -- the layers ------------------------------------------------------------------


def _ffn(w, y, q_):
    return _proj(silu(_proj(y, w["gate"]["kernel"], q_)) * _proj(y, w["up"]["kernel"], q_),
                 w["down"]["kernel"], q_)


@functools.partial(jax.jit, static_argnames=("hyper", "layer", "quant", "want_selected"))
def _layer(w, x, hyper, layer: int, quant, want_selected: bool):
    """One layer over one request's residual stream x (T, d)."""
    hyper = dict(hyper)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        eps = hyper["eps"]
        r = hyper["scale_depth"] / math.sqrt(hyper["depth"])
        y = rms(x, w["mixer_norm"]["scale"], eps)
        if hyper["mixer_types"][layer] == "lightning-attn":
            mixed, chosen = lightning(w["lightning"], y, hyper, layer, quant), None
        else:
            mixed, chosen = sparse(w["attention"], y, hyper, quant, want_selected)
        x = x + r * mixed
        ff = _blocks(lambda yb: _ffn(w, rms(yb, w["mlp_norm"]["scale"], eps), quant), x,
                     TOKEN_BLOCK)
        return x + r * ff, chosen


@functools.partial(jax.jit, static_argnames=("eps", "divisor", "quant"))
def _head(top, x, rows, eps, divisor, quant):
    with jax.default_matmul_precision("highest"):
        top = _f32(top)
        y = rms(x[rows], top["final_norm"]["scale"], eps)
        return quant(y) @ quant(top["lm_head"]) / divisor


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity, collect=None):
    """Logits (R, W, vocab) at positions `rows` (R, W) of each request's
    `tokens` (R, T), every request run alone."""
    hyper = top["hyper"]
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                          for k, v in hyper.items()))
    arrays = {k: v for k, v in top.items() if k != "hyper"}
    xs = [jnp.asarray(arrays["tok_embed"]["embedding"], jnp.float32)[t] * hyper["scale_emb"]
          for t in tokens]
    if collect is not None:
        collect["selected"] = {}
    for layer in range(layers):
        w = block_at(layer)
        chosen = []
        for i, x in enumerate(xs):
            xs[i], c = _layer(w, x, frozen, layer, quant, collect is not None)
            chosen.append(c)
        if collect is not None and chosen[0] is not None:
            collect["selected"][layer] = jnp.stack(chosen)
    return jnp.stack([_head(arrays, x, r, hyper["eps"], hyper["logit_divisor"], quant)
                      for x, r in zip(xs, rows)])
