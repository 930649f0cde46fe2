"""Plain reference: the forward pass of DeepSeek-V2's block (latent attention
with a decoupled rotary key, a dense first layer, routed experts beside
shared ones) in `jax.numpy`, float32, matrix products at `highest`
precision, per-head keys and values expanded from the latent, a full causal
softmax, no cache, no chunking, nothing of the program.

A layer is `x + attn(rms(x))` then `x + ffn(rms(x))`, RMSNorm with `eps`.

Attention: `c_q = rms(W_qa y)`; `[q_nope, q_pe] = split(W_qb c_q)` a head;
`[c_kv, k_pe] = split(W_kva y)`, `k_pe` one head shared by all;
`c_kv = rms(c_kv)`; `[k_nope, v] = split(W_kvb c_kv)` a head;
`q_pe, k_pe = rope(q_pe), rope(k_pe)` at the token's own position, pairs
(2i, 2i+1); `score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * s`; causal
softmax; `out = W_o concat_h(softmax_h v_h)`. It is computed a request and a
block of queries at a time, so that the scores of four requests of 16,512
tokens are never alive together, and a block of queries is scored against
the columns up to the end of its eighth of the request, not against the
dead ones past it.

Rotary: YaRN (`rope` below) blends each of the `d/2` frequencies between
`theta_i` and `theta_i / factor` by a linear ramp between the two correction
dimensions; the cos/sin factor is `mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`; the softmax scale is `(nope + rope)^-0.5 * mscale(factor,
mscale_all_dim)^2` with `mscale(f, m) = 0.1 m ln f + 1` (the model's own
published code; without `rope` it is the plain rotary and `(nope +
rope)^-0.5`, which is what the `transformers` port computes).

Feed-forward: a tree with `gate` is `W_down(silu(W_gate y) * (W_up y))`; a
tree with `experts` is `shared(y) + sum_{e in top-k} w_e expert_e(y)`:
`p = softmax(W_r y)` over all experts; a group's score is its largest `p`;
the `topk_group` best groups keep their `p`, the rest read 0; the `top_k`
largest are taken (the lower index wins a tie); `w_e = routed_scaling_factor
* p_e`, not renormalised. The tree holds the experts `first .. first +
count - 1` of the router's `total` (`experts` is `(count, ...)`): an
assignment on another expert adds nothing, here as in the program.

Final RMSNorm; the logits through an untied head.

What does not follow from the shapes of the weights (the rotary's
parameters, the router's groups, `top_k`, the scaling factor, which experts
the tree holds) comes in `top["hyper"]`, plain Python numbers beside the
arrays.

Departures from the published description, all of layout or of schedule and
none of arithmetic: `W_qb`, `W_kvb` and `W_o` are kept by head, `(rank,
heads, width)` and `(heads, width, d)`; every matrix is `(in, out)`; the
experts' matrices are stacked `(count, in, out)`; an expert runs over a
padded gather of the tokens routed to it, as many rows as the busiest held
expert has (read from the routing first), never over every token.

`quant` is the control's hook: it is applied to both operands of every
matrix product (the router's among them). The reference proper passes the
identity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries scored at a time: the largest divisor of a segment up to this
SEGMENTS = 8  # of a request's queries, each scored against the columns up to its end


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


# -- rotary -----------------------------------------------------------------


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary_frequencies(dim: int, rope) -> np.ndarray:
    """The `dim / 2` angular frequencies. `rope`: None or a tuple of
    (theta, factor, original_max_position_embeddings, beta_fast, beta_slow,
    mscale, mscale_all_dim)."""
    theta = 10000.0 if rope is None else rope[0]
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope is None or rope[1] <= 1:
        return plain
    _, factor, original, beta_fast, beta_slow = rope[:5]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(width: int, rope) -> float:
    if rope is None:
        return width ** -0.5
    return width ** -0.5 * yarn_mscale(rope[1], rope[6]) ** 2


def rotate(x, positions, rope):
    """x: (..., T, dim), pairs (2i, 2i+1); positions: (T,)."""
    dim = x.shape[-1]
    angle = positions[:, None].astype(jnp.float32) * jnp.asarray(
        rotary_frequencies(dim, rope), jnp.float32)
    m = 1.0 if rope is None else yarn_mscale(rope[1], rope[5]) / yarn_mscale(rope[1], rope[6])
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    pairs = x.reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


# -- attention ---------------------------------------------------------------


def attention(w, y, eps, rope, q_):
    """y: (rows, T, d), normed."""
    T = y.shape[1]
    # queries in a few segments, each against the columns up to its own end:
    # a column past a query's segment is dead for it and is not scored at all
    segments = max(n for n in range(1, SEGMENTS + 1) if T % n == 0)
    span = T // segments
    block = max(b for b in range(1, min(span, QUERY_BLOCK) + 1) if span % b == 0)
    rank = w["kv_a_norm"]["scale"].shape[0]
    d_rope = w["kv_a"]["kernel"].shape[1] - rank
    d_nope = w["q_b"]["kernel"].shape[-1] - d_rope
    scale = softmax_scale(d_nope + d_rope, rope)
    positions = jnp.arange(T)

    def one_request(y1):  # (T, d)
        c_q = rms_norm(q_(y1) @ q_(w["q_a"]["kernel"]), w["q_a_norm"], eps)
        q = jnp.einsum("tr,rhf->htf", q_(c_q), q_(w["q_b"]["kernel"]))
        kv = q_(y1) @ q_(w["kv_a"]["kernel"])
        c_kv = rms_norm(kv[:, :rank], w["kv_a_norm"], eps)
        k_pe = rotate(kv[:, rank:], positions, rope)  # (T, d_rope): one head
        expanded = jnp.einsum("tr,rhf->htf", q_(c_kv), q_(w["kv_b"]))
        k_nope, v = expanded[..., :d_nope], expanded[..., d_nope:]
        q_nope, q_pe = q[..., :d_nope], rotate(q[..., d_nope:], positions, rope)

        def one_segment(n):
            live = (n + 1) * span  # columns any query of the segment sees
            kn, kp, vs = k_nope[:, :live], k_pe[:live], v[:, :live]

            def one_block(i):
                first = n * span + i * block
                at = first + jnp.arange(block)
                qn = jax.lax.dynamic_slice_in_dim(q_nope, first, block, axis=1)
                qp = jax.lax.dynamic_slice_in_dim(q_pe, first, block, axis=1)
                s = (jnp.einsum("hqf,hlf->hql", q_(qn), q_(kn))
                     + jnp.einsum("hqf,lf->hql", q_(qp), q_(kp))) * scale
                s = jnp.where(positions[None, :live] <= at[:, None], s, -1e30)
                return jnp.einsum("hql,hlf->hqf", q_(jax.nn.softmax(s, -1)), q_(vs))

            out = jax.lax.map(one_block, jnp.arange(span // block))
            return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], span, -1)

        out = jnp.concatenate([one_segment(n) for n in range(segments)], axis=1)
        return jnp.einsum("htf,hfd->td", q_(out), q_(w["out"]["kernel"]))

    return jax.lax.map(one_request, y)


# -- feed-forwards -----------------------------------------------------------


def gated(w, y, q_):
    h = silu(q_(y) @ q_(w["gate"]["kernel"])) * (q_(y) @ q_(w["up"]["kernel"]))
    return q_(h) @ q_(w["down"]["kernel"])


def route(w, y, hyper, q_):
    """y: (N, d). Returns (ids, weights), (N, top_k) each, over all of the
    router's experts."""
    groups, keep, top_k, factor = (hyper[k] for k in (
        "n_group", "topk_group", "top_k", "routed_scaling_factor"))
    p = jax.nn.softmax(q_(y) @ q_(w["experts"]["router"]["kernel"]), -1)
    by_group = p.reshape(p.shape[0], groups, -1)
    _, best = jax.lax.top_k(by_group.max(-1), keep)
    kept = jnp.zeros(by_group.shape[:2], bool).at[
        jnp.arange(p.shape[0])[:, None], best].set(True)
    p = jnp.where(kept[:, :, None], by_group, 0.0).reshape(p.shape)
    weights, ids = jax.lax.top_k(p, top_k)
    return ids, weights * factor


@functools.partial(jax.jit, static_argnames=("eps", "rope", "quant"))
def _attend(w, x, eps, rope, quant):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        return x + attention(w["attention"], rms_norm(x, w["attn_norm"], eps), eps, rope, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense(w, x, eps, quant):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        return x + gated(w, rms_norm(x, w["ffn_norm"], eps), quant)


@functools.partial(jax.jit, static_argnames=("eps", "hyper", "quant"))
def _route(w, x, eps, hyper, quant):
    hyper = dict(hyper)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, w["ffn_norm"], eps).reshape(-1, x.shape[-1])
        ids, weights = route(w, y, hyper, quant)
    first, count = hyper["first"], hyper["count"]
    held = (ids >= first) & (ids < first + count)
    load = jnp.zeros((count,), jnp.int32).at[jnp.where(held, ids - first, count)].add(
        1, mode="drop")
    return ids, weights, load


@functools.partial(jax.jit, static_argnames=("eps", "hyper", "rows", "quant"))
def _routed(w, x, ids, weights, eps, hyper, rows: int, quant):
    """`shared(y) + sum w_e expert_e(y)` over the held experts, each over a
    gather of `rows` tokens: the ones routed to it first."""
    hyper = dict(hyper)
    w = _f32(w)
    first = hyper["first"]
    shape = x.shape
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, w["ffn_norm"], eps).reshape(-1, shape[-1])
        out = gated(w["shared"], y, quant)

        def one_expert(out, ew):
            e, gate, up, down = ew
            mine = ids == first + e  # (N, top_k): at most one a token
            weight = jnp.where(mine, weights, 0.0).sum(-1)
            chosen = mine.any(-1)
            order = jnp.argsort(~chosen, stable=True)[:rows]
            h = gated({"gate": {"kernel": gate}, "up": {"kernel": up},
                       "down": {"kernel": down}}, y[order], quant)
            return out.at[order].add(h * weight[order, None]), None

        experts = w["experts"]
        out, _ = jax.lax.scan(one_expert, out, (
            jnp.arange(experts["gate"].shape[0]), experts["gate"], experts["up"],
            experts["down"]))
    return x + out.reshape(shape)


def block(w, x, eps, hyper: dict, quant=identity):
    """x: (rows, T, d) float32; `w` one layer's tree."""
    rope = hyper.get("rope")
    x = _attend({k: w[k] for k in ("attention", "attn_norm")}, x, eps,
                None if rope is None else tuple(rope), quant)
    if "experts" not in w:
        return _dense({k: w[k] for k in ("ffn_norm", "gate", "up", "down")}, x, eps, quant)
    static = tuple(sorted({**{k: hyper[k] for k in (
        "n_group", "topk_group", "top_k", "routed_scaling_factor", "first")},
        "count": int(w["experts"]["gate"].shape[0])}.items()))
    ids, weights, load = _route(
        {"ffn_norm": w["ffn_norm"], "experts": {"router": w["experts"]["router"]}},
        x, eps, static, quant)
    busiest = max(int(np.asarray(load).max()), 1)
    rows = min(-(-busiest // 256) * 256, ids.shape[0])
    return _routed(w, x, ids, weights, eps, static, rows, quant)


@jax.jit
def embed(top, tokens):
    return top["tok_embed"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(top, x, rows, eps, quant=identity):
    """Logits at positions `rows` (batch, R) only, through the untied head."""
    top = _f32(top)
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, top["final_norm"], eps)
        return quant(y) @ quant(top["lm_head"]["kernel"])


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity, eps: float = 1e-6):
    """Full forward over `tokens` (batch, T), layer by layer so that one
    layer's weights are alive at a time; logits at `rows` (batch, R)."""
    hyper = top["hyper"]
    arrays = {k: v for k, v in top.items() if k != "hyper"}
    x = embed(arrays, tokens)
    for layer in range(layers):
        x = block(block_at(layer), x, eps, hyper, quant=quant)
    return head(arrays, x, rows, eps, quant=quant)
