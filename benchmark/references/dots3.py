"""Plain reference: the forward pass of the dots3-note language model's block
in `jax.numpy`, float32, matrix products at `highest` precision, keys and
values a head expanded from the latent, the selection by a full sort, no
cache, no kernels, no chunking, nothing of the program.

`y = rms(x)` (eps 1e-5), no bias anywhere, an untied head; a layer is
`x + attn(rms(x))` then `x + ffn(rms(x))`. Layer `i` is FULL or WINDOW by
`layer_types[i]`.

Full layer (`H` heads, softmax scale `(nope + rope)^-0.5`):

    c_q            = rms(W_qa y) * (d / q_rank)^0.5
    [q_nope, q_pe] = split(W_qb c_q)            a head
    [c_kv, k_pe]   = split(W_kva y);  c_kv = rms(c_kv) * (d / kv_rank)^0.5
    [k_nope, v]    = split(W_kvb c_kv)          a head
    q_pe, k_pe     = rope(q_pe), rope(k_pe)     pairs (2i, 2i+1), the token's own position
    indexer:  qI_j = W_qI c_q  (index heads x index width)
              kI   = layernorm(W_kI y)          ONE a token, scale only
              rope on the first `rope` values of qI_j and kI
              w    = W_w y * heads^-0.5 * width^-0.5
              I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
              S_t  = the `index_topk` columns s <= t with the largest I[t, s];
                     every s <= t while t < index_topk; the lower column wins a tie
    score_h[t, s]  = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale      s in S_t
    o_h            = softmax_s(score_h) v_h
    g              = sigmoid(W_g y)             one a head
    out            = W_o concat_h(g_h * o_h)

Window layer: the same latent attention at its own sizes and rotary base, no
indexer, the mask `t - window < s <= t`.

Feed-forward: a tree with `gate` is `W_down(silu(W_gate y) * (W_up y))`; a
tree with `experts` is `shared(y) + sum_e w_e expert_e(y)`: `s =
sigmoid(W_r y)` over all experts; the `top_k` largest of `s + b` are taken
(the lower index wins a tie; `b` selects only); `w_e = s_e / sum over the
chosen of s`, times `routed_scaling_factor`. The tree holds the experts
`first .. first + count - 1`: an assignment on another adds nothing.

The selection is a stable full sort of `-I` over the live columns. Attention
is computed a request, a group of heads and a block of queries at a time,
the queries in a few segments each scored against the columns up to its own
end, so that 33k columns fit; a window layer scores a block against the
band of columns it can see.

`collect`, a dict, is filled on request with each full layer's `S_t` (a
(batch, T, T) mask under `"selected"`) and each routed layer's chosen
experts (`"routed"`), by layer.

`quant` is the control's hook, applied to both operands of every matrix
product (the router's and the indexer's among them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64  # queries scored at a time: the largest divisor of a segment up to this
SEGMENTS = 8      # of a request's queries, each scored against the columns up to its end
HEAD_GROUP = 16   # heads expanded at a time


def identity(x):
    return x


def fp8(x):
    """Round to float8 (e4m3) with one scale a tensor: the nearest
    precision below the bfloat16 the configuration states."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def layer_norm(x, p, eps):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rotate(x, positions, theta: float):
    """x: (..., T, dim), pairs (2i, 2i+1); positions: (T,)."""
    dim = x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = positions[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _blocks(T: int):
    segments = max(n for n in range(1, SEGMENTS + 1) if T % n == 0)
    span = T // segments
    block = max(b for b in range(1, min(span, QUERY_BLOCK) + 1) if span % b == 0)
    return segments, span, block


# -- attention ---------------------------------------------------------------


def select(w, y1, c_q, positions, theta: float, pe: int, top_k: int, eps, q_):
    """The mask (T, T) of `S_t` for one request: `selected[t, s]`."""
    T = y1.shape[0]
    qi = jnp.einsum("tr,rhf->htf", q_(c_q), q_(w["index_q"]["kernel"]))
    ki = layer_norm(q_(y1) @ q_(w["index_k"]["kernel"]), w["index_k_norm"], eps)
    qi = jnp.concatenate([rotate(qi[..., :pe], positions, theta), qi[..., pe:]], -1)
    ki = jnp.concatenate([rotate(ki[..., :pe], positions, theta), ki[..., pe:]], -1)
    heads, width = qi.shape[0], qi.shape[-1]
    wi = (q_(y1) @ q_(w["index_w"]["kernel"])) * (heads ** -0.5 * width ** -0.5)
    _, _, block = _blocks(T)

    def one_block(i):
        first = i * block
        at = first + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(qi, first, block, axis=1)
        wb = jax.lax.dynamic_slice_in_dim(wi, first, block, axis=0)
        scores = (jax.nn.relu(jnp.einsum("hqf,sf->qhs", q_(qb), q_(ki)))
                  * wb[:, :, None]).sum(1)  # (block, T)
        live = positions[None, :] <= at[:, None]
        # a full, stable sort: of equal scores the lower column comes first
        order = jnp.argsort(jnp.where(live, -scores, jnp.inf), axis=-1, stable=True)
        rank = jnp.zeros((block, T), jnp.int32).at[
            jnp.arange(block)[:, None], order].set(jnp.arange(T, dtype=jnp.int32)[None])
        return live & (rank < top_k)

    return jax.lax.map(one_block, jnp.arange(T // block)).reshape(T, T)


def attention(w, y, eps, hyper: dict, window: bool, q_, want_selected: bool):
    """y: (rows, T, d), normed. Returns the layer's attention and, of a full
    layer where asked, the selection (rows, T, T)."""
    T, d = y.shape[1], y.shape[2]
    segments, span, block = _blocks(T)
    rank = w["kv_a_norm"]["scale"].shape[0]
    q_rank = w["q_a_norm"]["scale"].shape[0]
    pe = hyper["swa_rope_width" if window else "rope_width"]
    theta = hyper["swa_rope_theta" if window else "rope_theta"]
    heads = w["q_b"]["kernel"].shape[1]
    d_nope = w["q_b"]["kernel"].shape[-1] - pe
    scale = (d_nope + pe) ** -0.5
    reach = hyper["window"]
    positions = jnp.arange(T)
    group = max(g for g in range(1, HEAD_GROUP + 1) if heads % g == 0)
    band = min(T, block + reach - 1)  # columns a block of a window layer can see

    def one_request(y1):  # (T, d)
        c_q = rms_norm(q_(y1) @ q_(w["q_a"]["kernel"]), w["q_a_norm"], eps) * (
            d / q_rank) ** 0.5
        kv = q_(y1) @ q_(w["kv_a"]["kernel"])
        c_kv = rms_norm(kv[:, :rank], w["kv_a_norm"], eps) * (d / rank) ** 0.5
        k_pe = rotate(kv[:, rank:], positions, theta)  # (T, pe): one head
        gate = jax.nn.sigmoid(q_(y1) @ q_(w["gate"]["kernel"]))  # (T, heads)
        selected = None if window else select(
            w, y1, c_q, positions, theta, pe, hyper["index_topk"], eps, q_)

        def one_group(g):
            def of_group(kernel, axis):
                return jax.lax.dynamic_slice_in_dim(kernel, g * group, group, axis=axis)

            q = jnp.einsum("tr,rhf->htf", q_(c_q), q_(of_group(w["q_b"]["kernel"], 1)))
            expanded = jnp.einsum("tr,rhf->htf", q_(c_kv), q_(of_group(w["kv_b"], 1)))
            k_nope, v = expanded[..., :d_nope], expanded[..., d_nope:]
            q_nope, q_pe = q[..., :d_nope], rotate(q[..., d_nope:], positions, theta)

            def scored(first, lo, count, kn, kp, vs, chosen):
                """`block` queries from `first` against `count` columns from `lo`."""
                at = first + jnp.arange(block)
                cols = lo + jnp.arange(count)
                qn = jax.lax.dynamic_slice_in_dim(q_nope, first, block, axis=1)
                qp = jax.lax.dynamic_slice_in_dim(q_pe, first, block, axis=1)
                s = (jnp.einsum("hqf,hlf->hql", q_(qn), q_(kn))
                     + jnp.einsum("hqf,lf->hql", q_(qp), q_(kp))) * scale
                seen = cols[None, :] <= at[:, None]
                if window:
                    seen &= cols[None, :] > at[:, None] - reach
                else:
                    seen &= chosen
                s = jnp.where(seen[None], s, -1e30)
                return jnp.einsum("hql,hlf->hqf", q_(jax.nn.softmax(s, -1)), q_(vs))

            if window:
                def one_block(i):
                    first = i * block
                    lo = jnp.clip(first + block - band, 0, T - band)
                    cut = [jax.lax.dynamic_slice_in_dim(a, lo, band, axis=ax)
                           for a, ax in ((k_nope, 1), (k_pe, 0), (v, 1))]
                    return scored(first, lo, band, *cut, None)

                out = jax.lax.map(one_block, jnp.arange(T // block))
                out = jnp.moveaxis(out, 0, 1).reshape(group, T, -1)
            else:
                def one_segment(n):
                    live = (n + 1) * span  # columns any query of the segment sees

                    def one_block(i):
                        first = n * span + i * block
                        chosen = jax.lax.dynamic_slice_in_dim(
                            selected, first, block, axis=0)[:, :live]
                        return scored(first, 0, live, k_nope[:, :live], k_pe[:live],
                                      v[:, :live], chosen)

                    got = jax.lax.map(one_block, jnp.arange(span // block))
                    return jnp.moveaxis(got, 0, 1).reshape(group, span, -1)

                out = jnp.concatenate([one_segment(n) for n in range(segments)], axis=1)
            gated = out * jnp.swapaxes(
                jax.lax.dynamic_slice_in_dim(gate, g * group, group, axis=1), 0, 1)[..., None]
            return jnp.einsum("htf,hfd->td", q_(gated),
                              q_(of_group(w["out"]["kernel"], 0)))

        out = jax.lax.map(one_group, jnp.arange(heads // group)).sum(0)
        if want_selected and not window:
            return out, selected
        return out, jnp.zeros((), bool)

    return jax.lax.map(one_request, y)


# -- feed-forwards -----------------------------------------------------------


def gated(w, y, q_):
    h = silu(q_(y) @ q_(w["gate"]["kernel"])) * (q_(y) @ q_(w["up"]["kernel"]))
    return q_(h) @ q_(w["down"]["kernel"])


def route(w, y, hyper, q_):
    """y: (N, d). Returns (ids, weights), (N, top_k) each, over all of the
    router's experts: chosen by `s + b`, weighed by `s`, renormalised."""
    s = jax.nn.sigmoid(q_(y) @ q_(w["experts"]["router"]["kernel"]))
    _, ids = jax.lax.top_k(s + w["experts"]["bias"], hyper["top_k"])
    weights = jnp.take_along_axis(s, ids, axis=1)
    weights = weights / weights.sum(-1, keepdims=True)
    return ids, weights * hyper["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnames=("eps", "hyper", "window", "quant",
                                             "want_selected"))
def _attend(w, x, eps, hyper, window, quant, want_selected):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        out, selected = attention(w["attention"], rms_norm(x, w["attn_norm"], eps), eps,
                                  dict(hyper), window, quant, want_selected)
        return x + out, selected


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


def block(w, x, eps, hyper: dict, layer: int, quant=identity, collect=None):
    """x: (rows, T, d) float32; `w` one layer's tree."""
    window = hyper["layer_types"][layer] == "sliding_attention"
    static = tuple(sorted((k, v) for k, v in hyper.items() if k in (
        "window", "index_topk", "rope_theta", "swa_rope_theta", "rope_width",
        "swa_rope_width")))
    x, selected = _attend({k: w[k] for k in ("attention", "attn_norm")}, x, eps, static,
                          window, quant, collect is not None)
    if collect is not None and not window:
        collect.setdefault("selected", {})[layer] = np.asarray(selected)
    if "experts" not in w:
        return _dense({k: w[k] for k in ("ffn_norm", "gate", "up", "down")}, x, eps, quant)
    routing = tuple(sorted({**{k: hyper[k] for k in (
        "top_k", "routed_scaling_factor", "first")},
        "count": int(w["experts"]["gate"].shape[0])}.items()))
    ids, weights, load = _route(
        {"ffn_norm": w["ffn_norm"], "experts": {k: w["experts"][k]
                                                for k in ("router", "bias")}},
        x, eps, routing, quant)
    if collect is not None:
        collect.setdefault("routed", {})[layer] = np.asarray(ids).reshape(
            x.shape[0], x.shape[1], -1)
    busiest = max(int(np.asarray(load).max()), 1)
    rows = min(-(-busiest // 256) * 256, ids.shape[0])
    return _routed(w, x, ids, weights, eps, routing, rows, quant)


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


def logits_at(tokens, rows, top, block_at, layers: int, quant=identity, eps: float = 1e-5,
              collect=None):
    """Full forward over `tokens` (batch, T), layer by layer so that one
    layer's weights are alive at a time; logits at `rows` (batch, R)."""
    hyper = top["hyper"]
    arrays = {k: v for k, v in top.items() if k != "hyper"}
    x = embed(arrays, tokens)
    for layer in range(layers):
        x = block(block_at(layer), x, eps, hyper, layer, quant=quant, collect=collect)
    return head(arrays, x, rows, eps, quant=quant)
