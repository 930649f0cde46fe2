"""Decoder LM with latent attention and routed experts: the block of the
DeepSeek-V2 family, and of its descendants that mix FULL layers (with a
learned sparse attention's indexer) and WINDOW layers (their own latent
geometry, a bounded cache), gate their heads, rescale their latents and
route by a sigmoid with a selection bias. DeepSeek-V2 is the case of no
window layer, no indexer, no gate, no rescale and softmax scoring.

Every layer is ``x + attn(rms(x))`` then ``x + ffn(rms(x))`` with RMSNorm,
no bias anywhere, an untied head. The first ``first_dense`` layers have a
gated feed-forward of width ``d_ff``; the others the routed layer below.

Latent attention (``heads`` query heads, one latent a token)::

    c_q            = rms(q_a y)                        (q_lora_rank)
    [q_nope, q_pe] = split(q_b c_q)     a head         (qk_nope + qk_rope)
    [c_kv, k_pe]   = split(kv_a y)      ONE k_pe for all heads
    c_kv           = rms(c_kv)                         (kv_lora_rank)
    [k_nope, v]    = split(kv_b c_kv)   a head         (qk_nope + v_head)
    q_pe, k_pe     = rope(q_pe), rope(k_pe)  at the token's own position
    score_h        = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale
    out            = out concat_h(softmax_h v_h)

The rotary pairs are (2i, 2i+1); ``rope`` is YaRN's parameters (theta,
factor, original_max_position_embeddings, beta_fast, beta_slow, mscale,
mscale_all_dim) or None for the plain rotary at theta 10,000; the softmax
scale is ``(qk_nope + qk_rope)^-0.5 * mscale(factor, mscale_all_dim)^2``.

Routed layer: ``p = softmax(router y)`` over all ``n_routed_experts`` in
float32; the ``topk_group`` best of ``n_group`` groups keep their ``p``;
the ``top_k`` largest are taken, weighted ``routed_scaling_factor * p``, not
renormalised; ``ffn(y) = shared(y) + sum_e w_e expert_e(y)``, every expert
and the shared one a gated feed-forward. The module is told which experts
it holds, ``experts_held = (first, count)``: it routes over all, holds the
matrices of its own and computes their part (``ops.routed_experts``); with
``(0, n_routed_experts)``, the default, it is the uncut layer.

``decode=True`` is the serving path. What a layer caches a token is the
latent: ``c_kv`` after its norm and ``k_pe`` after its rotation, one leaf
``cached_latent`` of ``kv_lora_rank + qk_rope`` values and a
``cache_index``; no key or value a head. It is attended through the
serving pool's block table (``models.decode_cache.attend_paged``, which is
handed ``kv_b``): a decode step in the absorbed form (``q_nope_h W_uk_h`` is
a query of ``kv_lora_rank`` against ``c_kv``, the softmax weighs ``c_kv``
itself and ``W_uv_h`` expands the result, so ``heads`` query heads read one
key whose first ``kv_lora_rank`` columns are also its value), a prefill
chunk with keys and values a head expanded from each block as it is read
(``ops.attention`` says why each). A row's rotary position is its own
cache index: a lane's its own length, a chunk's ``start .. start + T - 1``.
Given ``valid`` (a chunk's real tokens), the apply returns the logits of
position ``valid - 1`` alone and routes no padding token. Each routed layer
sows its counters (assignments, those on held experts, experts touched and
held, the passes over an expert's matrices that the grouped kernel makes of
them, the busiest and the mean expert's load) into the ``counters``
collection.

The descendants' parts, each off by default. Layer ``i`` is **full** or
**window** by ``layer_types[i]`` (``"full_attention"`` /
``"sliding_attention"``).

A full layer (the sizes above; ``rope`` its rotary's parameters)::

    c_q            = rms(W_qa y) * (d / q_lora_rank)^0.5      latent_rescale
    [c_kv, k_pe]   = split(W_kva y);  c_kv = rms(c_kv) * (d / kv_lora_rank)^0.5
    indexer:  qI_j = W_qI c_q      (index heads x index width)
              kI   = layernorm(W_kI y)      ONE a token, scale only
              rope on the first qk_rope values of qI_j and kI
              w    = W_w y * heads^-0.5 * width^-0.5
              I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])     s <= t
              S_t  = the index_topk columns s <= t with the largest I[t, s];
                     every s <= t while t < index_topk; the lower column
                     wins a tie
    score_h[t, s]  = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale   s in S_t
    o_h            = softmax_s(score_h) v_h
    g              = sigmoid(W_g y)         one a head        head_gate
    out            = W_o concat_h(g_h * o_h)

A window layer: the same latent attention at ``window_geometry``'s sizes (its
own heads, ranks, head widths and rotary), no indexer, the mask ``t -
sliding_window < s <= t``, its own gate and rescale.

The router of such a model (``scoring="sigmoid"``): ``s = sigmoid(W_r y)``
in float32 over all experts; the ``top_k`` largest of ``s + b`` are taken
(``b`` a learned bias, ``selection_bias``, used to select only); weights
``s_e / sum over the chosen of s`` (``norm_topk_prob``) times
``routed_scaling_factor``.

On the serving path a full layer caches, a token, its latent and its index
key after norm and rotation (``cached_index_key``, a second paged leaf), a
window layer its latent in a ring a slot bounded by the window
(``cached_window_latent``; ``models.decode_cache``). A full layer sows
``sparse_columns_live`` (the sum over its real queries of ``t + 1``) and
``sparse_columns_selected`` (the sum of ``|S_t|``) into ``counters``.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models import register_model
from elephas_tpu.models.decode_cache import Indexer, attend_paged
from elephas_tpu.models.jamba import RMSNorm, _rows, gated_feed_forward

Rope = Optional[Tuple[float, ...]]


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary_frequencies(dim: int, rope: Rope) -> np.ndarray:
    """The ``dim / 2`` angular frequencies: YaRN blends ``theta_i`` and
    ``theta_i / factor`` by a linear ramp between the two correction
    dimensions (``beta_fast`` and ``beta_slow`` rotations over the original
    context)."""
    theta = 10000.0 if rope is None else rope[0]
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope is None or rope[1] <= 1:
        return plain
    _, factor, original, beta_fast, beta_slow = rope[:5]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(width: int, rope: Rope) -> float:
    return width ** -0.5 * (1.0 if rope is None
                            else yarn_mscale(rope[1], rope[6]) ** 2)


def rotate(x, positions, rope: Rope):
    """``x``: (batch, T, ..., dim) with pairs (2i, 2i+1); ``positions``:
    (batch, T). Float32."""
    dim = x.shape[-1]
    angle = positions[..., None].astype(jnp.float32) * jnp.asarray(
        rotary_frequencies(dim, rope), jnp.float32)
    m = 1.0 if rope is None else (yarn_mscale(rope[1], rope[5])
                                  / yarn_mscale(rope[1], rope[6]))
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (dim // 2,))
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


class LatentAttention(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope: Rope = None
    window: Optional[int] = None  # a query sees this many columns, its own too
    indexer: Optional[Tuple[int, int, int]] = None  # (heads, width, top_k)
    head_gate: bool = False
    latent_rescale: bool = False
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None):
        b, T, d_model = x.shape
        heads, rank = self.num_heads, self.kv_lora_rank
        nope, pe = self.qk_nope_head_dim, self.qk_rope_head_dim
        scale = softmax_scale(nope + pe, self.rope)
        if self.window is not None and self.indexer is not None:
            raise ValueError("a window layer has no indexer")

        c_q = RMSNorm(self.eps, name="q_a_norm")(
            nn.Dense(self.q_lora_rank, use_bias=False, dtype=self.dtype, name="q_a")(x))
        if self.latent_rescale:
            c_q = c_q * (d_model / self.q_lora_rank) ** 0.5
        q = nn.DenseGeneral((heads, nope + pe), use_bias=False, dtype=self.dtype,
                            name="q_b")(c_q.astype(self.dtype))  # (b, T, h, f)
        kv = nn.Dense(rank + pe, use_bias=False, dtype=self.dtype, name="kv_a")(x)
        c_kv = RMSNorm(self.eps, name="kv_a_norm")(kv[..., :rank])
        if self.latent_rescale:
            c_kv = c_kv * (d_model / rank) ** 0.5
        # W_uk and W_uv side by side, by head: (rank, heads, nope + v)
        kv_b = self.param("kv_b", nn.initializers.lecun_normal(),
                          (rank, heads, nope + self.v_head_dim))
        kv_b = kv_b.astype(self.dtype)

        leaf = "cached_latent" if self.window is None else "cached_window_latent"
        carried = self.decode and self.has_variable("cache", leaf)
        if self.decode:
            # a window layer's leaf says by its length what a slot keeps of
            # it: the window's columns behind the ``T`` at hand
            kept = T if self.window is None else self.window - 1 + T
            cached_latent = self.variable("cache", leaf, jnp.zeros,
                                          (b, 1, kept, rank + pe), self.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.array(0, jnp.int32))
        if carried:
            start = jnp.broadcast_to(cache_index.value, (b,))
        else:  # a full sequence, or the pass that shapes the cache
            start = jnp.zeros((b,), jnp.int32)
        positions = start[:, None] + jnp.arange(T)[None, :]
        with jax.named_scope("rope"):
            q_pe = rotate(q[..., nope:], positions, self.rope)
            k_pe = rotate(kv[..., rank:], positions, self.rope)

        index = None
        if self.indexer is not None:
            i_heads, i_width, top_k = self.indexer
            with jax.named_scope("indexer"):
                qi = nn.DenseGeneral((i_heads, i_width), use_bias=False,
                                     dtype=self.dtype, name="index_q")(
                                         c_q.astype(self.dtype))
                ki = nn.LayerNorm(epsilon=self.eps, use_bias=False, name="index_k_norm")(
                    nn.Dense(i_width, use_bias=False, dtype=self.dtype,
                             name="index_k")(x).astype(jnp.float32))
                wi = nn.Dense(i_heads, use_bias=False, dtype=jnp.float32,
                              name="index_w")(x.astype(jnp.float32)) * (
                                  i_heads ** -0.5 * i_width ** -0.5)
                qi = jnp.concatenate([rotate(qi[..., :pe], positions, self.rope),
                                      qi[..., pe:].astype(jnp.float32)], -1)
                ki = jnp.concatenate([rotate(ki[..., :pe], positions, self.rope),
                                      ki[..., pe:]], -1)
                qi, ki = qi.astype(self.dtype), ki.astype(self.dtype)
            if self.decode:
                cached_index_key = self.variable(
                    "cache", "cached_index_key", jnp.zeros, (b, 1, T, i_width),
                    self.dtype)
                index = Indexer(jnp.moveaxis(qi, 2, 1), wi, ki[:, None],
                                cached_index_key, top_k)

        if carried:
            if paged is None:
                raise NotImplementedError(
                    "a latent cache is attended through the serving pool's "
                    "block table only: there is no contiguous row of keys "
                    "and values a head to gather")
            with jax.named_scope("assemble"):  # a query's and a cache row's halves
                q_all = jnp.concatenate(
                    [q[..., :nope].astype(jnp.float32), q_pe], -1).astype(self.dtype)
                latent = jnp.concatenate([c_kv, k_pe], -1).astype(self.dtype)[:, None]
            out = attend_paged(jnp.moveaxis(q_all, 2, 1), latent, None,
                               cached_latent, None, cache_index, active, paged,
                               scale=scale, kv_b=kv_b, window=self.window,
                               indexer=index)  # (b, h, T, v)
            if index is not None:
                out, (live, selected) = out
                self.sow("counters", "sparse_columns_live", live)
                self.sow("counters", "sparse_columns_selected", selected)
            out = jnp.moveaxis(out, 1, 2)
        else:
            # the published form: keys and values a head, expanded
            expanded = jnp.einsum("btr,rhf->bthf", c_kv.astype(self.dtype), kv_b,
                                  preferred_element_type=jnp.float32)
            scores = (jnp.einsum("bqhf,bkhf->bhqk", q[..., :nope].astype(jnp.float32),
                                 expanded[..., :nope])
                      + jnp.einsum("bqhf,bkf->bhqk", q_pe, k_pe)) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            if self.window is not None:
                mask &= ~jnp.tril(jnp.ones((T, T), bool), -self.window)
            if self.indexer is not None:
                from elephas_tpu.ops.sparse_index import select_columns

                index_scores = (jax.nn.relu(jnp.einsum(
                    "bqhd,bkd->bqhk", qi, ki, preferred_element_type=jnp.float32))
                    * wi[..., None]).sum(2)  # (b, T, T)
                chosen = select_columns(
                    index_scores.reshape(b * T, T), jnp.tile(jnp.arange(T), b),
                    self.indexer[2], "paged_xla").reshape(b, 1, T, T) > 0
                scores = jnp.where(chosen, scores, jnp.finfo(jnp.float32).min)
            scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            out = jnp.einsum("bhqk,bkhf->bqhf", nn.softmax(scores, -1),
                             expanded[..., nope:])
        if self.head_gate:  # one gate a head, from the layer's normed input
            gate = nn.Dense(heads, use_bias=False, dtype=self.dtype, name="gate")(x)
            with jax.named_scope("head_gate"):
                out = out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))[..., None]
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="out")(out.astype(self.dtype))


class GatedFeedForward(nn.Module):
    d_ff: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        return gated_feed_forward(y, self.d_ff, self.dtype)


class Head(nn.Module):
    """The untied head: logits in float32 from operands in ``dtype``."""
    vocab_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.vocab_size))
        return jnp.einsum("btd,dv->btv", x.astype(self.dtype),
                          kernel.astype(self.dtype),
                          preferred_element_type=jnp.float32)


class RoutedExperts(nn.Module):
    n_routed_experts: int
    experts_held: Tuple[int, int]
    moe_d_ff: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    scoring: str = "softmax"  # or "sigmoid"
    norm_topk_prob: bool = False
    selection_bias: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, live=None):
        """``y``: (tokens, d); ``live``: (tokens,) bool, the tokens that are
        no padding and no idle lane (all of them where it is None)."""
        from elephas_tpu.ops.routed_experts import (
            group_limited_top_k,
            routed_body,
            routed_experts,
            rows_combined,
            weight_passes,
        )

        first, count = self.experts_held
        d, f = y.shape[-1], self.moe_d_ff
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        gate = self.param("gate", init, (count, d, f)).astype(self.dtype)
        up = self.param("up", init, (count, d, f)).astype(self.dtype)
        down = self.param("down", init, (count, f, d)).astype(self.dtype)
        logits = nn.Dense(self.n_routed_experts, use_bias=False, dtype=jnp.float32,
                          name="router")(y.astype(jnp.float32))
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.scoring!r}")
        with jax.named_scope("route"):
            score = nn.softmax(logits, -1) if self.scoring == "softmax" \
                else jax.nn.sigmoid(logits)
            by = None
            if self.selection_bias:  # chooses the experts, weighs none
                by = score + self.param("bias", nn.initializers.zeros,
                                        (self.n_routed_experts,)).astype(jnp.float32)
            ids, p = group_limited_top_k(score, self.n_group, self.topk_group,
                                         self.top_k, by=by)
            if self.norm_topk_prob:
                p = p / p.sum(-1, keepdims=True)
            if live is not None:  # an id past every expert is held nowhere
                ids = jnp.where(live[:, None], ids, self.n_routed_experts)
        body = routed_body(y.shape[0], self.top_k, d, f, y.dtype)
        out, load = routed_experts(y, ids, p * self.routed_scaling_factor,
                                   gate, up, down, first, body=body)
        tokens = y.shape[0] if live is None else live.sum()
        rows, assignments = y.shape[0] * self.top_k, tokens * self.top_k
        for name, value in (
                ("moe_assignments", assignments),
                ("moe_assignments_held", load.sum()),
                ("moe_experts_touched", (load > 0).sum()),
                ("moe_weight_passes", weight_passes(load, rows)),
                ("moe_rows_combined", rows_combined(load, assignments, rows, body)),
                ("moe_experts_held", count),
                ("moe_load_max", load.max()),
                ("moe_load_mean", load.sum() / count)):
            self.sow("counters", name, jnp.asarray(value, jnp.float32))
        return out


class LatentMoELayer(nn.Module):
    routed: bool
    d_ff: int
    attention: dict  # LatentAttention's sizes
    experts: dict    # RoutedExperts' sizes, and the shared experts' width
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None, live=None):
        with jax.named_scope("attention"):
            x = x + LatentAttention(
                **self.attention, eps=self.eps, dtype=self.dtype, decode=self.decode,
                name="attention")(RMSNorm(self.eps, name="attn_norm")(x).astype(self.dtype),
                                  active=active, paged=paged)
        with jax.named_scope("mlp"):
            y = RMSNorm(self.eps, name="ffn_norm")(x).astype(self.dtype)
            if not self.routed:
                return x + gated_feed_forward(y, self.d_ff, self.dtype)
            experts = dict(self.experts)
            shared = GatedFeedForward(experts.pop("shared_d_ff"), self.dtype,
                                      name="shared")(y)
            b, T, d = y.shape
            routed = RoutedExperts(**experts, dtype=self.dtype, name="experts")(
                y.reshape(b * T, d), None if live is None else live.reshape(b * T))
            return x + shared + routed.reshape(b, T, d).astype(shared.dtype)


class LatentMoELM(nn.Module):
    vocab_size: int = 102400
    d_model: int = 256
    num_layers: int = 3
    num_heads: int = 4
    q_lora_rank: int = 64
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    d_ff: int = 512
    first_dense: int = 1
    n_routed_experts: int = 8
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    n_shared_experts: int = 2
    moe_d_ff: int = 64
    top_k: int = 2
    n_group: int = 4
    topk_group: int = 2
    routed_scaling_factor: float = 1.0
    rope: Rope = None
    rms_eps: float = 1e-6
    max_seq_len: int = 163840
    # layer i's kind, "full_attention" or "sliding_attention" (all full: None)
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    # a window layer's (num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    # qk_rope_head_dim, v_head_dim, rope)
    window_geometry: Optional[Tuple] = None
    indexer: Optional[Tuple[int, int, int]] = None  # full layers': (heads, width, top_k)
    head_gate: bool = False
    latent_rescale: bool = False
    scoring: str = "softmax"
    norm_topk_prob: bool = False
    selection_bias: bool = False
    dtype: Any = jnp.float32
    attention: str = "dense"  # the one full-sequence form; the engine sets it
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, pad_offset=None, active=None,
                 paged=None, valid=None):
        del train, pad_offset  # no dropout; serving rows are never left-padded
        if not self.decode and (active is not None or paged is not None
                                or valid is not None):
            raise ValueError("active / paged / valid are the decode=True path's")
        b, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(
            tokens.astype(jnp.int32)).astype(self.dtype)
        attention = dict(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
            rope=self.rope)
        if self.indexer is not None or self.head_gate or self.latent_rescale:
            attention.update(indexer=self.indexer, head_gate=self.head_gate,
                             latent_rescale=self.latent_rescale)
        windowed = None
        if self.layer_types is not None and "sliding_attention" in self.layer_types:
            names = ("num_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                     "qk_rope_head_dim", "v_head_dim", "rope")
            windowed = dict(zip(names, self.window_geometry),
                            window=self.sliding_window, head_gate=self.head_gate,
                            latent_rescale=self.latent_rescale)
        experts = dict(
            n_routed_experts=self.n_routed_experts,
            experts_held=tuple(self.experts_held or (0, self.n_routed_experts)),
            moe_d_ff=self.moe_d_ff, top_k=self.top_k, n_group=self.n_group,
            topk_group=self.topk_group,
            routed_scaling_factor=self.routed_scaling_factor,
            shared_d_ff=self.n_shared_experts * self.moe_d_ff)
        if self.scoring != "softmax" or self.norm_topk_prob or self.selection_bias:
            experts.update(scoring=self.scoring, norm_topk_prob=self.norm_topk_prob,
                           selection_bias=self.selection_bias)
        live = None
        if valid is not None:
            live = jnp.arange(T)[None, :] < valid[:, None]
        elif active is not None:
            live = jnp.broadcast_to(active[:, None], (b, T))
        for i in range(self.num_layers):
            slides = windowed is not None and self.layer_types[i] == "sliding_attention"
            x = LatentMoELayer(i >= self.first_dense, self.d_ff,
                               windowed if slides else attention, experts,
                               eps=self.rms_eps, dtype=self.dtype, decode=self.decode,
                               name=f"Layer_{i}")(x, active=active, paged=paged, live=live)
        if valid is not None:  # the one row a prefill chunk samples from
            x = _rows(x, valid - 1, 1)
        with jax.named_scope("lm_head"):
            x = RMSNorm(self.rms_eps, name="final_norm")(x)
            return Head(self.vocab_size, self.dtype, name="lm_head")(x)


@register_model("latent_moe_lm")
def build_latent_moe_lm(vocab_size=102400, d_model=256, num_layers=3, num_heads=4,
                        q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16, d_ff=512, first_dense=1,
                        n_routed_experts=8, experts_held=None, n_shared_experts=2,
                        moe_d_ff=64, top_k=2, n_group=4, topk_group=2,
                        routed_scaling_factor=1.0, rope=None, rms_eps=1e-6,
                        max_seq_len=163840, layer_types=None, sliding_window=None,
                        window_geometry=None, indexer=None, head_gate=False,
                        latent_rescale=False, scoring="softmax",
                        norm_topk_prob=False, selection_bias=False,
                        dtype="float32"):
    first, count = experts_held or (0, n_routed_experts)
    if n_routed_experts % n_group or not 0 < topk_group <= n_group:
        raise ValueError(f"{n_routed_experts} experts do not divide into "
                         f"{n_group} groups, or top {topk_group} of them")
    if top_k > topk_group * (n_routed_experts // n_group):
        raise ValueError(f"top_k ({top_k}) is more than {topk_group} groups hold")
    if first < 0 or count < 1 or first + count > n_routed_experts:
        raise ValueError(f"experts_held {(first, count)} is no range of the "
                         f"{n_routed_experts} routed experts")
    if qk_rope_head_dim % 2:
        raise ValueError("the rotary width is a number of pairs")
    if layer_types is not None:
        layer_types = tuple(layer_types)
        if len(layer_types) != num_layers or set(layer_types) - {
                "full_attention", "sliding_attention"}:
            raise ValueError(f"layer_types names {num_layers} layers, each "
                             "full_attention or sliding_attention")
        if "sliding_attention" in layer_types:
            if not sliding_window or sliding_window < 1 or window_geometry is None \
                    or len(window_geometry) != 7:
                raise ValueError("a sliding_attention layer needs sliding_window "
                                 "and window_geometry (heads, q rank, kv rank, "
                                 "nope, rope, v head, rope parameters)")
            window_geometry = tuple(window_geometry[:6]) + (
                None if window_geometry[6] is None
                else tuple(float(v) for v in window_geometry[6]),)
    if indexer is not None:
        indexer = tuple(int(v) for v in indexer)
        if len(indexer) != 3 or indexer[1] < qk_rope_head_dim or indexer[2] < 1:
            raise ValueError("indexer is (heads, width >= qk_rope_head_dim, top_k)")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    return LatentMoELM(
        vocab_size=vocab_size, d_model=d_model, num_layers=num_layers,
        num_heads=num_heads, q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, d_ff=d_ff, first_dense=first_dense,
        n_routed_experts=n_routed_experts, experts_held=(first, count),
        n_shared_experts=n_shared_experts, moe_d_ff=moe_d_ff, top_k=top_k,
        n_group=n_group, topk_group=topk_group,
        routed_scaling_factor=routed_scaling_factor,
        rope=None if rope is None else tuple(float(v) for v in rope),
        rms_eps=rms_eps, max_seq_len=max_seq_len, layer_types=layer_types,
        sliding_window=sliding_window, window_geometry=window_geometry,
        indexer=indexer, head_gate=head_gate, latent_rescale=latent_rescale,
        scoring=scoring, norm_topk_prob=norm_topk_prob,
        selection_bias=selection_bias, dtype=jnp.dtype(dtype))
