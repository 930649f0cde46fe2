"""Sparse/linear hybrid decoder LM: MiniCPM-SALA's block. Layer ``l`` is a
block-sparse attention (``"minicpm4"``, InfLLM-v2's) or a lightning
attention (``"lightning-attn"``, linear attention with a fixed decay a
head) by ``mixer_types[l]``; every layer has a gated feed-forward.

``rms`` is RMSNorm with a learned scale; no layer has a bias; the head is
untied. With ``r = scale_depth / sqrt(depth)`` (``depth`` the published
number of layers, whatever the module holds)::

    h_0    = scale_emb * E[token]
    h'     = h + r * mixer(rms(h))
    h_next = h' + r * down(silu(gate y) * up y),  y = rms(h')
    logits = W_head rms(h_L) / (d_model / dim_model_base)

Lightning layer (``lightning_heads`` heads of ``lightning_head_dim``, the
same number of K/V heads, rotary positions at ``rope_theta``, pairs
``(2i, 2i + 1)``, the token's own position), head ``j`` with decay
``lambda_j = exp(-2^(-8 (j + 1) / H) * (1 - l / (depth - 1) + 1e-5))``,
``l`` the layer's published index (``layer_ids``)::

    q, k, v = W_q y, W_k y, W_v y;  q, k = rope(rms_head(q)), rope(rms_head(k))
    S_t     = lambda_j S_{t-1} + k_t^T v_t          (D x D a head, float32)
    o_t     = (q_t * D^-0.5) S_t
    out     = W_o (rms_head(o) * sigmoid(W_g y))    the output norm a head, its
                                                    scale over every head's values

Sparse layer (``num_heads`` query heads on ``num_kv_heads`` K/V heads, no
positions): ``q, k = rms_head(W_q y), rms_head(W_k y)``; compressed keys,
block scores and the selection ``Sel_g(t)`` as ``ops.sparse_index`` sets
them out (``selection``: kernel, stride, block, top_k, init_blocks, window,
dense_len); ``o_h,t`` is softmax attention of head ``h`` over the columns
``s <= t`` of the blocks its group selected; ``out = W_o (concat_h o_h *
sigmoid(W_g' y))``. ``rms_head`` of ``q`` and ``k`` is one scale of ``D``
shared by the heads, in both mixers.

``decode=True`` is the serving path. A lightning layer's ``cache`` holds a
state row a batch element, ``lightning_state`` (``(heads, D, D)``
float32, a STATE leaf of ``models.decode_cache``), and a ``cache_index``
for its positions; a sparse layer's holds ``cached_key`` /
``cached_value`` (its K/V heads), ``cached_compressed_key`` and a
``cache_index``. Over the paged pool a chunk continues every lightning
layer from the slot's state row and leaves the state after ``valid``
tokens; a decode step steps the active lanes' rows alone. A sparse layer
attends the pages it selected (``decode_cache.attend_block_sparse``) and
sows ``sparse_columns_live``, ``sparse_columns_selected`` and
``sparse_blocks_scored`` into ``counters``. Given ``valid``, the apply
returns the logits of position ``valid - 1`` alone, ``(batch, 1,
vocab)``.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models import register_model
from elephas_tpu.models.decode_cache import attend_block_sparse
from elephas_tpu.models.jamba import RMSNorm, _rows, gated_feed_forward
from elephas_tpu.ops.sparse_index import BlockSelection

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def lightning_log_decay(heads: int, layer: int, depth: int) -> np.ndarray:
    """``log lambda_j`` of each head of the layer of published index
    ``layer`` in a model of ``depth`` layers (MiniMax-01's rates)."""
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return (-slopes * (1.0 - layer / (depth - 1) + 1e-5)).astype(np.float32)


def rope(x, positions, theta: float):
    """x: (..., T, heads, D) float32, pairs (2i, 2i+1); positions: (rows, T)."""
    D = x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    angle = positions[..., None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def head_norm(o, eps: float):
    """RMS-normalise each head's values, o: (..., heads, D)."""
    return o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)


def _heads(x, n: int, D: int, name: str, dtype):
    return nn.DenseGeneral((n, D), use_bias=False, dtype=dtype, name=name)(x)


class LightningAttention(nn.Module):
    num_heads: int
    head_dim: int
    layer_id: int
    depth: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, y, active=None, valid=None):
        from elephas_tpu.ops.lightning import (
            lightning_body,
            lightning_chunk,
            lightning_step,
        )

        b, T, d_model = y.shape
        H, D = self.num_heads, self.head_dim
        q = RMSNorm(self.eps, name="q_norm")(_heads(y, H, D, "q", self.dtype))
        k = RMSNorm(self.eps, name="k_norm")(_heads(y, H, D, "k", self.dtype))
        v = _heads(y, H, D, "v", self.dtype).astype(jnp.float32)
        carried = self.decode and self.has_variable("cache", "lightning_state")
        if self.decode:
            state = self.variable("cache", "lightning_state", jnp.zeros, (b, H, D, D),
                                  jnp.float32)
            index = self.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
        at = jnp.broadcast_to(index.value, (b,)) if carried else jnp.zeros((b,), jnp.int32)
        positions = at[:, None] + jnp.arange(T)
        with jax.named_scope("rope"):
            q = rope(q, positions, self.rope_theta) * (D ** -0.5)
            k = rope(k, positions, self.rope_theta)
        log_decay = jnp.asarray(lightning_log_decay(H, self.layer_id, self.depth))
        if carried and active is not None:
            if T != 1:
                raise ValueError("a decode step is one token a lane")
            o, state.value = lightning_step(q[:, 0], k[:, 0], v[:, 0], jnp.exp(log_decay),
                                            state.value, active)
            o = o[:, None]
            index.value = jnp.where(active, index.value + 1, index.value)
        elif carried:  # one slot's chunk, from its state row
            if b != 1:
                raise ValueError(f"a prefill chunk is one slot's; got {b} rows")
            o, s = lightning_chunk(*(jnp.swapaxes(x[0], 0, 1) for x in (q, k, v)),
                                   log_decay, state.value[0],
                                   None if valid is None else valid[0],
                                   body=lightning_body(T, D))
            o = jnp.swapaxes(o, 0, 1)[None]  # (1, T, H, D)
            state.value, index.value = s[None], index.value + T
        else:  # a whole sequence a row, or the pass that shapes the cache
            def one(q_, k_, v_):
                return lightning_chunk(*(jnp.swapaxes(x, 0, 1) for x in (q_, k_, v_)),
                                       log_decay, jnp.zeros((H, D, D), jnp.float32))[0]

            o = jnp.swapaxes(jax.vmap(one)(q, k, v), 1, 2)  # (b, T, H, D)
        with jax.named_scope("output_norm"):
            scale = self.param("o_norm", nn.initializers.ones, (H * D,))
            o = head_norm(o, self.eps).reshape(b, T, H * D) * scale
            gate = nn.Dense(H * D, use_bias=False, dtype=self.dtype, name="gate")(y)
            o = (o * nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype, name="o")(o)


def _full_block_sparse(q, k, v, sel: BlockSelection, scale: float):
    """The sparse layer over a whole sequence, no cache: q (b, Hq, T, D),
    k, v (b, Hkv, T, D). Every (query, column) score is computed and the
    selection masks them."""
    from elephas_tpu.ops.sparse_index import _mean_keys, block_scores, select_blocks

    b, Hq, T, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    n_keys = -(-T // sel.block) * sel.keys_per_block
    last = jnp.arange(T)
    blocks = last // sel.block

    def one(q1, k1, v1):
        keys = _mean_keys(k1[None], (jnp.arange(n_keys) * sel.stride)[None], sel.kernel)[0]
        qg = jnp.moveaxis(q1, 1, 0).reshape(T, Hkv, g, D)
        scores = block_scores(qg, jnp.moveaxis(keys, 0, 1), last, sel, scale)
        mask = select_blocks(scores.reshape(T * Hkv, -1), jnp.repeat(last, Hkv), sel,
                             "paged_xla").reshape(T, Hkv, -1)
        seen = jnp.take(mask, blocks, axis=2) > 0  # (T, Hkv, T): the column's block
        seen = jnp.moveaxis(seen, 1, 0) & (last[None, None] <= last[None, :, None])
        s = jnp.einsum("kgtd,ksd->kgts", q1.reshape(Hkv, g, T, D), k1,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen[:, None], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, -1).astype(v1.dtype)
        return jnp.einsum("kgts,ksd->kgtd", p, v1,
                          preferred_element_type=jnp.float32).reshape(Hq, T, D)

    return jax.vmap(one)(q, k, v).astype(q.dtype)


class SparseAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    selection: Tuple[int, ...]
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, y, active=None, paged=None):
        b, T, d_model = y.shape
        Hq, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim
        sel = BlockSelection(*self.selection)
        scale = D ** -0.5

        def heads(n, name, norm):
            x = _heads(y, n, D, name, self.dtype)
            if norm:
                x = RMSNorm(self.eps, name=name + "_norm")(x)
            return jnp.swapaxes(x.astype(self.dtype), 1, 2)  # (b, n, T, D)

        q, k, v = heads(Hq, "q", True), heads(Hkv, "k", True), heads(Hkv, "v", False)
        if self.decode:
            init_pass = not self.has_variable("cache", "cached_key")
            shape = (b, Hkv, T, D)
            cached_key = self.variable("cache", "cached_key", jnp.zeros, shape, self.dtype)
            cached_value = self.variable("cache", "cached_value", jnp.zeros, shape,
                                         self.dtype)
            compressed = self.variable("cache", "cached_compressed_key", jnp.zeros,
                                       (b, Hkv, sel.stride, D), self.dtype)
            index = self.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
        if self.decode and not init_pass:
            if paged is None:
                raise NotImplementedError("a block-sparse layer decodes over the paged "
                                          "pool alone")
            out, (live, selected, scored) = attend_block_sparse(
                q, k, v, cached_key, cached_value, compressed, index, active, paged,
                sel, scale)
            self.sow("counters", "sparse_columns_live", live)
            self.sow("counters", "sparse_columns_selected", selected)
            self.sow("counters", "sparse_blocks_scored", scored)
        else:
            out = _full_block_sparse(q, k, v, sel, scale)
        out = jnp.swapaxes(out, 1, 2).reshape(b, T, Hq * D)
        gate = nn.Dense(Hq * D, use_bias=False, dtype=self.dtype, name="gate")(y)
        out = (out.astype(jnp.float32) * nn.sigmoid(gate.astype(jnp.float32)))
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype,
                        name="o")(out.astype(self.dtype))


class SALALayer(nn.Module):
    kind: str
    d_ff: int
    residual: float
    lightning: dict
    sparse: dict
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None, valid=None):
        y = RMSNorm(self.eps, name="mixer_norm")(x).astype(self.dtype)
        if self.kind == LIGHTNING:
            with jax.named_scope("lightning"):
                mixed = LightningAttention(**self.lightning, eps=self.eps, dtype=self.dtype,
                                           decode=self.decode, name="lightning")(
                    y, active=active, valid=valid)
        else:
            with jax.named_scope("sparse_attention"):
                mixed = SparseAttention(**self.sparse, eps=self.eps, dtype=self.dtype,
                                        decode=self.decode, name="attention")(
                    y, active=active, paged=paged)
        x = x + (self.residual * mixed.astype(jnp.float32)).astype(x.dtype)
        with jax.named_scope("mlp"):
            y = RMSNorm(self.eps, name="mlp_norm")(x).astype(self.dtype)
            ff = gated_feed_forward(y, self.d_ff, self.dtype)
            return x + (self.residual * ff.astype(jnp.float32)).astype(x.dtype)


class MiniCPMSALA(nn.Module):
    vocab_size: int = 73448
    d_model: int = 256
    mixer_types: Tuple[str, ...] = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
    layer_ids: Optional[Tuple[int, ...]] = None  # published index a layer
    depth: Optional[int] = None                  # published layers
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    lightning_heads: int = 4
    lightning_head_dim: int = 64
    d_ff: int = 1024
    selection: Tuple[int, ...] = (32, 16, 64, 64, 1, 2048, 8192)
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 524288
    dtype: Any = jnp.float32
    attention: str = "dense"  # the one full-sequence form; the engine sets it
    decode: bool = False

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pad_offset=None, active=None,
                 paged=None, valid=None):
        del train, pad_offset  # no dropout; serving rows are never left-padded
        if not self.decode and (active is not None or paged is not None
                                or valid is not None):
            raise ValueError("active / paged / valid are the decode=True path's")
        depth = self.depth or self.num_layers
        ids = self.layer_ids or tuple(range(self.num_layers))
        embed = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")
        x = (embed(tokens.astype(jnp.int32)).astype(jnp.float32) * self.scale_emb
             ).astype(self.dtype)
        sparse = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                      head_dim=self.head_dim, selection=tuple(self.selection))
        for i, kind in enumerate(self.mixer_types):
            lightning = dict(num_heads=self.lightning_heads,
                             head_dim=self.lightning_head_dim, layer_id=ids[i],
                             depth=depth, rope_theta=self.rope_theta)
            x = SALALayer(kind, self.d_ff, self.scale_depth / math.sqrt(depth), lightning,
                          sparse, eps=self.rms_eps, dtype=self.dtype, decode=self.decode,
                          name=f"Layer_{i}")(x, active=active, paged=paged, valid=valid)
        if valid is not None:  # the one row a prefill chunk samples from
            x = _rows(x, valid - 1, 1)
        with jax.named_scope("lm_head"):
            x = RMSNorm(self.rms_eps, name="final_norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (self.d_model, self.vocab_size))
            logits = jnp.einsum("btd,dv->btv", x.astype(self.dtype), head.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            return logits / (self.d_model / self.dim_model_base)


@register_model("minicpm_sala_lm")
def build_minicpm_sala_lm(vocab_size=73448, d_model=256, mixer_types=(SPARSE, LIGHTNING,
                          LIGHTNING, LIGHTNING), layer_ids=None, depth=None, num_heads=4,
                          num_kv_heads=2, head_dim=64, lightning_heads=4,
                          lightning_head_dim=64, d_ff=1024,
                          selection=(32, 16, 64, 64, 1, 2048, 8192), scale_emb=12.0,
                          scale_depth=1.4, dim_model_base=256, rope_theta=10000.0,
                          rms_eps=1e-6, max_seq_len=524288, dtype="float32"):
    mixer_types = tuple(mixer_types)
    if set(mixer_types) - {SPARSE, LIGHTNING}:
        raise ValueError(f"mixer_types are {SPARSE!r} or {LIGHTNING!r}, got {mixer_types}")
    if SPARSE not in mixer_types:
        raise ValueError("no sparse layer: the serving pool reads a row's length "
                         "from a paged layer's cache index")
    sel = BlockSelection(*selection)
    if num_heads % num_kv_heads or sel.block % sel.stride or \
            sel.window < sel.block + sel.kernel or \
            sel.init_blocks + -(-sel.window // sel.block) + 1 > sel.top_k:
        raise ValueError(f"heads {num_heads} on {num_kv_heads} K/V heads and the selection "
                         f"{sel} do not fit: whole groups, whole keys a block, a window "
                         "that holds every block whose keys are not all complete, and "
                         "room in top_k for the forced blocks")
    if layer_ids is not None and len(layer_ids) != len(mixer_types):
        raise ValueError("layer_ids gives one published index a layer")
    return MiniCPMSALA(
        vocab_size=vocab_size, d_model=d_model, mixer_types=mixer_types,
        layer_ids=None if layer_ids is None else tuple(layer_ids), depth=depth,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        lightning_heads=lightning_heads, lightning_head_dim=lightning_head_dim, d_ff=d_ff,
        selection=tuple(sel), scale_emb=scale_emb, scale_depth=scale_depth,
        dim_model_base=dim_model_base, rope_theta=rope_theta, rms_eps=rms_eps,
        max_seq_len=max_seq_len, dtype=jnp.dtype(dtype))
