"""Decoder-only transformer LM — the long-context flagship.

Not present in the reference (SURVEY.md §5.7: no long-context support
anywhere in elephas); included because long sequences are first-class in
the TPU rebuild. The attention implementation is pluggable:

- ``attention='dense'`` — plain softmax attention (XLA-fused),
- ``attention='flash'`` — Pallas blockwise kernel (``elephas_tpu.ops``),
- ``attention='ring'`` — sequence parallelism over the ``'seq'`` mesh
  axis via K/V rotation (``elephas_tpu.parallel.ring_attention``),
- ``attention='ulysses'`` — sequence parallelism via seq<->heads
  all-to-all re-sharding (``elephas_tpu.parallel.ulysses``),
- ``attention='auto'`` — topology-driven: under a bound ``'seq'`` mesh
  axis picks ulysses when the head count divides the axis (one dense
  shuffle instead of n−1 ring hops) and ring otherwise (works for ANY
  head count); outside shard_map falls back to the length-dispatched
  flash kernel. All choices are exact attention, so 'auto' is safe as
  a default — the user never has to know the topology math.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models import register_model
from elephas_tpu.models.decode_cache import attend_paged


def dense_causal_attention(q, k, v):
    """Reference softmax attention. q/k/v: (batch, heads, seq, head_dim)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seq = q.shape[2]
    mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


class SelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.float32
    attention: str = "dense"
    decode: bool = False

    @nn.compact
    def __call__(self, x, pad_offset=None, active=None, paged=None):
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        qkv = nn.DenseGeneral((3, self.num_heads, head_dim), dtype=self.dtype,
                              name="qkv")(x)
        q, k, v = jnp.moveaxis(qkv, -3, 0)  # each (batch, seq, heads, head_dim)
        q = jnp.transpose(q, (0, 2, 1, 3))
        k = jnp.transpose(k, (0, 2, 1, 3))
        v = jnp.transpose(v, (0, 2, 1, 3))
        if self.decode:
            return self._decode_attend(x, q, k, v, d_model, pad_offset, active,
                                       paged)
        attention = self.attention
        if attention == "auto" and not self.is_initializing():
            # Resolved at trace time (axis size is static): sequence-
            # parallel layout by topology under a bound 'seq' axis, flash
            # dispatch otherwise. Exact attention either way.
            from elephas_tpu.parallel.ring_attention import seq_axis_size_or_none

            n = seq_axis_size_or_none()
            if n is None:
                attention = "flash"
            else:
                attention = "ulysses" if self.num_heads % n == 0 else "ring"
        if attention == "flash":
            from elephas_tpu.ops.attention import flash_attention

            out = flash_attention(q, k, v, causal=True)
        elif (
            attention in ("ring", "ulysses") and not self.is_initializing()
        ):
            # Sequence-parallel: must be called inside shard_map with the
            # sequence dimension sharded over the 'seq' mesh axis (see
            # elephas_tpu.parallel.seq_parallel). During module init (which
            # runs outside shard_map, where the axis is unbound) the dense
            # path traces instead — attention has no parameters, so the
            # param structure is identical. 'ring' rotates K/V shards;
            # 'ulysses' re-shards seq<->heads with two all_to_alls and
            # runs full-length flash attention per head subset.
            if attention == "ring":
                from elephas_tpu.parallel.ring_attention import ring_attention

                out = ring_attention(q, k, v, causal=True)
            else:
                from elephas_tpu.parallel.ulysses import ulysses_attention

                out = ulysses_attention(q, k, v, causal=True)
        elif attention in ("dense", "ring", "ulysses", "auto"):
            out = dense_causal_attention(q, k, v)
        else:
            # A silent dense fallback under sequence parallelism would
            # compute shard-LOCAL attention — wrong math that still
            # converges. Unknown names must fail loudly.
            raise ValueError(
                f"unknown attention={self.attention!r}; expected one of "
                "'dense', 'flash', 'ring', 'ulysses', 'auto'"
            )
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(x.shape[0], x.shape[1], d_model)
        return nn.DenseGeneral(d_model, dtype=self.dtype, name="out")(out)

    def _decode_attend(self, x, q, k, v, d_model, pad_offset=None,
                       active=None, paged=None):
        """Incremental (KV-cache) attention for autoregressive sampling.

        The cache is SHAPED on the init pass (which feeds a full-length
        dummy, flax's standard decode protocol) and FILLED by applies:
        the current block's k/v land at ``cache_index`` (seq may be >1 —
        batched PREFILL fills the whole prompt in one forward — or 1 per
        sampling step), and each query attends over everything cached up
        to its own position. ``cache_index`` is a scalar () when every
        row writes the same column (``generate`` — left-padding aligns
        the batch) or a (batch,) vector of independent per-row columns
        (the serving KV pool, where each slot is mid-decode at its own
        depth). ``pad_offset`` (batch,) masks each row's leading
        left-pad columns out of attention. ``active`` (batch,) bool —
        serving only — freezes INACTIVE rows' ``cache_index``: free pool
        slots ride along in the fixed-shape decode batch for the whole
        pool lifetime, and without the freeze their index vectors march
        past ``max_len`` while nothing is admitted. ``paged``
        (``PagedDecode``) — serving's paged pool only — says the K/V
        variables ARE the pool's physical blocks: a decode step's one
        column a lane, or a prefill chunk's columns of one slot, go into
        their blocks and the queries attend through the block table
        (``decode_cache.attend_paged``), no contiguous row is ever
        built. Training never touches this path
        — it exists for ``generate`` and ``serving``."""
        b, h, seq, head_dim = q.shape
        init_pass = not self.has_variable("cache", "cached_key")
        cached_key = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((b, h, seq, head_dim), self.dtype),
        )
        cached_value = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((b, h, seq, head_dim), self.dtype),
        )
        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.array(0, jnp.int32)
        )
        if init_pass:
            # Shaping pass only: ordinary causal attention; caches start
            # zeroed at the full length.
            out = dense_causal_attention(q, k, v)
        elif paged is not None:
            out = attend_paged(q, k.astype(self.dtype), v.astype(self.dtype),
                               cached_key, cached_value, cache_index, active,
                               paged)
        else:
            from elephas_tpu.ops.attention import cache_attention_mask

            idx = cache_index.value
            if idx.ndim == 0:
                ck = jax.lax.dynamic_update_slice(
                    cached_key.value, k.astype(self.dtype), (0, 0, idx, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cached_value.value, v.astype(self.dtype), (0, 0, idx, 0)
                )
            else:
                # Per-row write positions: one scatter per row (vmapped
                # dynamic_update_slice over the batch dim).
                row_update = jax.vmap(
                    lambda cache, blk, i: jax.lax.dynamic_update_slice(
                        cache, blk, (0, i, 0)
                    )
                )
                ck = row_update(cached_key.value, k.astype(self.dtype), idx)
                cv = row_update(cached_value.value, v.astype(self.dtype), idx)
            cached_key.value = ck
            cached_value.value = cv
            if active is not None:
                if idx.ndim == 0:
                    raise ValueError(
                        "active masks require per-row (batch,) cache "
                        "indices (the serving pool layout); generate()'s "
                        "scalar index path never passes active"
                    )
                cache_index.value = jnp.where(active, idx + seq, idx)
            else:
                cache_index.value = idx + seq
            max_len = ck.shape[2]
            scale = 1.0 / np.sqrt(head_dim)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, ck) * scale
            valid = cache_attention_mask(max_len, seq, idx, pad_offset)
            scores = jnp.where(valid, scores, jnp.finfo(scores.dtype).min)
            weights = nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", weights, cv)
            if pad_offset is not None:
                # Queries at left-pad columns have NO valid key (their
                # softmax row is all -inf → NaN). Zero them so the pad
                # columns' residual stream stays finite — otherwise the
                # NEXT layer caches NaN keys there and 0-weight * NaN
                # poisons every real query downstream.
                qcols = idx + jnp.arange(seq) if jnp.asarray(idx).ndim == 0 \
                    else idx[:, None] + jnp.arange(seq)[None, :]
                qpad = qcols < pad_offset[:, None]  # (batch, seq)
                out = jnp.where(qpad[:, None, :, None], 0.0, out)
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(
            x.shape[0], x.shape[1], d_model
        )
        return nn.DenseGeneral(d_model, dtype=self.dtype, name="out")(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    attention: str = "dense"
    decode: bool = False

    @nn.compact
    def __call__(self, x, pad_offset=None, active=None, paged=None):
        d_model = x.shape[-1]
        # Model-part names for the device trace (HLO metadata only).
        with jax.named_scope("attention"):
            y = nn.LayerNorm(dtype=jnp.float32)(x)
            x = x + SelfAttention(self.num_heads, dtype=self.dtype,
                                  attention=self.attention,
                                  decode=self.decode)(y, pad_offset=pad_offset,
                                                      active=active,
                                                      paged=paged)
        with jax.named_scope("mlp"):
            y = nn.LayerNorm(dtype=jnp.float32)(x)
            h = nn.Dense(d_model * self.mlp_ratio, dtype=self.dtype)(y)
            h = nn.gelu(h)
            return x + nn.Dense(d_model, dtype=self.dtype)(h)


class TransformerLM(nn.Module):
    vocab_size: int = 32000
    d_model: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_seq_len: int = 2048
    dtype: Any = jnp.float32
    attention: str = "dense"
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, pad_offset=None,
                 active=None, paged=None):
        seq = tokens.shape[1]
        x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(
            tokens.astype(jnp.int32)
        )
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_seq_len, self.d_model),
        )
        if self.decode:
            return self._decode_forward(tokens, x, pos, seq, pad_offset,
                                        active, paged)
        if pad_offset is not None or active is not None or paged is not None:
            raise ValueError(
                "pad_offset / active / paged (ragged left-padded serving "
                "batches) are only supported on the decode=True path"
            )
        from elephas_tpu.parallel.ring_attention import (
            require_seq_axis,
            seq_axis_size_or_none,
        )

        seq_parallel = self.attention in ("ring", "ulysses") or (
            # 'auto' is sequence-parallel exactly when a 'seq' axis is
            # bound (mirrors SelfAttention's trace-time resolution).
            self.attention == "auto" and seq_axis_size_or_none() is not None
        )
        if seq_parallel and not self.is_initializing():
            # Under sequence parallelism `tokens` is the local shard; index
            # the positional table at global positions.
            offset = require_seq_axis(
                feature=f"attention='{self.attention}'"
            ) * seq
            x = (x + jax.lax.dynamic_slice_in_dim(pos, offset, seq, axis=0)).astype(
                self.dtype
            )
        else:
            x = (x + pos[:seq]).astype(self.dtype)
        for _ in range(self.num_layers):
            x = Block(self.num_heads, dtype=self.dtype, attention=self.attention)(x)
        with jax.named_scope("lm_head"):
            x = nn.LayerNorm(dtype=jnp.float32)(x.astype(jnp.float32))
            # Next-token logits, tied head kept separate for simplicity.
            return nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(x)

    def _decode_forward(self, tokens, x, pos, seq, pad_offset=None,
                        active=None, paged=None):
        """Incremental forward for sampling: positional embedding from a
        module-level position counter (advanced by each apply's block
        length — the batched prompt prefill, then one token per sampling
        step), ordinary blocks with KV-cache attention. Init pass
        (full-length dummy) shapes the caches and the parameter tree
        identically to the training model, so trained params drop in.

        ``pos_index`` mirrors the layers' ``cache_index``: scalar for
        the aligned ``generate`` batch, (batch,) per-row for serving
        slots. With ``pad_offset`` set, a row's REAL position is its
        cache column minus its left-pad count, so a ragged row embeds
        its first real token at position 0 — token-identical to
        decoding that row alone."""
        init_pass = not self.has_variable("cache", "pos_index")
        pos_index = self.variable(
            "cache", "pos_index", lambda: jnp.array(0, jnp.int32)
        )
        if init_pass:
            x = (x + pos[:seq]).astype(self.dtype)
        else:
            idx = pos_index.value
            if active is not None:
                # Serving pool: free slots' position counters freeze in
                # lockstep with their frozen layer cache_index vectors.
                pos_index.value = jnp.where(active, idx + seq, idx)
            else:
                pos_index.value = idx + seq
            if idx.ndim == 0 and pad_offset is None:
                x = (
                    x + jax.lax.dynamic_slice_in_dim(pos, idx, seq, axis=0)
                ).astype(self.dtype)
            else:
                cols = idx[..., None] + jnp.arange(seq)  # (seq,) or (b, seq)
                if pad_offset is not None:
                    cols = cols - pad_offset[:, None]
                # Pad columns clip to position 0 — their embeddings are
                # garbage but masked out of every real query's attention.
                cols = jnp.clip(cols, 0, self.max_seq_len - 1)
                x = (x + jnp.take(pos, cols, axis=0)).astype(self.dtype)
        for _ in range(self.num_layers):
            x = Block(self.num_heads, dtype=self.dtype, attention="dense",
                      decode=True)(x, pad_offset=pad_offset, active=active,
                                   paged=paged)
        with jax.named_scope("lm_head"):
            x = nn.LayerNorm(dtype=jnp.float32)(x.astype(jnp.float32))
            return nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(x)


def sample_tokens(logits, key, greedy, top_k, temperature):
    """Shared sampling head for ``generate`` and the serving engine:
    greedy argmax, or top-k-truncated categorical at ``temperature``.
    ``greedy``/``top_k`` must be trace-time constants."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k:
        # Keep the k highest logits, mask the rest to -inf: the
        # standard tail-truncation that stops temperature sampling
        # from wandering off the model's manifold. lax.top_k is
        # O(V) per step vs a full sort's O(V log V).
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(
            logits >= kth, logits, jnp.finfo(logits.dtype).min
        )
    return jax.random.categorical(key, logits / temperature).astype(
        jnp.int32
    )


def sample_tokens_at(logits, base_key, positions, greedy, top_k,
                     temperature):
    """Position-deterministic sampling: like ``sample_tokens`` but the
    PRNG key for each row is ``fold_in(base_key, positions[row])`` —
    the pad-free sequence position of the token being sampled. Any two
    programs that sample the same position of the same stream (plain
    decode, chunked prefill, speculative draft/verify) therefore draw
    the SAME random number, which is what makes speculative decode
    byte-identical to plain decode at temperature > 0, not just greedy.

    ``logits``: (N, vocab); ``positions``: (N,) int32. Greedy ignores
    the key entirely (argmax)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(
            logits >= kth, logits, jnp.finfo(logits.dtype).min
        )
    sample_row = jax.vmap(
        lambda row, p: jax.random.categorical(
            jax.random.fold_in(base_key, p), row / temperature
        )
    )
    return sample_row(logits, positions).astype(jnp.int32)


# Trace-time counter: the traced body runs ONCE per compilation, so this
# counts compiles — tests assert ragged batches of varying lengths reuse
# one program (recompiles only on genuine shape/static changes).
_GENERATE_TRACES = 0


def generate_trace_count() -> int:
    """How many times the generate program has been (re)compiled."""
    return _GENERATE_TRACES


@functools.partial(
    jax.jit,
    static_argnames=("module", "max_new", "greedy", "top_k", "use_stop"),
)
def _generate_scan(module, params, prompt, cache, rng, max_new, greedy,
                   top_k, temperature, pad_offset, stop_token, use_stop):
    global _GENERATE_TRACES
    _GENERATE_TRACES += 1

    def sample(logits, key):
        return sample_tokens(logits, key, greedy, top_k, temperature)

    # PREFILL: one batched forward over the whole prompt fills every
    # layer's cache in parallel — O(plen) sequential single-token steps
    # would dominate long-context generation.
    logits, mutated = module.apply(
        {"params": params, "cache": cache}, prompt, mutable=["cache"],
        pad_offset=pad_offset,
    )
    rng, key = jax.random.split(rng)
    first = sample(logits[:, -1, :], key)
    done = (first == stop_token) if use_stop else jnp.zeros(
        first.shape, bool
    )

    def step(carry, _):
        tok, cache, rng, done = carry
        logits, mutated = module.apply(
            {"params": params, "cache": cache},
            tok[:, None],
            mutable=["cache"],
            pad_offset=pad_offset,
        )
        rng, key = jax.random.split(rng)
        nxt = sample(logits[:, 0, :], key)
        if use_stop:
            # A finished row keeps emitting stop_token and stops
            # advancing — its output is frozen, per-row early stop
            # under one fixed-trip-count compiled program.
            nxt = jnp.where(done, stop_token, nxt)
            done = done | (nxt == stop_token)
        return (nxt, mutated["cache"], rng, done), nxt

    (_, _, _, _), rest = jax.lax.scan(
        step, (first, mutated["cache"], rng, done), None, length=max_new - 1
    )
    return jnp.concatenate([prompt, first[:, None], rest.T], axis=1)


def left_pad_prompts(prompts, pad_token: int = 0):
    """Left-pad a ragged batch of prompts to a (batch, max_len) array.

    ``prompts``: sequence of 1-D int token sequences (possibly of
    different lengths). Returns ``(padded, lengths)`` — real tokens of
    row ``i`` occupy the LAST ``lengths[i]`` columns, so every row's
    final prompt token lands in the same column and the whole batch
    decodes under one compiled program.
    """
    rows = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    if any(len(r) < 1 for r in rows):
        raise ValueError("every prompt must have at least 1 token")
    lengths = np.array([len(r) for r in rows], np.int32)
    plen = int(lengths.max())
    padded = np.full((len(rows), plen), int(pad_token), np.int32)
    for i, r in enumerate(rows):
        padded[i, plen - len(r):] = r
    return padded, lengths


def make_decode_cache(decode_module, batch: int, total_len: int):
    """Zeroed KV caches for ``total_len`` columns straight from shapes
    (eval_shape: no param materialization, no full-length attention
    forward on dummies)."""
    cache_shapes = jax.eval_shape(
        lambda: decode_module.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, total_len), jnp.int32)
        )
    )["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
    )


def make_paged_decode_cache(decode_module, max_slots: int, num_blocks: int,
                            block_size: int, prefill_chunk: int = 1):
    """Zeroed PAGED decode cache: the same pytree structure as
    ``make_decode_cache`` but with every K/V leaf laid out as physical
    blocks of ``block_size`` columns (``ops.attention.pool_leaf_shape``:
    ``(num_blocks, heads, rows, lanes)``, a few columns packed to a
    row) instead of one contiguous ``(max_slots, heads, max_len,
    head_dim)`` row per slot. A host-side block table maps ``slot ->
    block ids``; slots share blocks by holding the same id
    (reference-counted by the pool).

    Index leaves (``cache_index``/``pos_index``) stay per-SLOT
    ``(max_slots,)`` vectors — positions are a property of the logical
    sequence, not of physical block placement — so the same flax apply
    drives both layouts: over gathered rows (prefill chunks,
    speculative windows) or over the blocks themselves (``paged=``).
    A latent leaf (``cached_latent``: one head, key and value in one) is
    laid out ``ops.attention.latent_leaf_shape``, a block's columns minor.
    An indexer's key (``cached_index_key``) is laid out as the latent is.
    A block-sparse attention's compressed keys (``cached_compressed_key``)
    are ``block_size / stride`` keys a block and head
    (``ops.sparse_index.compressed_leaf_shape``).
    A state leaf (``models.decode_cache``: a recurrence's or a
    convolution's) is one row a slot, ``(max_slots, ...)``, never paged. A
    window layer's latent (``cached_window_latent``) is a ring of blocks a
    slot, ``(max_slots, ring_blocks, 1, width, block_size)``: its length in
    the module's own cache is the window, and ``prefill_chunk`` the widest
    chunk that will be written into it at once."""
    from elephas_tpu.models.decode_cache import (
        COLUMN_MINOR,
        INDEX,
        KV,
        WINDOW,
        leaf_kind,
        leaf_name,
        ring_blocks,
    )
    from elephas_tpu.ops.attention import latent_leaf_shape, pool_leaf_shape
    from elephas_tpu.ops.sparse_index import compressed_leaf_shape

    cache_shapes = jax.eval_shape(
        lambda: decode_module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)
        )
    )["cache"]

    def build(path, s):
        kind = leaf_kind(path)
        if kind == KV:
            _, heads, _, head_dim = s.shape
            if leaf_name(path) == "cached_compressed_key":
                return jnp.zeros(compressed_leaf_shape(
                    num_blocks, heads, block_size, s.shape[2], head_dim), s.dtype)
            if leaf_name(path) in COLUMN_MINOR:
                return jnp.zeros(
                    latent_leaf_shape(num_blocks, block_size, head_dim),
                    s.dtype)
            return jnp.zeros(
                pool_leaf_shape(num_blocks, heads, block_size, head_dim),
                s.dtype,
            )
        if kind == INDEX:
            return jnp.zeros((max_slots,), jnp.int32)
        if kind == WINDOW:
            _, _, window, width = s.shape
            blocks = ring_blocks(window, prefill_chunk, block_size)
            return jnp.zeros(
                (max_slots,) + latent_leaf_shape(blocks, block_size, width), s.dtype)
        return jnp.zeros((max_slots,) + s.shape[1:], s.dtype)

    return jax.tree_util.tree_map_with_path(build, cache_shapes)


def generate(
    compiled,
    prompt,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
    params=None,
    prompt_lengths=None,
    stop_token: Optional[int] = None,
    pad_token: int = 0,
):
    """Autoregressive sampling from a ``TransformerLM`` — the inference
    half of the long-context story (absent in the reference, which has
    no generative models at all; SURVEY.md §5.7).

    ``prompt``: (batch, prompt_len) int tokens, or a RAGGED batch — a
    list/tuple of 1-D token sequences of different lengths, left-padded
    here with ``pad_token`` (equivalently, pass a pre-padded 2-D array
    plus ``prompt_lengths``). Ragged rows are masked through prefill
    and cache (padding never attended, positions counted from each
    row's first real token), so the output is token-identical to
    decoding each row alone — under ONE compiled program for the padded
    shape, no per-length recompiles (``generate_trace_count``).

    ``stop_token``: per-row early stop — a row that emits it freezes
    (keeps emitting ``stop_token``) while the rest of the batch decodes
    on. Returns (batch, prompt_len + max_new_tokens) tokens including
    the (padded) prompt. Greedy at ``temperature=0`` (default),
    categorical otherwise (temperature is a traced operand — sweeping
    it never recompiles); ``top_k > 0`` truncates sampling to the k
    most likely tokens.

    KV-cache incremental decoding: one batched PREFILL forward fills
    every layer's cache over the prompt, then one O(L·d) forward per
    sampled token, the whole loop one compiled program. Trained
    parameters drop in unchanged — the decode path shapes an identical
    parameter tree; models trained with ring/ulysses/flash attention
    sample through the cache path (same math, single device).
    """
    module = compiled.module
    if not isinstance(module, TransformerLM):
        raise TypeError(
            f"generate() samples TransformerLM models, got {type(module).__name__}"
        )
    params = params if params is not None else compiled.params
    if isinstance(prompt, (list, tuple)):
        if prompt_lengths is not None:
            raise ValueError(
                "pass prompt_lengths only with a pre-padded 2-D prompt array"
            )
        prompt, prompt_lengths = left_pad_prompts(prompt, pad_token)
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(
            f"prompt must be (batch, prompt_len>=1), got {prompt.shape}"
        )
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if not 0 <= top_k <= module.vocab_size:
        raise ValueError(
            f"top_k must be in [0, vocab_size={module.vocab_size}], got {top_k}"
        )
    b, plen = prompt.shape
    pad_offset = None
    if prompt_lengths is not None:
        lengths = np.asarray(prompt_lengths, np.int32).reshape(-1)
        if lengths.shape != (b,):
            raise ValueError(
                f"prompt_lengths must have shape ({b},), got {lengths.shape}"
            )
        if (lengths < 1).any() or (lengths > plen).any():
            raise ValueError(
                f"prompt_lengths must be in [1, {plen}], got {lengths}"
            )
        # All-full-length batches keep the (faster) unmasked program.
        if (lengths < plen).any():
            pad_offset = jnp.asarray(plen - lengths)
    if stop_token is not None and not 0 <= stop_token < module.vocab_size:
        raise ValueError(
            f"stop_token must be in [0, vocab_size={module.vocab_size}), "
            f"got {stop_token}"
        )
    total = plen + max_new_tokens
    if total > module.max_seq_len:
        raise ValueError(
            f"prompt_len {plen} + max_new_tokens {max_new_tokens} exceeds "
            f"max_seq_len {module.max_seq_len}"
        )
    # decode=True with attention='dense': the cache path replaces the
    # attention impl; sequence-parallel training configs sample fine.
    decode_module = dataclasses.replace(module, decode=True, attention="dense")
    cache = make_decode_cache(decode_module, b, total)
    out = _generate_scan(
        decode_module, params, prompt, cache,
        jax.random.PRNGKey(seed), max_new_tokens,
        float(temperature) <= 0.0, int(top_k), jnp.float32(temperature),
        pad_offset,
        jnp.int32(0 if stop_token is None else stop_token),
        stop_token is not None,
    )
    return np.asarray(out)


@register_model("transformer_lm")
def build_transformer_lm(
    vocab_size=32000,
    d_model=256,
    num_heads=8,
    num_layers=4,
    max_seq_len=2048,
    dtype="float32",
    attention="dense",
):
    if attention not in ("dense", "flash", "ring", "ulysses", "auto"):
        raise ValueError(
            f"unknown attention={attention!r}; expected one of "
            "'dense', 'flash', 'ring', 'ulysses', 'auto'"
        )
    return TransformerLM(
        vocab_size=vocab_size,
        d_model=d_model,
        num_heads=num_heads,
        num_layers=num_layers,
        max_seq_len=max_seq_len,
        dtype=jnp.dtype(dtype),
        attention=attention,
    )
