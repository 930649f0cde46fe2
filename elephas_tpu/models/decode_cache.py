"""What kind of thing a leaf of a decode ``cache`` collection is.

A decode module's ``cache`` tree is the whole of what the serving pool
holds for it, and every program that touches the pool has to treat four
kinds of leaf differently. This is the one place that tells them apart,
by the leaf's name:

- ``KV``: ``cached_key`` / ``cached_value``, attention's columns, or
  ``cached_latent``, a latent attention's: one head whose columns are key
  and, in their first part, value at once, with no value leaf beside it.
  In the paged pool they are physical blocks shared through the block table
  (``ops.attention.pool_leaf_shape``), in a gathered row ``(batch, heads,
  len, head_dim)``. ``cached_index_key`` is a K/V leaf too: the one key a
  token that a learned sparse attention's indexer scores (``ops.
  sparse_index``), a second paged leaf beside the latent of the same layer,
  written through the same table at the same column and laid out as the
  latent is (``COLUMN_MINOR``). ``cached_compressed_key`` is a K/V leaf too:
  a block-sparse attention's compressed keys (``ops.sparse_index``:
  selection by blocks), ``block_size / stride`` of them a block and K/V
  head, ``(num_blocks, heads, block_size // stride, width)``, written
  through the same table as the columns that complete them are. In the
  module's own cache it is ``(batch, heads, stride, width)``: the third
  dimension says how many columns a key stands for, not a length.
- ``WINDOW``: ``cached_window_latent``, the latent of a layer whose queries
  see the last ``window`` columns only. What a slot keeps of it is bounded
  by the window, not by the sequence: a RING of blocks a slot,
  ``(slots, ring_blocks, 1, width, block_size)``, column ``c`` in block
  ``c // block_size % ring_blocks``, with room for the window's columns
  behind a prefill chunk's first and the chunk itself (``ring_blocks``). It
  is a slot's own as a state row is: never shared through the block table,
  cleared at release, and a resident prefix says nothing about it.
- ``INDEX``: ``cache_index`` / ``pos_index``, the column a row writes
  next: a scalar in a fresh module cache, a ``(slots,)`` vector in a pool.
- ``STATE``: anything else: a recurrence's or a convolution's state.
  Its leading dimension is the batch, so a pool holds one row a slot,
  ``(slots, ...)``; it is never paged, never shared between slots, and a
  block of K/V says nothing about it.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

KV, INDEX, STATE, WINDOW = "kv", "index", "state", "window"

_KINDS = {"cached_key": KV, "cached_value": KV, "cached_latent": KV,
          "cached_index_key": KV, "cached_compressed_key": KV,
          "cached_window_latent": WINDOW,
          "cache_index": INDEX, "pos_index": INDEX}

# leaves of one head whose blocks keep their columns minor
# (``ops.attention.latent_leaf_shape``)
COLUMN_MINOR = ("cached_latent", "cached_index_key", "cached_window_latent")


def leaf_name(path) -> str:
    return path[-1].key if hasattr(path[-1], "key") else str(path[-1])


def leaf_kind(path) -> str:
    return _KINDS.get(leaf_name(path), STATE)


def leaves_of_kind(cache, kind: str):
    """``(path, leaf)`` of every leaf of ``kind``, in tree order."""
    return [(path, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if leaf_kind(path) == kind]


def has_state(cache) -> bool:
    return bool(leaves_of_kind(cache, STATE))


def ring_blocks(window: int, chunk: int, block_size: int) -> int:
    """Blocks a slot's ring of a window layer holds: the ``window - 1``
    columns behind a prefill chunk's first and the chunk's own, rounded up
    to blocks, and one more where a chunk need not start on a block."""
    return -(-(window - 1 + chunk) // block_size) + (chunk % block_size != 0)


def has_latent(cache) -> bool:
    """Whether a K/V leaf of ``cache`` is a latent: key and value in one."""
    return any(leaf_name(path) == "cached_latent"
               for path, _ in leaves_of_kind(cache, KV))


def state_bytes(cache) -> int:
    """Bytes of every state leaf of ``cache``."""
    return sum(leaf.size * leaf.dtype.itemsize
               for _, leaf in leaves_of_kind(cache, STATE))


def first_index(cache):
    """The write column of every row: layers advance in lockstep, so the
    first ``cache_index`` leaf speaks for all."""
    return next(leaf for path, leaf in leaves_of_kind(cache, INDEX)
                if leaf_name(path) == "cache_index")


class PagedDecode(NamedTuple):
    """What an apply over the serving pool's physical blocks needs beside
    the cache: the (rows, blocks_per_slot) block ``table`` of the rows it
    is given (every slot for a decode step, the one slot of a prefill
    chunk), the name of the attention ``body``
    (``ops.attention.PAGED_BODIES``) and, for a chunk, whether it is
    ``aligned``: it starts at a block's first column and is whole blocks
    wide, so its columns are written as blocks; and how many of its tokens
    are ``valid``, the others right-padding whose queries nobody reads (a
    traced scalar; None where every token counts)."""

    table: Any
    body: str
    aligned: bool = False
    valid: Any = None


class Indexer(NamedTuple):
    """What a learned sparse attention hands ``attend_paged`` beside its
    queries: the index queries ``q`` (rows, index heads, T, width) and their
    weights ``w`` (rows, T, index heads), the new tokens' index keys ``k``
    (rows, 1, T, width), the ``cached`` variable that holds the pool's index
    keys, and ``top_k``, how many columns a query attends."""

    q: Any
    w: Any
    k: Any
    cached: Any
    top_k: int


def _ring_rows(ring_blocks: int, block_size: int, window: int, first, count: int):
    """Of a ring, the ``count`` blocks from the one that holds column
    ``first - (window - 1)`` on, in column order, and that block's first
    column: the ring read as the short sequence a window layer attends."""
    j0 = jnp.maximum(first - (window - 1), 0) // block_size
    return (j0[..., None] + jnp.arange(count)) % ring_blocks, j0 * block_size


def attend_paged(q, k, v, cached_key, cached_value, cache_index, active,
                 paged: PagedDecode, scale=None, kv_b=None, window=None,
                 indexer: Indexer = None):
    """A decode module's attention where its K/V variables ARE the pool's
    physical blocks: the new columns go into their blocks and the queries
    attend through the block table, no contiguous row is ever built.
    ``q``: (rows, q_heads, T, head_dim), ``k``/``v``: (rows, heads, T,
    head_dim). Under an ``active`` mask it is a decode step, one token a
    lane (``ops.attention.paged_decode_attention``); without one, a
    prefill chunk of one slot, ``T`` tokens from column ``cache_index``
    on (``paged_chunk_attention``). Returns (rows, q_heads, T, head_dim)
    and leaves the three variables as the step left them.

    A latent attention passes ``v`` and ``cached_value`` as None and
    ``kv_b``, (rank, q_heads, nope + v_head), the matrix that expands a
    latent into a head's key and value: ``k`` is the new latent columns
    (``rank`` values, then the shared rotary key), ``q`` a head's own query
    (``nope`` values, then its rotary part), and the result (rows, q_heads,
    T, v_head). ``scale`` is the softmax scale where it is not ``head_dim
    ** -0.5``.

    With a ``window`` (a latent attention's) ``cached_key`` is the layer's
    ring (``WINDOW`` above), every lane's in a decode step and the slot's own
    in a chunk: the block table is not read; the ring's blocks from the one
    that holds the oldest column a query still sees are attended in column
    order, under the window's bound.

    With an ``indexer`` (a latent attention's) the new index keys go into
    their leaf through the table, the queries' index scores over the slot's
    live index keys choose ``top_k`` columns a query (``ops.sparse_index``),
    and attention runs over those alone. Returns ``(out, (live,
    selected))`` then: the live columns of the apply's real queries, summed,
    and how many of them were attended."""
    from elephas_tpu.ops.attention import (
        paged_chunk_attention,
        paged_decode_attention,
    )

    rows, _, T, _ = q.shape
    idx = cache_index.value
    latent = cached_value is None
    pools = (cached_key.value, None if latent else cached_value.value)
    table, at, told, counts = paged.table, idx, {}, None
    if window is not None:
        ring = pools[0]  # (rows, ring blocks, 1, width, block_size)
        blocks, block_size = ring.shape[1], ring.shape[-1]
        pools = (ring.reshape((-1,) + ring.shape[2:]), None)
        # a lane's window touches a few blocks, a chunk's every one
        count = min(blocks, (window - 2) // block_size + 2) if active is not None \
            else blocks
        table, first = _ring_rows(blocks, block_size, window, idx, count)
        table = table + jnp.arange(rows)[:, None] * blocks
        at, told = idx - first, {"window": window}
    if active is not None:
        if T != 1:
            raise ValueError("a paged decode step attends one token per lane; "
                             "speculative windows gather their rows")
        if indexer is not None:
            told["selected"], counts = _select(
                indexer, table, idx, paged.body, active=active)
        out, *pools = paged_decode_attention(
            q[:, :, 0], k[:, :, 0], None if latent else v[:, :, 0], *pools,
            table, at, active, paged.body, scale=scale, kv_b=kv_b, **told)
        cache_index.value = jnp.where(active, idx + 1, idx)
        out = out[:, :, None, :]
    else:
        if rows != 1:
            raise ValueError("a prefill chunk over the paged pool is one "
                             f"slot's; got {rows} rows")
        if indexer is not None:
            told["selected"], counts = _select(
                indexer, table[0], idx[0], paged.body, valid=paged.valid,
                aligned=paged.aligned)
        out, *pools = paged_chunk_attention(
            q[0], k[0], None if latent else v[0], *pools, table[0],
            at[0], paged.body, paged.aligned, scale=scale, kv_b=kv_b,
            valid=paged.valid, **told)
        cache_index.value = idx + T
        out = out[None]
    cached_key.value = pools[0] if window is None else pools[0].reshape(ring.shape)
    if not latent:
        cached_value.value = pools[1]
    return out if indexer is None else (out, counts)


def _select(indexer: Indexer, table, idx, body: str, active=None, valid=None,
            aligned: bool = False):
    """Write the new index keys, score the live ones and select: the mask
    (queries, S) of what each query attends, and ``(live, selected)``. Under
    ``active`` a decode step (``table`` every lane's row, ``idx`` the column
    each lane writes), else one slot's chunk from column ``idx`` on."""
    from elephas_tpu.ops.attention import (
        _write_windows,
        scatter_prefill_blocks,
        scatter_prefill_columns,
    )
    from elephas_tpu.ops.sparse_index import index_scores, select_columns

    pool = indexer.cached.value
    if active is not None:
        with jax.named_scope("kv_write"):
            pool = _write_windows(pool, table, idx, indexer.k[:, :, :1], active,
                                  True)
        scores = index_scores(indexer.q[:, :, 0], indexer.w[:, 0], pool, table,
                              idx, body, active=active)
        last, real = idx, active
    else:
        write = scatter_prefill_blocks if aligned else scatter_prefill_columns
        with jax.named_scope("kv_write"):
            pool = write(pool, table, idx, indexer.k[0], latent=True)
        scores = index_scores(indexer.q[0], indexer.w[0], pool, table, idx, body,
                              valid=valid)
        steps = jnp.arange(scores.shape[0])
        last = idx + steps
        real = steps < (scores.shape[0] if valid is None else valid)
    indexer.cached.value = pool
    mask = select_columns(scores, last, indexer.top_k, body)
    live = jnp.where(real, last + 1, 0).sum()
    selected = jnp.where(real[:, None], mask, 0).astype(jnp.int32).sum()
    return mask, (live.astype(jnp.float32), selected.astype(jnp.float32))


def attend_block_sparse(q, k, v, cached_key, cached_value, cached_compressed,
                        cache_index, active, paged: PagedDecode, sel, scale: float):
    """A block-sparse attention's layer over the paged pool
    (``ops.sparse_index``: selection by blocks, ``sel`` its sizes): the new
    columns go into their blocks and the compressed keys they complete into
    theirs, each query scores its K/V head's compressed keys, selects whole
    blocks, and attends those pages alone. ``q``: (rows, q_heads, T,
    head_dim), ``k``/``v``: (rows, heads, T, head_dim); a decode step under
    ``active``, one slot's chunk without it, as ``attend_paged`` has them.

    Returns ``(out, (live, selected, scored, gathered))``: (rows, q_heads,
    T, head_dim), and over the apply's real queries and K/V heads the
    columns each could see, the columns it attended, the blocks it scored
    (none for a query that attends every column) and the chosen pages the
    chunk kernel fetched for it one by one (none on a decode step, or where
    the chunk walks)."""
    from elephas_tpu.ops.attention import (
        _write_windows,
        block_sparse_chunk_attention,
        block_sparse_decode_attention,
        scatter_prefill_blocks,
        scatter_prefill_columns,
    )
    from elephas_tpu.ops.sparse_index import (
        block_scores,
        chosen_blocks,
        pallas_block_scores,
        row_keys,
        select_blocks,
        write_chunk_keys,
        write_step_keys,
    )

    rows, q_heads, T, D = q.shape
    heads = k.shape[1]
    group = q_heads // heads
    idx, table = cache_index.value, paged.table
    nb = table.shape[1]
    if active is not None:
        if T != 1:
            raise ValueError("a paged decode step attends one token per lane")
        with jax.named_scope("kv_write"):
            k_pool = _write_windows(cached_key.value, table, idx, k[:, :, :1], active)
            v_pool = _write_windows(cached_value.value, table, idx, v[:, :, :1], active)
            comp = write_step_keys(cached_compressed.value, k_pool, table, idx, active,
                                   sel, D)
        qg = q[:, :, 0].reshape(rows, heads, group, D)
        scores = block_scores(qg, row_keys(comp, table), idx, sel, scale)
        last = jnp.repeat(idx, heads)
        mask = select_blocks(scores.reshape(rows * heads, nb), last, sel, paged.body)
        chosen = chosen_blocks(mask, sel.table_width).reshape(rows, heads, -1)
        out = block_sparse_decode_attention(qg, k_pool, v_pool, table, chosen, idx, D,
                                            scale).reshape(rows, q_heads, 1, D)
        cache_index.value = jnp.where(active, idx + 1, idx)
        real = jnp.repeat(active, heads)
        gathered = jnp.zeros((), jnp.int32)
    else:
        if rows != 1:
            raise ValueError(f"a prefill chunk over the paged pool is one slot's; got {rows} rows")
        row, start = table[0], idx[0]
        valid = T if paged.valid is None else paged.valid
        write = scatter_prefill_blocks if paged.aligned else scatter_prefill_columns
        with jax.named_scope("kv_write"):
            k_pool = write(cached_key.value, row, start, k[0])
            v_pool = write(cached_value.value, row, start, v[0])
            comp = write_chunk_keys(cached_compressed.value, k_pool, row, start, valid,
                                    k[0], sel, D)
        keys = row_keys(comp, row[None])[0]
        qt = jnp.moveaxis(q[0], 0, 1).reshape(T, heads, group, D)
        at = start + jnp.arange(T)
        if paged.body == "paged_pallas":
            scores = jnp.moveaxis(pallas_block_scores(
                jnp.moveaxis(qt, 0, 2), keys, start, sel, scale), 0, 1)
        else:
            tile = max(d for d in range(1, min(T, 256) + 1) if T % d == 0)
            scores = jax.lax.map(
                lambda i: block_scores(jax.lax.dynamic_slice_in_dim(qt, i * tile, tile, 0),
                                       keys, start + i * tile + jnp.arange(tile), sel,
                                       scale), jnp.arange(T // tile)).reshape(T, heads, -1)
        last = jnp.repeat(at, heads)
        mask = select_blocks(scores[..., :nb].reshape(T * heads, nb), last, sel, paged.body)
        picked = jnp.moveaxis(mask.reshape(T, heads, nb), 0, 2)  # (heads, nb, T)
        out, gathered = block_sparse_chunk_attention(qt, k_pool, v_pool, row, start, picked,
                                                     D, scale, paged.body, sel, valid)
        out = jnp.moveaxis(out.reshape(T, q_heads, D), 0, 1)[None]
        cache_index.value = idx + T
        real = jnp.repeat(jnp.arange(T) < valid, heads)
    cached_key.value, cached_value.value, cached_compressed.value = k_pool, v_pool, comp
    return out, _block_counts(mask, last, real, sel) + (gathered.astype(jnp.float32),)


def _block_counts(mask, last, real, sel):
    """Over the (query, K/V head) rows that are ``real``: the columns each
    could see, those its blocks hold up to its own, and the blocks it
    scored (every live one, for a query that selects)."""
    first = jnp.arange(mask.shape[-1]) * sel.block
    held = jnp.clip(last[:, None] - first[None] + 1, 0, sel.block)
    selected = jnp.where(mask > 0, held, 0).sum(-1)
    scored = jnp.where(last >= sel.dense_len, last // sel.block + 1, 0)
    return tuple(jnp.where(real, x, 0).sum().astype(jnp.float32)
                 for x in (last + 1, selected, scored))
