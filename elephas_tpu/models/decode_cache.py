"""What kind of thing a leaf of a decode ``cache`` collection is.

A decode module's ``cache`` tree is the whole of what the serving pool
holds for it, and every program that touches the pool has to treat three
kinds of leaf differently. This is the one place that tells them apart,
by the leaf's name:

- ``KV``: ``cached_key`` / ``cached_value``, attention's columns, or
  ``cached_latent``, a latent attention's: one head whose columns are key
  and, in their first part, value at once, with no value leaf beside it.
  In the paged pool they are physical blocks shared through the block table
  (``ops.attention.pool_leaf_shape``), in a gathered row ``(batch, heads,
  len, head_dim)``.
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

KV, INDEX, STATE = "kv", "index", "state"

_KINDS = {"cached_key": KV, "cached_value": KV, "cached_latent": KV,
          "cache_index": INDEX, "pos_index": INDEX}


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


def attend_paged(q, k, v, cached_key, cached_value, cache_index, active,
                 paged: PagedDecode, scale=None, kv_b=None):
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
    ** -0.5``."""
    from elephas_tpu.ops.attention import (
        paged_chunk_attention,
        paged_decode_attention,
    )

    rows, _, T, _ = q.shape
    idx = cache_index.value
    latent = cached_value is None
    pools = (cached_key.value, None if latent else cached_value.value)
    if active is not None:
        if T != 1:
            raise ValueError("a paged decode step attends one token per lane; "
                             "speculative windows gather their rows")
        out, *pools = paged_decode_attention(
            q[:, :, 0], k[:, :, 0], None if latent else v[:, :, 0], *pools,
            paged.table, idx, active, paged.body, scale=scale, kv_b=kv_b)
        cache_index.value = jnp.where(active, idx + 1, idx)
        out = out[:, :, None, :]
    else:
        if rows != 1:
            raise ValueError("a prefill chunk over the paged pool is one "
                             f"slot's; got {rows} rows")
        out, *pools = paged_chunk_attention(
            q[0], k[0], None if latent else v[0], *pools, paged.table[0],
            idx[0], paged.body, paged.aligned, scale=scale, kv_b=kv_b,
            valid=paged.valid)
        cache_index.value = idx + T
        out = out[None]
    cached_key.value = pools[0]
    if not latent:
        cached_value.value = pools[1]
    return out
