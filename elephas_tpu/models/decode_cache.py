"""What kind of thing a leaf of a decode ``cache`` collection is.

A decode module's ``cache`` tree is the whole of what the serving pool
holds for it, and every program that touches the pool has to treat three
kinds of leaf differently. This is the one place that tells them apart,
by the leaf's name:

- ``KV``: ``cached_key`` / ``cached_value``, attention's columns. In the
  paged pool they are physical blocks shared through the block table
  (``ops.attention.pool_leaf_shape``), in the contiguous pool and in a
  gathered row ``(batch, heads, len, head_dim)``.
- ``INDEX``: ``cache_index`` / ``pos_index``, the column a row writes
  next: a scalar in a fresh module cache, a ``(slots,)`` vector in a pool.
- ``STATE``: anything else: a recurrence's or a convolution's state.
  Its leading dimension is the batch, so a pool holds one row a slot,
  ``(slots, ...)``; it is never paged, never shared between slots, and a
  block of K/V says nothing about it.
"""

from __future__ import annotations

import jax

KV, INDEX, STATE = "kv", "index", "state"

_KINDS = {"cached_key": KV, "cached_value": KV,
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


def state_bytes(cache) -> int:
    """Bytes of every state leaf of ``cache``."""
    return sum(leaf.size * leaf.dtype.itemsize
               for _, leaf in leaves_of_kind(cache, STATE))


def first_index(cache):
    """The write column of every row: layers advance in lockstep, so the
    first ``cache_index`` leaf speaks for all."""
    return next(leaf for path, leaf in leaves_of_kind(cache, INDEX)
                if leaf_name(path) == "cache_index")
