"""The indexer of a learned sparse attention, over the paged pool.

A full layer of such a model scores every live column ``s <= t`` of a query
``t`` with a small side network, the indexer, and attends the ``k`` columns
that score best and no other::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        j over index heads
    S_t     = the k columns s <= t with the largest I[t, s]; every s <= t
              while t < k; the lower column wins a tie

``kI`` is ONE key a token, ``index_head_dim`` wide, and is cached: a second
paged leaf beside the layer's latent, ``(num_blocks, 1, width, block_size)``
with a block's columns minor (``ops.attention.latent_leaf_shape``), written
through the same block table at the same column. Two steps, each with an
XLA body (the CPU's, and the kernels' reference) and a kernel for one TPU:

- ``index_scores``: ``I`` of a chunk's queries (or of a decode step's one
  query a lane) against the slot's index keys through its table row, float32
  accumulation, ``(queries, columns)``. The chunk kernel walks (query tile,
  column step) pairs and skips those wholly above the diagonal; what it
  leaves there, and past the last live step, is never read as a score: the
  selection masks by ``s <= t`` first.
- ``select_columns``: the set ``S_t`` as a mask, EXACT, ties included. The
  ``k``-th largest score of a row is found by building its order-preserving
  32-bit key bit by bit (32 counting passes over the row), and of the
  columns that tie with it the lowest are taken by 16 more passes over
  their column numbers: nothing is sorted and nothing approximated.

Below the indexer, a second rule of the same kind: selection by BLOCKS of
the pool (InfLLM-v2's), by compressed keys a paged leaf of their own holds,
with the same exact selection over block scores (section "selection by
blocks").
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INT_MIN = -(1 << 31)

# Rows a grid step of the selection kernel holds: whole int8 tiles.
_SELECT_ROWS = 32
# Queries a tile of the index-scores kernel.
_INDEX_QUERY_TILE = 256
# Blocks a grid step of the decode step's index kernel covers, where a row
# has as many: a step costs about 0.4 us whatever it scores, and a lane's
# one query against 512 columns is less than that (on the chip, 16 lanes of
# 9k-35k columns: 0.39 ms a call at 4 blocks a step, where the XLA body
# that gathers every block takes 0.15).
_INDEX_DECODE_BLOCKS = 16
_SPARSE_VMEM = 64 << 20


def index_step_columns(block_size: int) -> int:
    """Columns a grid step of the chunk kernels covers (512, or a block):
    the index scores, the selection's mask and the attention over it are
    padded to whole steps of this width."""
    return max(1, 512 // block_size) * block_size


def padded_columns(blocks_per_slot: int, block_size: int) -> int:
    columns = index_step_columns(block_size)
    return -(-blocks_per_slot * block_size // columns) * columns


# -- index scores -------------------------------------------------------------


def _index_scores_xla(q, w, pool, table):
    """``q``: (rows, Q, heads, width); ``w``: (rows, Q, heads) float32;
    ``pool``: the index-key leaf; ``table``: (rows, blocks_per_slot).
    Returns (rows, Q, blocks_per_slot * block_size) float32."""
    kb = pool[table][:, :, 0]  # (rows, bps, width, bs); OOB ids clamp
    s = jnp.einsum("rqhd,rjdb->rqhjb", q, kb, preferred_element_type=jnp.float32)
    scores = (jax.nn.relu(s) * w[..., None, None].astype(jnp.float32)).sum(2)
    return scores.reshape(*scores.shape[:2], -1)


def _index_scores_kernel(phys_ref, steps_ref, start_ref, q_ref, w_ref, *refs,
                         blocks: int):
    """Grid step (query tile ``i``, column step ``j``): the index scores of
    ``tq`` queries against ``blocks`` blocks of index keys, one head at a
    time, accumulated in float32 under each head's weight. A step wholly
    above the tile's diagonal is not computed."""
    del phys_ref, steps_ref
    k_refs, o_ref = refs[:blocks], refs[blocks]
    i, j = pl.program_id(0), pl.program_id(1)
    heads, tq, _ = q_ref.shape
    columns = blocks * k_refs[0].shape[1]

    @pl.when(j * columns <= start_ref[0] + (i + 1) * tq - 1)
    def _score():
        keys = jnp.concatenate([ref[...] for ref in k_refs], axis=1)
        acc = jnp.zeros((tq, columns), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[h], keys, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def pallas_index_scores(q, w, pool, row, start, valid=None, tq: int | None = None,
                        interpret: bool = False):
    """Index scores of one chunk of ONE slot. ``q``: (heads, C, width);
    ``w``: (C, heads) float32; ``pool``: (num_blocks, 1, width, block_size);
    ``row``: (blocks_per_slot,); ``start``, ``valid``: scalars. Returns (C,
    ``padded_columns``) float32; only entries ``[i, s]`` with ``s <= start +
    i`` mean anything."""
    heads, chunk, width = q.shape
    num_blocks, _, _, block_size = pool.shape
    columns = index_step_columns(block_size)
    blocks = columns // block_size
    if tq is None:
        tq = max(d for d in range(1, min(chunk, _INDEX_QUERY_TILE) + 1)
                 if chunk % d == 0)
    most = -(-row.shape[0] // blocks)
    phys = jnp.clip(row.astype(jnp.int32), 0, num_blocks - 1)
    phys = jnp.pad(phys, (0, most * blocks - row.shape[0]), mode="edge")
    start = start.astype(jnp.int32)
    valid = (jnp.int32(chunk) if valid is None
             else jnp.clip(valid.astype(jnp.int32), 1, chunk))
    steps = jnp.clip((start + valid - 1) // columns + 1, 1, most)
    key_blocks = [
        pl.BlockSpec((None, None, width, block_size),
                     lambda i, j, phys, *_, b=b: (phys[j * blocks + b], 0, 0, 0))
        for b in range(blocks)
    ]
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(chunk // tq, steps),
            in_specs=[pl.BlockSpec((heads, tq, width), lambda i, j, *_: (0, i, 0)),
                      pl.BlockSpec((tq, heads), lambda i, j, *_: (i, 0)),
                      *key_blocks],
            out_specs=pl.BlockSpec((tq, columns), lambda i, j, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, most * columns), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SPARSE_VMEM,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="index_scores",
    )(phys, steps.reshape(1), start.reshape(1), q.astype(pool.dtype),
      w.astype(jnp.float32), *([pool] * blocks))


def _index_decode_kernel(lane_ref, first_ref, table_ref, idx_ref,  # prefetch
                         q_ref, w_ref, *refs, blocks: int):
    """Grid step ``g``: one lane's one query, (heads, width), against
    ``blocks`` blocks of its index keys: the heads are the rows of the
    product, weighed and summed to one row of scores."""
    del lane_ref, first_ref, table_ref, idx_ref
    k_refs, o_ref = refs[:blocks], refs[blocks]
    keys = jnp.concatenate([ref[...] for ref in k_refs], axis=1)
    s = jax.lax.dot_general(q_ref[0], keys, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (w_ref[0] * jnp.maximum(s, 0.0)).sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_index_decode_scores(q, w, pool, table, idx, active,
                               interpret: bool = False):
    """Index scores of one decode step: lane ``s``'s one query against its
    index keys up to column ``idx[s]`` (already written). ``q``: (slots,
    heads, width); ``w``: (slots, heads) float32. Returns (slots, S) float32,
    ``S`` the row's columns in whole steps of this kernel; the grid runs over
    the active lanes' live steps, and what the others' places hold is never
    read as a score."""
    slots, heads, width = q.shape
    num_blocks, _, _, block_size = pool.shape
    bps = table.shape[1]
    blocks = min(_INDEX_DECODE_BLOCKS, bps)
    columns = blocks * block_size
    most = -(-bps // blocks)
    idx = idx.astype(jnp.int32)
    flat = jnp.clip(table.astype(jnp.int32), 0, num_blocks - 1)
    flat = jnp.pad(flat, ((0, 0), (0, most * blocks - bps)), mode="edge")
    live = active[:, None] & (jnp.arange(most)[None] * columns <= idx[:, None])
    (work,) = jnp.nonzero(live.reshape(-1), size=live.size, fill_value=0)
    work = work.astype(jnp.int32)
    lane, first = work // most, work % most
    steps = live.sum().astype(jnp.int32)
    key_blocks = [
        pl.BlockSpec((None, None, width, block_size),
                     lambda g, lane, first, table, *_, b=b: (
                         table[lane[g] * (most * blocks) + first[g] * blocks + b],
                         0, 0, 0))
        for b in range(blocks)
    ]
    out = pl.pallas_call(
        functools.partial(_index_decode_kernel, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[pl.BlockSpec((1, heads, width),
                                   lambda g, lane, *_: (lane[g], 0, 0)),
                      pl.BlockSpec((1, heads, 1),
                                   lambda g, lane, *_: (lane[g], 0, 0)),
                      *key_blocks],
            out_specs=pl.BlockSpec(
                (1, 1, columns), lambda g, lane, first, *_: (lane[g], 0, first[g])),
        ),
        out_shape=jax.ShapeDtypeStruct((slots, 1, most * columns), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="index_scores",
    )(lane, first, flat.reshape(-1), idx, q.astype(pool.dtype),
      w.astype(jnp.float32)[:, :, None], *([pool] * blocks))
    return out[:, 0]


def index_scores(q, w, pool, table, start, body: str, valid=None, active=None):
    """``I`` of a chunk (``q``: (heads, C, width), ``w``: (C, heads),
    ``table`` the slot's row, ``start`` its first column) or, under an
    ``active`` mask, of a decode step (``q``: (slots, heads, width), ``w``:
    (slots, heads), ``table`` every lane's row, ``start`` the column each
    lane just wrote). Returns (C or slots, S) float32, ``S`` at least the
    row's columns (``padded_columns``, or the decode kernel's whole steps)."""
    block_size = pool.shape[3]
    with jax.named_scope("index_scores"):
        if body == "paged_pallas" and active is None:
            return pallas_index_scores(q, w, pool, table, start, valid)
        if body == "paged_pallas":
            return pallas_index_decode_scores(q, w, pool, table, start, active)
        if active is None:
            scores = _index_scores_xla(jnp.moveaxis(q, 0, 1)[None], w[None], pool,
                                       table[None])[0]
        else:
            scores = _index_scores_xla(q[:, None], w[:, None], pool, table)[:, 0]
        pad = padded_columns(table.shape[-1], block_size) - scores.shape[-1]
        return jnp.pad(scores, ((0, 0), (0, pad)))


# -- selection ----------------------------------------------------------------


def _select_rows(scores, cols, last, k: int):
    """The mask of ``S_t`` for every row. ``scores``: (rows, S) float32;
    ``cols``: (1 or rows, S) int32 column numbers; ``last``: (rows, 1) int32,
    the last live column of each row. Pure ``jnp``: the kernel's body and
    the XLA body alike."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # a float's bits, as an int32 that orders as the floats do
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    live = cols <= last
    key = jnp.where(live, key, jnp.int32(_INT_MIN))  # below every live key

    def count(pred):
        return pred.astype(jnp.int32).sum(axis=1, keepdims=True)

    # the k-th largest key: the largest tau with k keys at or above it
    tau = jnp.where(count(key >= 0) >= k, jnp.int32(0), jnp.int32(_INT_MIN))

    def bit_of_key(i, tau):
        cand = tau + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= k, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit_of_key, tau)
    above, ties = key > tau, key == tau
    need = k - count(above)  # of the ties, the lowest columns
    col_bits = max(1, (scores.shape[1] - 1).bit_length())

    def bit_of_column(i, c):
        cand = c + jnp.left_shift(jnp.int32(1), col_bits - 1 - i)
        return jnp.where(count(ties & (cols < cand)) < need, cand, c)

    cut = jax.lax.fori_loop(0, col_bits, bit_of_column, jnp.zeros_like(tau))
    return live & (above | (ties & (cols <= cut)))


def _select_kernel(s_ref, last_ref, o_ref, *, k: int):
    cols = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    o_ref[...] = _select_rows(s_ref[...], cols, last_ref[...], k).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def pallas_select_columns(scores, last, k: int, interpret: bool = False):
    """``scores``: (rows, S) float32, ``S`` a multiple of 128; ``last``:
    (rows,) int32. Returns the mask (rows, S) int8, 1 on ``S_t``."""
    rows, S = scores.shape
    padded = -(-rows // _SELECT_ROWS) * _SELECT_ROWS
    scores = jnp.pad(scores, ((0, padded - rows), (0, 0)))
    last = jnp.pad(last.astype(jnp.int32), (0, padded - rows))[:, None]
    mask = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(padded // _SELECT_ROWS,),
        in_specs=[pl.BlockSpec((_SELECT_ROWS, S), lambda i: (i, 0)),
                  pl.BlockSpec((_SELECT_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_SELECT_ROWS, S), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, S), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_SPARSE_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="select_columns",
    )(scores, last)
    return mask[:rows]


def select_columns(scores, last, k: int, body: str):
    """The mask of ``S_t`` a row: (rows, S) int8. ``scores``: (rows, S)
    float32; ``last``: (rows,) the last live column of each row (columns
    past it are never selected, whatever ``scores`` holds there)."""
    with jax.named_scope("select_columns"):
        if body == "paged_pallas":
            return pallas_select_columns(scores, last, k)
        cols = jnp.arange(scores.shape[1], dtype=jnp.int32)[None]
        return _select_rows(scores, cols, last.astype(jnp.int32)[:, None],
                            k).astype(jnp.int8)


# -- selection by blocks ------------------------------------------------------
#
# A block-sparse attention (InfLLM-v2's) selects whole blocks of columns, and
# a block is a page of the pool. It scores them by COMPRESSED keys: of each
# K/V head, key ``i`` is the mean of the keys of columns ``stride * i ..
# stride * i + kernel - 1`` and exists once the last of them is written.
# A query ``t`` of a group ``g`` of query heads::
#
#     p_h[t, i] = softmax over the keys i that exist at t of (q_h,t . c_g,i * scale)
#     B_g[t, b] = max over the keys i that start in block b of sum_{h in g} p_h[t, i]
#     Sel_g(t)  = the first init_blocks blocks, the blocks that hold a column of
#                 t - window + 1 .. t, and the blocks of largest B_g[t, .] among
#                 the rest, until top_k blocks; the lower block wins a tie;
#                 every block up to t's where t < dense_len
#
# The compressed keys are a paged leaf of their own, ``block / stride`` keys a
# block, written through the slot's block table as the columns that complete
# them are: key ``i`` lives in the block it starts in, and the last keys of a
# block are complete only once the next block's first columns are written.


class BlockSelection(NamedTuple):
    """The sizes of a block-sparse attention's selection, in columns where
    not said otherwise."""

    kernel: int       # columns a compressed key is the mean of
    stride: int       # columns between two compressed keys' first
    block: int        # columns a block (a page of the pool)
    top_k: int        # blocks a query attends, the forced ones among them
    init_blocks: int  # leading blocks every query attends
    window: int       # columns behind a query whose blocks it attends
    dense_len: int    # a query before this column attends every column

    @property
    def keys_per_block(self) -> int:
        return self.block // self.stride

    @property
    def table_width(self) -> int:
        """Blocks a query can attend: ``top_k``, or every block up to
        ``dense_len``'s."""
        return max(self.top_k, -(-self.dense_len // self.block))


def compressed_leaf_shape(num_blocks: int, heads: int, block_size: int, stride: int,
                          width: int):
    """The compressed keys' pool leaf: ``block_size / stride`` keys a block
    and K/V head."""
    return (num_blocks, heads, block_size // stride, width)


def _pool_columns(pool, table, first, count: int, head_dim: int):
    """Columns ``first .. first + count - 1`` of each row's K/V head(s):
    ``pool`` a packed K/V leaf (``ops.attention.pool_leaf_shape``), ``table``
    (rows, blocks_per_slot), ``first`` (rows,). Returns (rows, heads, count,
    head_dim); columns before 0 or past the row's blocks hold anything."""
    rows, bps = table.shape
    bs = pool.shape[2] * pool.shape[3] // head_dim
    n = (count - 2) // bs + 2  # blocks a run of `count` columns can touch
    j0 = jnp.floor_divide(first, bs)
    ids = jnp.take_along_axis(table, jnp.clip(j0[:, None] + jnp.arange(n), 0, bps - 1), 1)
    blocks = pool[ids]  # (rows, n, heads, r, lanes); OOB ids clamp
    blocks = blocks.reshape(*blocks.shape[:-2], bs, head_dim)
    cols = jnp.moveaxis(blocks, 1, 2).reshape(rows, blocks.shape[2], n * bs, head_dim)
    return jax.vmap(lambda c, at: jax.lax.dynamic_slice_in_dim(c, at, count, 1))(
        cols, first - j0 * bs)


def _mean_keys(window, starts, kernel: int):
    """``window``: (rows, heads, W, D) consecutive columns; ``starts``: (rows,
    M) each key's first column in it. Returns (rows, M, heads, D), the mean
    of ``kernel`` columns from each start, float32."""
    idx = starts[:, :, None] + jnp.arange(kernel)  # (rows, M, kernel)
    got = jax.vmap(lambda w, i: jnp.take(w, i, axis=1, mode="clip"))(window, idx)
    return jnp.moveaxis(got.astype(jnp.float32).mean(axis=3), 1, 2)


def _put_keys(leaf, table, keys, numbers, ok, sel: BlockSelection):
    """Write ``keys`` (rows, M, heads, D), compressed keys ``numbers`` (rows,
    M) of each row, where ``ok``: into the block each starts in, through the
    row's table; the others drop."""
    num_blocks = leaf.shape[0]
    bps = table.shape[1]
    j = numbers * sel.stride // sel.block
    ok = ok & (numbers >= 0) & (j < bps)
    ids = jnp.take_along_axis(table, jnp.clip(j, 0, bps - 1), 1)
    ids = jnp.where(ok, ids, num_blocks)
    at = numbers % sel.keys_per_block
    return leaf.at[ids.reshape(-1), :, at.reshape(-1)].set(
        keys.reshape(-1, *keys.shape[2:]).astype(leaf.dtype), mode="drop")


def write_chunk_keys(leaf, k_pool, row, start, valid, k_new, sel: BlockSelection,
                     head_dim: int):
    """Write the compressed keys that a prefill chunk's columns ``start ..
    start + valid - 1`` complete. ``k_pool`` already holds the chunk's keys;
    ``k_new``: (heads, T, D), those keys; the ``kernel - 1`` columns before
    ``start`` are read back from the pool."""
    T = k_new.shape[1]
    K, s = sel.kernel, sel.stride
    lo = start - (K - 1)
    before = _pool_columns(k_pool, row[None], jnp.reshape(lo, (1,)), K - 1, head_dim)
    window = jnp.concatenate([before, k_new[None].astype(before.dtype)], axis=2)
    first = jnp.maximum(-(-lo // s), 0)  # the first key whose last column is start's or later
    numbers = first + jnp.arange(T // s + 1)
    keys = _mean_keys(window, (numbers * s - lo)[None], K)
    ok = numbers * s + K - 1 < start + valid
    return _put_keys(leaf, row[None], keys, numbers[None], ok[None], sel)


def write_step_keys(leaf, k_pool, table, idx, active, sel: BlockSelection, head_dim: int):
    """Write the compressed key that each active lane's new column ``idx``
    completes, if it completes one. ``k_pool`` already holds the column."""
    K, s = sel.kernel, sel.stride
    lo = idx - (K - 1)
    done = active & (lo >= 0) & (lo % s == 0)
    window = _pool_columns(k_pool, table, lo, K, head_dim)
    keys = _mean_keys(window, jnp.zeros((idx.shape[0], 1), jnp.int32), K)
    return _put_keys(leaf, table, keys, (lo // s)[:, None], done[:, None], sel)


def row_keys(leaf, table):
    """Every compressed key of each row, in key order: (rows, heads,
    blocks_per_slot * keys_per_block, D)."""
    keys = leaf[table]  # (rows, bps, heads, per_block, D); OOB ids clamp
    keys = jnp.moveaxis(keys, 2, 1)
    return keys.reshape(*keys.shape[:2], -1, keys.shape[-1])


def block_scores(q, keys, last, sel: BlockSelection, scale: float):
    """``B_g[t, b]``. ``q``: (rows, heads, group, D), a query's heads by K/V
    head; ``keys``: (rows, heads, n_keys, D) each row's compressed keys in
    key order (``row_keys``), or (heads, n_keys, D) the one slot's that
    every row reads; ``last``: (rows,) each query's column. Returns (rows,
    heads, n_keys / keys_per_block) float32, 0 where a block has no key
    yet."""
    rows, heads, n_keys = q.shape[0], keys.shape[-3], keys.shape[-2]
    with jax.named_scope("block_scores"):
        s = jnp.einsum("rhgd,rhkd->rhgk" if keys.ndim == 4 else "rhgd,hkd->rhgk", q,
                       keys.astype(q.dtype), preferred_element_type=jnp.float32) * scale
        exists = (jnp.arange(n_keys) * sel.stride + sel.kernel - 1 <= last[:, None])
        exists = exists[:, None, None]
        top = jnp.max(jnp.where(exists, s, jnp.finfo(jnp.float32).min), -1, keepdims=True)
        e = jnp.where(exists, jnp.exp(s - top), 0.0)
        p = e / jnp.maximum(e.sum(-1, keepdims=True), jnp.finfo(jnp.float32).tiny)
        P = p.sum(2)  # (rows, heads, n_keys)
        return P.reshape(rows, heads, -1, sel.keys_per_block).max(-1)


def select_blocks(scores, last, sel: BlockSelection, body: str):
    """``Sel(t)`` as a mask (rows, blocks) int8 of 0 and 1. ``scores``: (rows,
    blocks) float32 (``block_scores``); ``last``: (rows,) each query's
    column. The forced blocks are given the largest score there is, so the
    exact top-``k`` of ``select_columns`` (ties to the lower block) takes
    them first."""
    rows, nb = scores.shape
    width = -(-nb // 128) * 128  # the selection kernel's rows are whole lanes
    blocks = jnp.arange(width)
    own = last // sel.block
    local = (blocks[None] + 1) * sel.block - 1 >= last[:, None] - sel.window + 1
    forced = (blocks[None] < sel.init_blocks) | local
    keyed = jnp.where(forced, jnp.inf, jnp.pad(scores, ((0, 0), (0, width - nb))))
    with jax.named_scope("select_blocks"):
        mask = select_columns(keyed, own, sel.top_k, body)
    dense = (last < sel.dense_len)[:, None] & (blocks[None] <= own[:, None])
    return jnp.where(dense, jnp.int8(1), mask)[:, :nb]


def scored_blocks(sel: BlockSelection) -> int:
    """The most blocks ``select_blocks`` gives a query at or past
    ``dense_len`` by score: ``top_k`` less the forced ones, its init blocks
    and the fewest blocks that hold a window of ``sel.window`` columns.
    Those two lie apart only where ``dense_len`` is a window past the init
    blocks, which this holds ``sel`` to."""
    if sel.dense_len < sel.init_blocks * sel.block + sel.window:
        raise ValueError(f"dense_len {sel.dense_len} is less than a window of {sel.window} "
                         f"past {sel.init_blocks} init blocks of {sel.block}")
    return sel.top_k - sel.init_blocks - -(-sel.window // sel.block)


def chosen_blocks(mask, width: int):
    """The selected blocks of each row in ascending order, ``(rows, width)``,
    then the out-of-range block number in the places left."""
    rows, nb = mask.shape
    blocks = jnp.arange(nb, dtype=jnp.int32)
    key = jnp.where(mask > 0, -blocks, -nb)  # the lowest block first
    top, _ = jax.lax.top_k(key, min(width, nb))
    top = jnp.pad(top, ((0, 0), (0, width - top.shape[1])), constant_values=-nb)
    return jnp.where(top > -nb, -top, nb)


def _block_scores_kernel(start_ref,   # scalar prefetch: (1,) int32
                         q_ref,       # (1, group, tq, D)
                         k_ref,       # (1, keys_per_block, nbp, D)
                         o_ref,       # (1, tq, nbp)
                         p_ref,       # scratch (keys_per_block, tq, nbp) float32
                         *, sel: BlockSelection, scale: float):
    i = pl.program_id(1)
    group, tq, _ = q_ref.shape[1:]
    per_block, nbp = k_ref.shape[1], k_ref.shape[2]
    t = start_ref[0] + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    first = jax.lax.broadcasted_iota(jnp.int32, (1, nbp), 1) * sel.block
    ends = [first + r * sel.stride + sel.kernel - 1 for r in range(per_block)]

    @pl.when(start_ref[0] + (i + 1) * tq - 1 < sel.dense_len)
    def _dense():  # every query of the tile attends every column: nothing to score
        o_ref[0] = jnp.zeros((tq, nbp), jnp.float32)

    @pl.when(start_ref[0] + (i + 1) * tq - 1 >= sel.dense_len)
    def _score():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

        def head(h, carry):
            qh = q_ref[0, h]
            s = [jnp.where(ends[r] <= t, jax.lax.dot_general(
                qh, k_ref[0, r], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale, -jnp.inf)
                for r in range(per_block)]
            top = s[0].max(axis=1, keepdims=True)
            for r in range(1, per_block):
                top = jnp.maximum(top, s[r].max(axis=1, keepdims=True))
            top = jnp.where(top == -jnp.inf, 0.0, top)
            e = [jnp.exp(x - top) for x in s]
            total = e[0].sum(axis=1, keepdims=True)
            for r in range(1, per_block):
                total = total + e[r].sum(axis=1, keepdims=True)
            inv = 1.0 / jnp.maximum(total, jnp.finfo(jnp.float32).tiny)
            for r in range(per_block):
                p_ref[r] += e[r] * inv
            return carry

        jax.lax.fori_loop(0, group, head, 0)
        best = p_ref[0]
        for r in range(1, per_block):
            best = jnp.maximum(best, p_ref[r])
        o_ref[0] = best


@functools.partial(jax.jit, static_argnames=("sel", "scale", "tq", "interpret"))
def pallas_block_scores(q, keys, start, sel: BlockSelection, scale: float,
                        tq: int | None = None, interpret: bool = False):
    """``B_g[t, b]`` of one chunk of ONE slot. ``q``: (heads, group, T, D), a
    query's heads by K/V head; ``keys``: (heads, n_keys, D) the slot's
    compressed keys in key order (``row_keys``); ``start``: the chunk's first
    column. Returns (heads, T, blocks) float32, the blocks padded to whole
    lanes; a tile whose queries all attend every column is not scored and
    reads 0. A grid step is one K/V head and a tile of queries: the slot's
    keys stay in VMEM, laid out a plane for each key of a block, so that a
    block's score is the largest of its planes' and no lane is reshaped."""
    heads, group, T, D = q.shape
    per_block = sel.keys_per_block
    nb = keys.shape[1] // per_block
    nbp = -(-nb // 128) * 128
    if tq is None:
        tq = max(d for d in range(8, min(T, 256) + 1, 8) if T % d == 0)
    planes = jnp.swapaxes(keys.reshape(heads, nb, per_block, D), 1, 2)
    planes = jnp.pad(planes, ((0, 0), (0, 0), (0, nbp - nb), (0, 0))).astype(q.dtype)
    out = pl.pallas_call(
        functools.partial(_block_scores_kernel, sel=sel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, T // tq),
            in_specs=[pl.BlockSpec((1, group, tq, D), lambda h, i, *_: (h, 0, i, 0)),
                      pl.BlockSpec((1, per_block, nbp, D), lambda h, i, *_: (h, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, tq, nbp), lambda h, i, *_: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((per_block, tq, nbp), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, T, nbp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_SPARSE_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="block_scores",
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q, planes)
    return out
