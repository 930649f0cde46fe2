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
"""

from __future__ import annotations

import functools

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
