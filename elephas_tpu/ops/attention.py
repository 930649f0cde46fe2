"""Blockwise (flash) attention.

``flash_attention(q, k, v, causal)`` computes softmax attention in tiles
so the (seq × seq) score matrix never materializes in HBM. On TPU a
Pallas kernel is used (MXU-tiled, VMEM-resident running max/sum); on CPU
(tests) an XLA ``lax.scan`` blockwise implementation with identical
numerics runs instead.

Shapes: q, k, v are (batch, heads, seq, head_dim); returns the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _blockwise_reference(q, k, v, causal: bool, block_q: int, block_k: int):
    """Numerically-stable streaming softmax over k/v blocks (XLA path)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=q.dtype))
    q = q * scale

    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    # Pad seq dims to block multiples (masked out below).
    q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * block_q - sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * block_k - sk), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * block_k - sk), (0, 0)))

    q_blocks = q.reshape(b, h, nq, block_q, d)

    def process_q_block(qi, q_blk):
        q_pos = qi * block_q + jnp.arange(block_q)

        def scan_kv(carry, kj):
            acc, row_max, row_sum = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, kj * block_k, block_k, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kj * block_k, block_k, axis=2)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk)
            k_pos = kj * block_k + jnp.arange(block_k)
            valid = k_pos[None, :] < sk
            if causal:
                valid = valid & (k_pos[None, :] <= q_pos[:, None])
            scores = jnp.where(valid[None, None], scores, -jnp.inf)
            new_max = jnp.maximum(row_max, scores.max(axis=-1))
            # Renormalize the running accumulator to the new max.
            correction = jnp.exp(row_max - new_max)
            correction = jnp.where(jnp.isfinite(row_max), correction, 0.0)
            weights = jnp.exp(scores - new_max[..., None])
            acc = acc * correction[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", weights, v_blk
            )
            row_sum = row_sum * correction + weights.sum(axis=-1)
            return (acc, new_max, row_sum), None

        acc0 = jnp.zeros((b, h, block_q, d), dtype=q.dtype)
        max0 = jnp.full((b, h, block_q), -jnp.inf, dtype=q.dtype)
        sum0 = jnp.zeros((b, h, block_q), dtype=q.dtype)
        (acc, _, row_sum), _ = jax.lax.scan(
            scan_kv, (acc0, max0, sum0), jnp.arange(nk)
        )
        return acc / jnp.maximum(row_sum[..., None], 1e-30)

    outs = [
        process_q_block(qi, q_blocks[:, :, qi]) for qi in range(nq)
    ]
    out = jnp.concatenate(outs, axis=2)
    return out[:, :, :sq]


def cache_attention_mask(max_len, seq, idx, pad_offset=None):
    """Validity mask for KV-cache incremental attention.

    The current block of ``seq`` queries lands at cache columns
    ``idx + [0, seq)``; each query may attend every cached column up to
    its own (causal within the block, everything cached before it), but
    never the leading left-pad columns of its row.

    ``idx``: scalar () — one shared write position (the ``generate``
    path, where left-padding aligns every row's columns) — or (batch,)
    — per-row positions (the serving KV-pool path, where slots decode at
    independent depths). ``pad_offset``: None, or (batch,) count of
    left-pad columns per row; column ``j`` is a pad key for row ``b``
    iff ``j < pad_offset[b]``.

    Returns a bool mask broadcastable against (batch, heads, seq,
    max_len) scores: (1, 1, seq, max_len) when both idx and pad_offset
    are row-independent, else (batch, 1, seq, max_len).
    """
    cols = jnp.arange(max_len)
    rows = jnp.arange(seq)
    idx = jnp.asarray(idx)
    if idx.ndim == 0:
        # (seq, max_len): same causal frontier for every row.
        valid = cols[None, :] <= idx + rows[:, None]
        valid = valid[None, :, :]  # (1, seq, max_len)
    else:
        # (batch, seq, max_len): per-row frontier.
        valid = cols[None, None, :] <= idx[:, None, None] + rows[None, :, None]
    if pad_offset is not None:
        pad_offset = jnp.asarray(pad_offset)
        valid = valid & (cols[None, None, :] >= pad_offset[:, None, None])
    return valid[:, None]  # broadcast over heads


# -- paged KV cache: layout, gather/scatter, decode attention ------------------
#
# The serving pool stores K/V as physical blocks shared across slots through
# a (max_slots, blocks_per_slot) block table. A block holds ``block_size``
# columns of ``head_dim`` values per head, PACKED ``pack`` columns to a row:
# a leaf is ``(num_blocks, heads, block_size // pack, pack * head_dim)`` and
# column ``c`` of a block is lanes ``[(c % pack) * head_dim, ...)`` of row
# ``c // pack``. With ``head_dim`` 64 a row is 128 wide, the TPU's lane
# count: the array's default device layout is then row-major, so a block is
# one contiguous piece of HBM that a gather, a scatter along dimension 0 or
# a DMA moves alone. (A 64-wide minor dimension is padded to 128 lanes, and
# the default layout of ``(num_blocks, heads, bs, 64)`` puts ``num_blocks``
# minor-most: every program that touched the pool first copied the whole
# leaf into a workable layout and copied it back, 74 % of a decode step.)
# Row-major, the packed leaf is the same memory as the logical
# ``(num_blocks, heads, block_size, head_dim)``; the helpers below reshape
# the few blocks they gather, never the pool.
#
# Unallocated table entries carry the OUT-OF-RANGE id ``num_blocks``:
# gathers clamp (the garbage columns sit at or past every reader's cache
# index, so the causal mask hides them) and scatters drop
# (``mode="drop"``), so no index is ever negative. Every write is a
# read-modify-write of whole blocks along dimension 0, in place on a
# donated leaf: a scatter on two dimensions would relayout the pool.

_LANES = 128


def pool_leaf_shape(num_blocks, heads, block_size, head_dim):
    """Shape of a K or V pool leaf: as many columns to a row as fill the
    lanes and divide the block."""
    pack = math.gcd(block_size, max(1, _LANES // head_dim))
    return (num_blocks, heads, block_size // pack, pack * head_dim)


def latent_leaf_shape(num_blocks, block_size, width):
    """Shape of a latent pool leaf, one head whose columns are key and, in
    their first part, value: ``(num_blocks, 1, width, block_size)``, a
    block's COLUMNS minor. A latent is 576 values wide where the lanes are
    128: rows of 576 would be padded to 640 on the device (and the default
    layout of ``(..., 128, 576)`` puts the 128 minor-most anyway, so every
    program would relayout the pool); with ``block_size`` a multiple of 128
    this leaf is row-major and dense, a block one contiguous piece of HBM,
    and a column's ``width`` values cost their own bytes and no more."""
    return (num_blocks, 1, width, block_size)


def _unpack(blocks, head_dim: int):
    """(..., heads, rows, pack * d) gathered blocks -> (..., heads, bs, d)."""
    return blocks.reshape(*blocks.shape[:-2], -1, head_dim)


def _rows_of(blocks):
    """(..., n, heads, bs, d) blocks of one row -> (..., heads, n * bs, d)."""
    blocks = jnp.moveaxis(blocks, -4, -3)
    return blocks.reshape(*blocks.shape[:-3], -1, blocks.shape[-1])


# The helpers below are jitted: a 48-layer program calls each 96 times at
# one shape, and the inner jit is traced and lowered once for all of them.


@functools.partial(jax.jit, static_argnames=("head_dim",))
def paged_to_contiguous(leaf, table, head_dim: int):
    """Gather a paged K/V leaf into per-slot contiguous rows: returns
    (max_slots, heads, blocks_per_slot * block_size, head_dim)."""
    return _rows_of(_unpack(leaf[table], head_dim))


def _write_windows(pool_leaf, table, start, window, ok, latent=False):
    """Write ``window[s]``, (heads, count, head_dim), at columns
    ``[start[s], start[s] + count)`` of row ``table[s]``: the blocks the
    window touches are gathered, updated and set back along dimension 0.
    Blocks of a row with ``ok[s]`` false, past the row's table, or
    unallocated go to the out-of-range id and drop. A ``latent`` leaf's
    blocks are turned columns-major for the update and back."""
    num_blocks, _, r, lanes = pool_leaf.shape
    rows, bps = table.shape
    _, count, head_dim = window.shape[1:]
    bs = r * lanes // head_dim
    n = (count + bs - 2) // bs + 1  # blocks a window of `count` can touch
    first = start // bs
    js = first[:, None] + jnp.arange(n)[None, :]
    ids = jnp.take_along_axis(table, jnp.clip(js, 0, bps - 1), axis=1)
    touched = (js < bps) & (js * bs < (start + count)[:, None])
    ids = jnp.where(ok[:, None] & touched, ids, num_blocks)
    blocks = pool_leaf[ids]  # (rows, n, heads, r, lanes); OOB clamps
    if latent:
        blocks = jnp.swapaxes(blocks, -1, -2)
    win = _rows_of(_unpack(blocks, head_dim))
    win = jax.vmap(
        lambda w, new, at: jax.lax.dynamic_update_slice(w, new, (0, at, 0))
    )(win, window.astype(pool_leaf.dtype), start - first * bs)
    heads = win.shape[1]
    win = jnp.moveaxis(win.reshape(rows, heads, n, bs, head_dim), 2, 1)
    if latent:
        win = jnp.swapaxes(win, -1, -2)
    return pool_leaf.at[ids.reshape(-1)].set(
        win.reshape((rows * n,) + pool_leaf.shape[1:]), mode="drop"
    )


@functools.partial(jax.jit, static_argnames=("latent",))
def scatter_prefill_columns(pool_leaf, row_table, start, chunk, latent=False):
    """Write one prefill chunk's columns ``[start, start + C)`` of ONE
    slot into its physical blocks. ``chunk``: (heads, C, head_dim).
    Columns landing in unallocated blocks (right-pad garbage past the
    slot's allocation) drop."""
    return _write_windows(
        pool_leaf, row_table[None], jnp.reshape(start, (1,)), chunk[None],
        jnp.ones((1,), bool), latent,
    )


@functools.partial(jax.jit, static_argnames=("count",))
def scatter_spec_columns(pool_leaf, contiguous, table, idx, count, active):
    """Write each slot's ``count`` freshly-computed columns
    ``[idx[s], idx[s] + count)`` back into its physical blocks, for
    speculative draft/verify windows.

    ``contiguous`` is the (max_slots, heads, L, head_dim) view AFTER an
    apply with seq == count wrote those columns (``idx`` is the
    PRE-advance cache index vector; ``count`` is static). Inactive lanes
    and columns past the row's virtual capacity drop. Rejected-suffix
    columns are written too — they sit at or past every reader's causal
    frontier until a later accepted token overwrites them, so they are
    never attended.
    """
    written = jax.vmap(
        lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, count, axis=1)
    )(contiguous, idx)
    return _write_windows(pool_leaf, table, idx, written, active)


# -- paged decode attention: the pool read and written in place ---------------
#
# One decode step never builds a per-slot contiguous cache. Per layer,
# (a) each active lane's new K/V column goes into its physical block and
# (b) each lane's one query attends over that lane's live blocks through
# the block table. Two bodies with the same numerics (float32 scores and
# softmax): a Pallas kernel on a single TPU that moves live blocks only,
# and a plain XLA body elsewhere (CPU, a GSPMD mesh) that is also the
# kernel's reference.

PAGED_BODIES = ("paged_pallas", "paged_xla")

# VMEM the kernel may plan for, inside Mosaic's default scoped limit.
_PAGED_VMEM_BUDGET = 12 << 20

# K and V bytes a grid step of the decode kernel moves at the least, where
# a row has as many blocks and VMEM the room: a step costs about 0.4 us
# whatever it moves (on the chip, microseconds a call of one layer at 1 / 2
# / 4 / 8 blocks a step: gpt2-xl's 400-KB blocks 204 / 164 / 154 / 158,
# where the copies alone take 146; the hybrid's 32-KB blocks at 1 / 8 / 32
# / 48 / 72 a step 711 / 186 / 129 / 128 / 127).
_DECODE_STEP_BYTES = 3 << 19


def _paged_pallas_fits(pool_shape, dtype, head_dim, q_heads=None,
                       blocks=1, latent=False) -> bool:
    """Whether the kernel's tiles lower for this pool layout at ``blocks``
    blocks a grid step: rows a full lane width, whole sublane tiles a head
    (so ``(blocks, heads, r, lanes)`` folds to ``(blocks * heads * r,
    lanes)`` for free), and buffers that fit VMEM: two each of a step's K
    and V blocks, and the float32 scores of a step, their columns and what
    the softmax makes of them. ``q_heads``: the query heads, where they
    are more than the pool's K/V heads. A ``latent`` pool has its own
    layout and kernels (``_latent_fits``)."""
    if latent:
        return _latent_fits(pool_shape, dtype, head_dim, q_heads, blocks)
    _, heads, rows, lanes = pool_shape
    itemsize = jnp.dtype(dtype).itemsize
    if lanes % _LANES or itemsize not in (2, 4) or rows % (32 // itemsize):
        return False
    block_bytes = heads * rows * lanes * itemsize
    query_rows = (lanes // head_dim) * (-(-(q_heads or heads) // 16) * 16)
    scores_bytes = 4 * query_rows * heads * rows
    return blocks * (4 * block_bytes + 5 * scores_bytes) <= _PAGED_VMEM_BUDGET


def _latent_fits(pool_shape, dtype, head_dim, q_heads, blocks=1) -> bool:
    """Whether the latent kernels' tiles lower for this pool layout
    (``latent_leaf_shape``), at ``blocks`` blocks a grid step of the decode
    kernel: one head, a block's columns a whole number of lane tiles, its
    width and a lane's heads whole sublane tiles, and buffers that fit
    VMEM: two of a step's blocks, and the float32 scores of the heads
    against them (and what the softmax makes of them)."""
    _, heads, width, block_size = pool_shape
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    if (heads != 1 or width != head_dim or itemsize not in (2, 4)
            or block_size % _LANES or width % sublanes
            or not q_heads or q_heads % sublanes):
        return False
    held = 2 * blocks * width * block_size * itemsize
    scores = 3 * 4 * q_heads * blocks * block_size
    return held + scores <= _PAGED_VMEM_BUDGET


def paged_decode_blocks(pool_shape, dtype, head_dim, q_heads,
                        blocks_per_slot, latent: bool = False) -> int:
    """Blocks of a lane that one grid step of the decode kernel folds, from
    the layout alone: as many as move ``_DECODE_STEP_BYTES``, a row's worth
    at the most, and no more than VMEM holds (one, where that is all). A
    ``latent`` pool is one leaf: a column is read once for key and value."""
    _, heads, rows, lanes = pool_shape
    moved = (1 if latent else 2) * heads * rows * lanes * \
        jnp.dtype(dtype).itemsize  # K and V
    blocks = max(1, min(blocks_per_slot, -(-_DECODE_STEP_BYTES // moved)))
    while blocks > 1 and not _paged_pallas_fits(pool_shape, dtype, head_dim,
                                                q_heads, blocks, latent):
        blocks -= 1
    return blocks


def paged_decode_body(pool_shape, dtype, head_dim, mesh=None,
                      q_heads=None, latent: bool = False) -> str:
    """Name of the body ``paged_decode_attention`` runs for this backend,
    mesh and pool layout. A Pallas call is not partitioned by sharding
    annotations, so a mesh takes the XLA body."""
    if _on_tpu() and mesh is None and _paged_pallas_fits(
            pool_shape, dtype, head_dim, q_heads, latent=latent):
        return "paged_pallas"
    return "paged_xla"


def _paged_attend_xla(q, k_pool, v_pool, table, last, scale=None,
                      kv_b=None, selected=None, window=None):
    """Plain body of both paged attentions: gather one layer's blocks
    through the table and score them in the block layout ``(rows, blocks,
    heads, bs, head_dim)``. ``q``: (rows, q_heads, Q, head_dim), ``Q``
    queries a table row, ``g`` query heads to a K/V head (1 for full
    multi-head); ``last``: (rows, Q) the last column each query attends.
    Without a ``v_pool`` the pool is a latent one and ``kv_b`` expands it
    (``paged_decode_attention``): the queries are absorbed, a column's first
    ``rank`` values are its value, and the result goes through ``W_uv``.
    ``selected``: (rows, Q, S) of 0 and 1, the columns each query attends of
    its live ones (all of them where None); ``window``: a query attends the
    ``window`` columns that end with its ``last`` only."""
    if v_pool is None:
        nope = _nope_width(q, kv_b, k_pool.shape[2])
        q = _absorb(q, kv_b, k_pool.shape[2])
    rows, q_heads, count, head_dim = q.shape
    bps = table.shape[1]
    kb = k_pool[table]  # OOB ids clamp; masked below
    if v_pool is None:  # a latent leaf: a block's columns minor
        kb = jnp.swapaxes(kb, -1, -2)
        vb = kb[..., :kv_b.shape[0]]
    else:
        kb = _unpack(kb, head_dim)
        vb = _unpack(v_pool[table], head_dim)
    heads, bs = kb.shape[-3], kb.shape[-2]
    scores = jnp.einsum(
        "shgqd,sjhbd->shgqjb",
        q.reshape(rows, heads, q_heads // heads, count, head_dim), kb,
        preferred_element_type=jnp.float32,
    ) * (1.0 / (head_dim ** 0.5) if scale is None else scale)
    cols = (jnp.arange(bps) * bs)[:, None] + jnp.arange(bs)[None, :]
    valid = cols <= last[:, :, None, None]  # (rows, Q, bps, bs)
    if window is not None:
        valid &= cols > last[:, :, None, None] - window
    if selected is not None:
        valid &= selected[..., :bps * bs].reshape(rows, count, bps, bs) > 0
    scores = jnp.where(valid[:, None, None], scores,
                       jnp.finfo(jnp.float32).min)
    flat = scores.reshape(*scores.shape[:4], bps * bs)
    weights = jax.nn.softmax(flat, axis=-1).reshape(scores.shape)
    out = jnp.einsum(
        "shgqjb,sjhbd->shgqd", weights.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32,
    )
    out = out.reshape(q.shape[:-1] + vb.shape[-1:]).astype(q.dtype)
    return out if v_pool is not None else _expand_values(out, kv_b, nope)


def _nope_width(q, kv_b, width: int) -> int:
    """Of a head's query (nope + pe wide), the part that meets the expanded
    keys: a latent column is ``width`` = rank + pe wide, ``kv_b`` has the
    rank."""
    return q.shape[-1] - (width - kv_b.shape[0])


def _absorb(q, kv_b, width: int):
    """A head's query against its expanded keys is a query of ``rank``
    against the latent itself. ``q``: (..., heads, queries, nope + pe);
    ``kv_b``: (rank, heads, nope + v_head). Returns (..., heads, queries,
    width): ``q_nope_h W_uk_h``, then the rotary part as it is."""
    nope = _nope_width(q, kv_b, width)
    lat = jnp.einsum("...hqf,rhf->...hqr", q[..., :nope], kv_b[..., :nope],
                     preferred_element_type=jnp.float32)
    return jnp.concatenate([lat.astype(q.dtype), q[..., nope:]], -1)


def _expand_values(out, kv_b, nope: int):
    """What the softmax made of the latents, (..., heads, queries, rank),
    through ``W_uv``: a head's own values, (..., heads, queries, v_head)."""
    return jnp.einsum("...hqr,rhf->...hqf", out, kv_b[..., nope:],
                      preferred_element_type=jnp.float32).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("body", "scale", "window"))
def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, table, idx,
                           active, body: str, scale=None, kv_b=None,
                           selected=None, window=None):
    """One decode step's attention of one layer over the paged pool.

    ``q``: (slots, q_heads, head_dim); ``k_new``/``v_new``: (slots,
    heads, head_dim), the step's one token per lane. ``heads`` is the
    pool's, and ``q_heads`` a multiple of it: with grouped heads the pool
    holds the K/V heads the model has, never a copy a query head.
    ``k_pool``/``v_pool``: pool leaves (see the layout above). ``table``: (slots, blocks_per_slot) block ids, unallocated
    entries out of range. ``idx``: (slots,) the column each lane writes,
    so lane ``s`` attends columns ``<= idx[s]`` of its blocks
    ``table[s, : idx[s] // bs + 1]`` (paged rows are never left-padded).
    ``active``: (slots,) bool; an inactive lane writes nothing and its
    output is not read.

    Returns ``(out, k_pool, v_pool)`` with ``out`` (slots, heads,
    head_dim); the pools carry the new columns. ``body`` is one of
    ``PAGED_BODIES`` (``paged_decode_body`` picks it).

    A latent pool (``latent_leaf_shape``): ``v_new`` and ``v_pool`` are
    None and ``kv_b``, (rank, q_heads, nope + v_head), is the matrix that
    expands a latent into a head's key and value; ``q`` is (slots, q_heads,
    nope + pe) and ``k_new`` (slots, 1, rank + pe). The step runs absorbed:
    ``q_nope_h W_uk_h`` is a query of ``rank`` against the latent itself, a
    column's first ``rank`` values are also its value, read with the key and
    not again, and ``W_uv_h`` expands the result, so every head reads the
    one latent head and nothing a head is ever built (a lane has one query:
    expanding its thousands of columns would cost ``rank`` products a
    column where absorbing costs them once). ``out`` is (slots, q_heads,
    v_head) and the returned ``v_pool`` None. ``scale`` is the softmax
    scale where it is not ``head_dim ** -0.5``. A latent step may also be
    given ``selected``, (slots, S) of 0 and 1: the columns a lane's query
    attends of its live ones (``ops.sparse_index``; both bodies do the dense
    arithmetic over the live blocks and give the exact result over the set),
    and a static ``window``: lane ``s`` attends columns ``idx[s] - window <
    c <= idx[s]`` alone.
    """
    if body not in PAGED_BODIES:
        raise ValueError(f"unknown paged body {body!r}; expected one of "
                         f"{PAGED_BODIES}")
    if (selected is not None or window is not None) and v_pool is not None:
        raise NotImplementedError("a selection or a window is a latent pool's")
    if body == "paged_pallas":
        from elephas_tpu.ops.attention_pallas import (
            pallas_latent_decode_attention,
            pallas_paged_decode_attention,
        )

        # one kernel writes the column and attends: an XLA scatter before
        # it would have the compiler stage the whole leaf through VMEM
        with jax.named_scope("paged_attention"):
            if v_pool is None:
                width = k_pool.shape[2]
                out, k_pool = pallas_latent_decode_attention(
                    _absorb(q[:, :, None], kv_b, width)[:, :, 0], k_new, k_pool,
                    table, idx, active, kv_b.shape[0], scale,
                    selected=selected, window=window)
                out = _expand_values(out[:, :, None], kv_b,
                                     _nope_width(q, kv_b, width))[:, :, 0]
                return out, k_pool, None
            return pallas_paged_decode_attention(
                q, k_new, v_new, k_pool, v_pool, table, idx, active
            )
    with jax.named_scope("kv_write"):
        k_pool = _write_windows(k_pool, table, idx, k_new[:, :, None], active,
                                v_pool is None)
        if v_pool is not None:
            v_pool = _write_windows(v_pool, table, idx, v_new[:, :, None],
                                    active)
    with jax.named_scope("paged_attention"):
        out = _paged_attend_xla(
            q[:, :, None], k_pool, v_pool, table, idx[:, None], scale, kv_b,
            None if selected is None else selected[:, None], window)[:, :, 0]
    return out, k_pool, v_pool


# -- paged chunk attention: a prefill chunk over the pool in place ------------
#
# A prefill chunk of ONE slot never builds the slot's contiguous row
# either. Per layer, (a) the chunk's K/V columns go into the blocks they
# land in (block-grained, ``scatter_prefill_columns``) and (b) the chunk's
# queries attend the slot's live blocks through its table row, query ``i``
# over columns ``<= start + i``. The same two bodies, chosen the same way.


def _widest_divisor(n: int, limit: int) -> int:
    """The largest divisor of ``n`` that is no more than ``limit``."""
    return max(d for d in range(1, min(n, limit) + 1) if n % d == 0)


def _chunk_tiles(heads, block_size, chunk):
    """Tile sizes of the chunk kernel, from the shapes alone: K/V heads a
    grid step, queries a tile, blocks a grid step. A step covers as many
    columns as the chunk is wide and 256 at the least: a grid step costs
    about as much as a head's work in it, and what a wider step scores
    past the chunk's last column is dead (on the chip, microseconds a call
    at ``start`` 0 / the longest: chunks of 128 on 25 heads, 256 columns
    a step 24 / 60, 512 32 / 51, 128 23 / 99; chunks of 512 on one K/V
    head of 20 query heads, 512 columns 56 / 319, 256 88 / 568)."""
    head_group = max(n for n in range(1, 9) if heads % n == 0)
    return (head_group, _widest_divisor(chunk, 512),
            max(1, max(256, min(chunk, 512)) // block_size))


def _paged_chunk_fits(pool_shape, dtype, head_dim, chunk) -> bool:
    """Whether the chunk kernel's tiles lower for this pool layout and
    chunk width: rows a full lane width, whole sublane tiles a block and
    a query tile, and buffers inside the VMEM budget."""
    _, heads, rows, lanes = pool_shape
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    pack = lanes // head_dim
    head_group, tile, blocks = _chunk_tiles(heads, rows * pack, chunk)
    if (lanes % _LANES or itemsize not in (2, 4) or rows % sublanes
            or tile % sublanes):
        return False
    query_rows = pack * tile
    moved = (2 * head_group * query_rows * lanes  # queries in, output back
             + 2 * blocks * head_group * rows * lanes) * itemsize
    held = 4 * head_group * query_rows * (lanes + 2 * _LANES)  # acc, max, sum
    scores = 4 * 4 * query_rows * blocks * rows
    bounds = 4 * query_rows * _LANES
    return 2 * (moved + bounds) + held + scores <= _PAGED_VMEM_BUDGET


# Queries a tile of the latent chunk kernel, at the most.
_LATENT_QUERY_TILE = 512


def _latent_chunk_tiles(q_heads, block_size, chunk):
    """Tile sizes of the latent chunk kernel, from the shapes alone: heads a
    grid step (each with all of the chunk's queries resident; a block is
    moved once for them, and a head's keys and values are expanded once a
    step), blocks a grid step (512 columns, or a block), and the queries a
    tile inside a step: the widest divisor of the chunk up to
    ``_LATENT_QUERY_TILE``. A step walks the tiles that hold a live (query,
    column) pair and no other (``latent_chunk_tiles_visited`` counts
    them)."""
    group = max(n for n in range(1, 5) if q_heads % n == 0)
    return (group, max(1, 512 // block_size),
            _widest_divisor(chunk, _LATENT_QUERY_TILE))


def latent_chunk_tiles_visited(start: int, valid: int, chunk: int, tile: int,
                               columns: int):
    """``(visited, dense)`` of one chunk call of the latent chunk kernel,
    from the host's integers: the (query tile, column step) pairs its grid
    steps walk, a tile ``tile`` queries and a step ``columns`` columns, and
    what one tile of the whole chunk a step up to column ``start + chunk -
    1`` amounts to in the same units (the kernel before it tiled its
    queries or knew ``valid``). The kernel's own arithmetic, step by
    step."""
    end = (valid - 1) // tile + 1
    visited = sum(end - max(j * columns - start, 0) // tile
                  for j in range((start + valid - 1) // columns + 1))
    return visited, (chunk // tile) * ((start + chunk - 1) // columns + 1)


def paged_chunk_body(pool_shape, dtype, head_dim, chunk, mesh=None,
                     q_heads=None, latent: bool = False) -> str:
    """Name of the body ``paged_chunk_attention`` runs for this backend,
    mesh, pool layout and chunk width, as ``paged_decode_body`` has it."""
    if latent:  # the chunk kernel sets its own VMEM limit
        fits = _latent_fits(pool_shape, dtype, head_dim, q_heads)
    else:
        fits = _paged_chunk_fits(pool_shape, dtype, head_dim, chunk)
    if _on_tpu() and mesh is None and fits:
        return "paged_pallas"
    return "paged_xla"


@functools.partial(jax.jit, static_argnames=("latent",))
def scatter_prefill_blocks(pool_leaf, row_table, start, chunk, latent=False):
    """``scatter_prefill_columns`` for a chunk of whole blocks: ``start``
    and the chunk's width are multiples of the block size, so the chunk IS
    its blocks and nothing of the pool is read. The one relayout is the
    chunk's own, ``pack`` columns to a row."""
    num_blocks, heads, r, lanes = pool_leaf.shape
    count, head_dim = chunk.shape[1:]
    bs = r * lanes // head_dim
    js = start // bs + jnp.arange(count // bs)
    ids = jnp.where(js < row_table.shape[0],
                    row_table[jnp.clip(js, 0, row_table.shape[0] - 1)],
                    num_blocks)
    if latent:  # (1, count, width) -> (count / bs, 1, width, bs)
        blocks = jnp.swapaxes(chunk.astype(pool_leaf.dtype).reshape(
            heads, count // bs, bs, head_dim), -1, -2)
    else:
        blocks = chunk.astype(pool_leaf.dtype).reshape(
            heads, count // bs, r, lanes)
    return pool_leaf.at[ids].set(jnp.moveaxis(blocks, 1, 0), mode="drop")


@functools.partial(jax.jit, static_argnames=("body", "aligned", "scale",
                                             "window"))
def paged_chunk_attention(q, k_new, v_new, k_pool, v_pool, row, start,
                          body: str, aligned: bool = False, scale=None,
                          kv_b=None, valid=None, selected=None, window=None):
    """One prefill chunk's attention of one layer of ONE slot over the
    paged pool.

    ``q``: (q_heads, C, head_dim); ``k_new``/``v_new``: (heads, C,
    head_dim), the chunk's columns ``[start, start + C)``; ``heads`` is
    the pool's and ``q_heads`` a multiple of it. ``k_pool``/``v_pool``:
    pool leaves; ``row``: (blocks_per_slot,) the slot's block ids,
    unallocated entries out of range; ``start``: scalar. Query ``i``
    attends columns ``<= start + i`` of the slot's blocks.

    Returns ``(out, k_pool, v_pool)`` with ``out`` (q_heads, C, head_dim);
    the pools carry the chunk's columns, but those in unallocated blocks,
    which drop (the right-pad tail past the slot's allocation: its queries
    are not read). ``body`` is one of ``PAGED_BODIES``
    (``paged_chunk_body`` picks it). ``aligned`` is the caller's promise
    that ``start`` is a multiple of the block size, as ``C`` then has to
    be: the chunk is written as whole blocks, where otherwise the blocks
    it touches are read, updated and set back. ``valid``: a scalar, the
    chunk's real tokens, the rest right-padding (None: all of them). Only
    the latent kernel reads it: there the rows at or past ``valid`` come
    back as zeros it never scored. The K/V kernel and the XLA body ignore
    it and score every row, whose padding rows nobody reads either way.

    A latent pool: ``v_new`` and ``v_pool`` None, ``kv_b`` and ``scale`` as
    ``paged_decode_attention`` has them, ``q`` (q_heads, C, nope + pe) and
    ``k_new`` (1, C, rank + pe); ``out`` is (q_heads, C, v_head). The kernel
    runs a chunk EXPANDED: the latents of a grid step's blocks go through
    ``W_uk_h`` and ``W_uv_h`` once a step and head, and are scored as keys
    and values of that head by the chunk's queries in tiles
    (``_latent_chunk_tiles``), of which a step visits those that hold a live
    (query, column) pair: none wholly above the diagonal, none past
    ``valid``, and no step past column ``start + valid - 1``. With
    thousands of queries to a column, the expansion (``rank`` products a
    column and head) is a fifth of the scores it feeds, and those cost
    ``nope + pe + v_head`` products a (query, column, head) where the
    absorbed form costs ``2 rank + pe``, 3.4 times as many at the published
    sizes (on the chip the absorbed kernel ran at three quarters of the
    MXU's peak and was still three fifths of a chunk's time). The XLA body
    runs absorbed, as a decode step. ``selected``, (C, S) of 0 and 1, and a
    static ``window`` bound what query ``i`` attends as they bound a decode
    step's lane: its row of the selection, and columns ``start + i - window
    < c <= start + i``; the kernel then scores every tile it visits masked,
    and under a window visits no tile whose queries have all left a step's
    columns behind.
    """
    if body not in PAGED_BODIES:
        raise ValueError(f"unknown paged body {body!r}; expected one of "
                         f"{PAGED_BODIES}")
    if (selected is not None or window is not None) and v_pool is not None:
        raise NotImplementedError("a selection or a window is a latent pool's")
    write = scatter_prefill_blocks if aligned else scatter_prefill_columns
    with jax.named_scope("kv_write"):
        if v_pool is None:
            k_pool = write(k_pool, row, start, k_new, latent=True)
        else:
            k_pool = write(k_pool, row, start, k_new)
            v_pool = write(v_pool, row, start, v_new)
    with jax.named_scope("paged_attention"):
        if body == "paged_pallas":
            from elephas_tpu.ops.attention_pallas import (
                pallas_latent_chunk_attention,
                pallas_paged_chunk_attention,
            )

            if v_pool is None:
                out = pallas_latent_chunk_attention(
                    q, kv_b, k_pool, row, start, scale, valid,
                    selected=selected, window=window)
            else:
                out = pallas_paged_chunk_attention(q, k_pool, v_pool, row,
                                                   start)
        else:
            out = _paged_attend_xla(
                q[None], k_pool, v_pool, row[None],
                (start + jnp.arange(q.shape[1]))[None], scale, kv_b,
                None if selected is None else selected[None], window)[0]
    return out, k_pool, v_pool


# -- attention over selected blocks: a block-sparse layer's pages -------------
#
# A block-sparse attention (``ops.sparse_index``: selection by blocks) picks,
# for each query and K/V head, whole blocks of the pool. A decode step reads
# a lane's chosen pages alone, through a table of them. A chunk's XLA body
# walks the slot's live blocks a step of ``_SPARSE_CHUNK_STEP`` at a time,
# each query scoring the blocks it chose (every one up to its own column,
# before ``dense_len``) with an online softmax. On one TPU a kernel keeps a
# tile's running sums in VMEM and walks only the tile's band: every live
# step where the tile has a query before ``dense_len``, else the blocks its
# queries are forced to attend (the init blocks and those of the window),
# which a tile of queries shares. Each query then fetches the pages it chose
# by score outside the band with copies of its own and scores them alone.

# Blocks a step of the chunk's walk covers.
_SPARSE_CHUNK_STEP = 8
# Queries a tile of the chunk kernel.
_SPARSE_QUERY_TILE = 256
# Words a tile of a 1-D array in SMEM holds: a block of one is whole tiles.
_SMEM_TILE = 1024
# Blocks a word of the kernel's copy of the selection holds, a bit each.
_WORD = 32


def block_sparse_decode_attention(q, k_pool, v_pool, table, chosen, idx,
                                  head_dim: int, scale: float):
    """One decode step's attention over each lane's chosen blocks.

    ``q``: (slots, heads, group, D), a lane's query heads by its K/V head;
    ``k_pool``/``v_pool``: pool leaves holding the step's columns;
    ``table``: (slots, blocks_per_slot); ``chosen``: (slots, heads, W) the
    blocks of the lane's row each K/V head attends, out-of-range numbers
    past them (``sparse_index.chosen_blocks``); ``idx``: (slots,) the column
    each lane just wrote. Only the chosen pages of the lane's own K/V head
    are read. Returns (slots, heads, group, D)."""
    bps = table.shape[1]
    bs = k_pool.shape[2] * k_pool.shape[3] // head_dim
    heads = jnp.arange(q.shape[1])[None, :, None]
    with jax.named_scope("block_sparse_attention"):
        phys = jnp.take_along_axis(table[:, None, :], jnp.clip(chosen, 0, bps - 1), 2)
        kb = _unpack(k_pool[phys, heads], head_dim)  # (slots, heads, W, bs, D)
        vb = _unpack(v_pool[phys, heads], head_dim)
        cols = chosen[..., None] * bs + jnp.arange(bs)
        ok = (chosen[..., None] < bps) & (cols <= idx[:, None, None, None])
        s = jnp.einsum("shgd,shwbd->shgwb", q, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, :, None], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s.reshape(*s.shape[:3], -1), axis=-1).reshape(s.shape)
        out = jnp.einsum("shgwb,shwbd->shgd", p.astype(vb.dtype), vb,
                         preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _chunk_walk_xla(q, k_pool, v_pool, row, start, picked, head_dim: int,
                    scale: float):
    """The XLA body of ``block_sparse_chunk_attention``: the slot's live
    blocks a step at a time, every query's scores under its mask, an online
    softmax. Returns (H, g, T, D) float32."""
    H, g, T, D = q.shape
    bps = row.shape[0]
    bs = k_pool.shape[2] * k_pool.shape[3] // head_dim
    G = min(_SPARSE_CHUNK_STEP, bps)
    last = start + jnp.arange(T)
    floor = jnp.finfo(jnp.float32).min

    def step(j, carry):
        acc, m, l = carry
        blocks = j * G + jnp.arange(G)
        ids = jnp.clip(blocks, 0, bps - 1)
        kb = _rows_of(_unpack(k_pool[row[ids]], head_dim))  # (H, G * bs, D)
        vb = _rows_of(_unpack(v_pool[row[ids]], head_dim))
        s = jnp.einsum("hgtd,hcd->hgtc", q, kb, preferred_element_type=jnp.float32) * scale
        cols = (blocks[:, None] * bs + jnp.arange(bs)).reshape(-1)
        chose = jnp.repeat(jnp.take(picked, ids, axis=1) > 0, bs, axis=1)  # (H, G * bs, T)
        ok = jnp.swapaxes(chose, 1, 2) & (cols[None] <= last[:, None])[None] & \
            jnp.repeat(blocks < bps, bs)[None, None]
        s = jnp.where(ok[:, None], s, floor)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(ok[:, None], jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "hgtc,hcd->hgtd", p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        return acc, m_new, l * fix + p.sum(-1)

    acc, _, l = jax.lax.fori_loop(
        0, (start + T - 1) // (bs * G) + 1, step,
        (jnp.zeros((H, g, T, D), jnp.float32), jnp.full((H, g, T), floor),
         jnp.zeros((H, g, T), jnp.float32)))
    return acc / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]


def _words_of(picked):
    """``picked`` (H, blocks, T) as words of 32 blocks' bits: (H, blocks /
    32, T) int32, bit ``b % 32`` of word ``b // 32`` set where query ``t``
    attends block ``b``."""
    H, nb, T = picked.shape
    words = -(-nb // _WORD)
    bits = jnp.pad(picked, ((0, 0), (0, words * _WORD - nb), (0, 0))).reshape(H, words, _WORD, T)
    weight = jnp.left_shift(1, jnp.arange(_WORD, dtype=jnp.int32))[:, None]
    packed = jnp.where(bits > 0, weight, 0).sum(2, dtype=jnp.int32)
    return jnp.pad(packed, ((0, 0), (0, -words % 8), (0, 0)))  # whole sublane tiles


def _nth_set(words, n: int):
    """The first ``n`` set bits of each column of ``words`` (H, words, T)
    in order, as bit numbers: (H, n, T) int32, 0 past the column's count;
    and the count (H, T). Each word's set bits are counted, and the n-th is
    found inside its word: no sort and no scan."""
    nw = words.shape[1]
    held = jax.lax.population_count(words)
    j = jnp.arange(nw)
    before = (held[:, None] * (j[None, :] < j[:, None])[None, :, :, None]).sum(2)
    k = jnp.arange(n)[None, :, None]
    word = ((before + held)[:, None] <= k[..., None, :]).sum(2)  # (H, n, T): the k-th's word
    here = word[:, :, None] == j[:, None]  # (H, n, nw, T), one-hot: sums, not gathers
    got = jnp.where(here, words[:, None], 0).sum(2)
    k = k - jnp.where(here, before[:, None], 0).sum(2)  # its place among the word's set bits
    place = jnp.zeros_like(k)  # the most bits below which k or fewer are set
    for step in (16, 8, 4, 2, 1):
        wider = place + step
        below = jax.lax.population_count(got & ((1 << wider) - 1))
        place = jnp.where(below <= k, wider, place)
    return jnp.where(word < nw, word * _WORD + place, 0), held.sum(1)


def _bits_between(lo, hi, nw: int):
    """(nw, T) int32 words with the bits of blocks ``lo[t] <= b < hi[t]``."""
    first = jnp.arange(nw, dtype=jnp.int32)[:, None] * _WORD
    a = jnp.clip(lo[None] - first, 0, _WORD)
    b = jnp.clip(hi[None] - first, 0, _WORD)

    def below(n):  # bits 0 .. n - 1
        return jnp.where(n >= _WORD, -1, (1 << jnp.minimum(n, _WORD - 1)) - 1)

    return below(b) & ~below(a)


def block_sparse_chunk_plan(picked, start, valid, sel, tq: int, bs: int):
    """What the chunk kernel walks and what each query fetches, for tiles of
    ``tq`` queries and blocks of ``bs`` columns. A tile whose first query
    selects (``sel``) walks a BAND: its init blocks, then whole steps of the
    walk from the one that holds its first query's window's first column to
    the one that holds its last query's column; every block a tile with a
    query before ``dense_len`` can attend is in its band. A selecting query
    fetches the pages it chose outside the band itself: only blocks it
    chose by score, so no more than ``sparse_index.scored_blocks(sel)``.

    Returns ``tiles`` (T / tq * 3,) int32, each tile's init blocks and the
    first and last block of its band (its queries fetch only where the band
    starts past block 0); ``words``, ``picked`` as bits (``_words_of``);
    ``lists`` (H * T * R,) int32, a record of ``R`` a query: how many pages
    it fetches (none at or past ``valid``), then their blocks in order,
    block 0 in the places left, a tile's records whole SMEM tiles; and
    ``fetched``, the chosen pages of all records."""
    from elephas_tpu.ops.sparse_index import scored_blocks

    H, nb, T = picked.shape
    G = _SPARSE_CHUNK_STEP
    nt = T // tq
    first = start + jnp.arange(nt) * tq  # each tile's first query's column
    hi = jnp.minimum((first + tq - 1) // bs, nb - 1)
    width = max(1, scored_blocks(sel))
    R = width + 1
    if _SMEM_TILE % tq == 0:  # a tile's records fill whole tiles of 1-D SMEM
        R = -(-R * tq // _SMEM_TILE) * _SMEM_TILE // tq
    words = _words_of(picked)
    selects = first >= sel.dense_len
    lo = jnp.maximum(first - sel.window + 1, 0) // bs
    lo = jnp.where(selects, jnp.minimum(lo - lo % G, hi - hi % G), 0)
    init = jnp.minimum(sel.init_blocks, lo)

    def records():
        outside = _bits_between(jnp.repeat(init, tq), jnp.repeat(lo, tq), words.shape[1])
        nth, count = _nth_set(words & jnp.where(jnp.arange(T) < valid, outside, 0), width)
        rec = jnp.concatenate([count[:, None], nth], 1)  # (H, 1 + width, T)
        return jnp.pad(jnp.swapaxes(rec, 1, 2), ((0, 0), (0, 0), (0, R - 1 - width)))

    # a chunk with no query past dense_len walks: no record is built
    lists = jax.lax.cond(selects[-1], records, lambda: jnp.zeros((H, T, R), jnp.int32))
    tiles = jnp.stack([init, lo, hi], -1).reshape(-1).astype(jnp.int32)
    return tiles, words, lists.reshape(-1), lists[..., 0].sum()


def _sparse_chunk_kernel(phys_ref, start_ref, tiles_ref,  # scalar prefetch
                         q_ref,                           # (tq, g * D): the heads side by side
                         words_hbm,                       # (H, blocks / 32, T): picked's bits
                         lists_hbm,                       # (H * T * R,): each query's record
                         k_hbm, v_hbm,                    # the pools, left in HBM
                         o_ref,                           # (tq, g * D): the heads side by side
                         words_ref,                       # (blocks / 32, tq): the tile's
                         lists_ref,                       # SMEM (tq * R,): the tile's
                         band_k, band_v,                  # (2, G, bs, D): a step's pages
                         own_k, own_v,                    # (2, W, bs, D): a query's pages
                         acc_ref, max_ref, sum_ref,       # (g, tq, D|1) float32
                         sem,                             # DMAs in flight: (5, 2)
                         *, G: int, W: int, scale: float):
    """Grid step (K/V head ``h``, query tile ``i``): the tile's queries of
    the group's heads, first against its band ``G`` blocks a step, each
    query under its bits of ``picked`` and the diagonal, then query by
    query against the pages it fetched, all in one online softmax. The
    kernel moves every page itself: while a step (a query) is scored, the
    next one's pages are on their way into the other half of the buffers.
    Every place of a half is filled, and those past the band's live blocks
    or the query's own pages are weighted 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, i = pl.program_id(0), pl.program_id(1)
    tq, D = q_ref.shape[0], band_k.shape[3]
    g = q_ref.shape[1] // D
    bs = band_k.shape[2]
    R = lists_ref.shape[0] // tq
    floor = jnp.finfo(jnp.float32).min
    start = start_ref[0]
    init, lo, hi = (tiles_ref[i * 3 + n] for n in range(3))
    fetches = lo > 0  # only a band past block 0 leaves chosen pages outside it
    init_steps = (init + G - 1) // G
    steps = init_steps + (hi - lo + G) // G

    def band_step(s):  # (its first block, its blocks)
        first = jnp.where(s < init_steps, s * G, lo + (s - init_steps) * G)
        return first, jnp.minimum(G, jnp.where(s < init_steps, init, hi + 1) - first)

    def pair(to):  # the K and V buffers: 0 the band's, 1 a query's
        return ((k_hbm, (band_k, own_k)[to]), (v_hbm, (band_v, own_v)[to]))

    def fetch(blocks, to, slot):
        """Start the copies of head ``h``'s pages of the slot's blocks
        ``blocks(b)``, one for every place of half ``slot`` of ``pair(to)``:
        every place is filled, so all of them are waited for at once."""
        def page(b, carry):
            phys = phys_ref[blocks(b)]
            for leaf, (pool, buf) in enumerate(pair(to)):
                pltpu.make_async_copy(pool.at[phys, h], buf.at[slot, b],
                                      sem.at[2 * to + leaf, slot]).start()
            return carry

        jax.lax.fori_loop(0, pair(to)[0][1].shape[1], page, 0)

    def arrived(to, slot):
        for leaf, (pool, buf) in enumerate(pair(to)):
            pltpu.make_async_copy(pool.at[pl.ds(0, buf.shape[1]), h], buf.at[slot],
                                  sem.at[2 * to + leaf, slot]).wait()

    def band_fetch(s, slot):
        first = band_step(s)[0]
        fetch(lambda b: first + b, 0, slot)

    def own_fetch(r, slot):  # past its count a record names block 0
        fetch(lambda n: lists_ref[r * R + 1 + n], 1, slot)

    bits = pltpu.make_async_copy(words_hbm.at[h, :, pl.ds(pl.multiple_of(i * tq, tq), tq)],
                                 words_ref, sem.at[4, 0])
    bits.start()
    records = pltpu.make_async_copy(
        lists_hbm.at[pl.ds((h * pl.num_programs(1) + i) * lists_ref.shape[0], lists_ref.shape[0])],
        lists_ref, sem.at[4, 1])
    records.start()
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    max_ref[...] = jnp.full(max_ref.shape, floor, jnp.float32)
    sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
    band_fetch(0, 0)
    bits.wait()
    records.wait()

    @pl.when(fetches & (lists_ref[0] > 0))
    def _first_query():
        own_fetch(0, 0)

    # a block's choice spread over its columns: (tq, G) through a 0/1 matrix
    spread = (jax.lax.broadcasted_iota(jnp.int32, (G, G * bs), 1) // bs ==
              jax.lax.broadcasted_iota(jnp.int32, (G, G * bs), 0)).astype(jnp.float32)
    t = start + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def walk(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < steps)
        def _ahead():
            band_fetch(s + 1, 1 - slot)

        arrived(0, slot)
        first, live = band_step(s)
        # a step's G blocks lie in one word: their bits, (G, tq), then (tq, G)
        word = words_ref[pl.ds(first // _WORD, 1), :]
        shift = first % _WORD + jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        bits = jax.lax.shift_right_logical(word, shift) & 1
        chose = jnp.dot(jnp.transpose(bits.astype(jnp.float32)), spread,
                        preferred_element_type=jnp.float32) > 0.5
        cols = first * bs + jax.lax.broadcasted_iota(jnp.int32, (1, G * bs), 1)
        ok = chose & (cols <= t) & (cols < (first + live) * bs)
        keys = band_k[slot].reshape(G * bs, D)
        vals = band_v[slot].reshape(G * bs, D)

        def head(n, carry):
            qh = q_ref[:, pl.ds(pl.multiple_of(n * D, D), D)]
            s = jax.lax.dot_general(qh, keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, floor)
            m_prev = max_ref[n]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            fix = jnp.exp(m_prev - m_new)
            sum_ref[n] = sum_ref[n] * fix + p.sum(axis=1, keepdims=True)
            acc_ref[n] = acc_ref[n] * fix + jnp.dot(
                p.astype(vals.dtype), vals, preferred_element_type=jnp.float32)
            max_ref[n] = m_new
            return carry

        return jax.lax.fori_loop(0, g, head, carry)

    jax.lax.fori_loop(0, steps, walk, 0)

    @pl.when(fetches)
    def _gather():
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, W * bs), 1)

        def query(r, carry):
            slot = jax.lax.rem(r, 2)

            @pl.when((r + 1 < tq) & (lists_ref[jnp.minimum(r + 1, tq - 1) * R] > 0))
            def _ahead():
                own_fetch(r + 1, 1 - slot)

            n = lists_ref[r * R]

            @pl.when(n > 0)
            def _score():  # the query's heads against its pages: a row of the tile
                arrived(1, slot)
                row = pl.ds(r, 1)
                # the query's heads: its row of the 16 (a bfloat16 tile) it lies in
                rows = q_ref[pl.ds(pl.multiple_of(r - r % 16, 16), 16), :]
                pick = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == r % 16
                qr = jnp.where(pick, rows.astype(jnp.float32), 0.0).sum(
                    axis=0, keepdims=True).reshape(g, D).astype(own_k.dtype)
                s = jax.lax.dot_general(qr, own_k[slot].reshape(W * bs, D),
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32) * scale
                ok = cols < n * bs
                s = jnp.where(ok, s, floor)
                m_prev = max_ref[:, row, :].reshape(g, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
                fix = jnp.exp(m_prev - m_new)
                sum_ref[:, row, :] = (sum_ref[:, row, :].reshape(g, 1) * fix
                                      + p.sum(axis=1, keepdims=True)).reshape(g, 1, 1)
                acc_ref[:, row, :] = (acc_ref[:, row, :].reshape(g, D) * fix + jnp.dot(
                    p.astype(own_v.dtype), own_v[slot].reshape(W * bs, D),
                    preferred_element_type=jnp.float32)).reshape(g, 1, D)
                max_ref[:, row, :] = m_new.reshape(g, 1, 1)

            return carry

        jax.lax.fori_loop(0, tq, query, 0)

    def put(n, carry):  # head n's result, into its lanes of the queries' rows
        o_ref[:, pl.ds(pl.multiple_of(n * D, D), D)] = (
            acc_ref[n] / jnp.maximum(sum_ref[n], jnp.finfo(jnp.float32).tiny)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, g, put, 0)


@functools.partial(jax.jit, static_argnames=("scale", "sel", "tq", "interpret"))
def pallas_block_sparse_chunk_attention(q, k_pool, v_pool, row, start, picked,
                                        scale: float, sel, valid, tq: int | None = None,
                                        interpret: bool = False):
    """The chunk kernel. ``q``: (T, H, g, D), a query's heads by K/V head
    (each query's row of the layer's projection); ``k_pool``/``v_pool``: pool
    leaves of a lane width a column (``pool_leaf_shape`` with ``head_dim``
    128); ``row``: (blocks_per_slot,); ``start``: scalar; ``picked``: (H,
    blocks_per_slot, T), 1 where query ``t`` attends the block; ``sel``:
    the selection ``picked`` was made by (``sparse_index.BlockSelection``),
    whose forced blocks make each tile's band (``block_sparse_chunk_plan``);
    ``valid``: the chunk's real queries (the others fetch nothing and mean
    nothing). A grid step is a K/V head and a query tile, its running sums
    in VMEM; the pools stay in HBM and the kernel copies the pages it
    reads. Returns (T, H * g * D), a query's heads side by side as the
    layer's projection reads them, and the chosen pages the queries
    fetched (a query's copies fill its places past its count with block 0,
    weighted 0 and not counted)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, g, D = q.shape
    num_blocks, _, rows, lanes = k_pool.shape
    bs = rows * lanes // D
    G = _SPARSE_CHUNK_STEP
    if tq is None:
        tq = _widest_divisor(T, _SPARSE_QUERY_TILE)
    start = jnp.asarray(start, jnp.int32)
    tiles, words, lists, fetched = block_sparse_chunk_plan(picked, start, valid, sel, tq, bs)
    W = lists.shape[0] // (H * T) - 1  # a record is a count and W blocks
    phys = jnp.pad(jnp.clip(row.astype(jnp.int32), 0, num_blocks - 1), (0, G), mode="edge")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_sparse_chunk_kernel, G=G, W=W, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H, T // tq),
            in_specs=[pl.BlockSpec((tq, g * D), lambda h, i, *_: (i, h)),
                      hbm,
                      hbm, hbm, hbm],
            out_specs=pl.BlockSpec((tq, g * D), lambda h, i, *_: (i, h)),
            scratch_shapes=[pltpu.VMEM((words.shape[1], tq), jnp.int32),
                            pltpu.SMEM((lists.shape[0] // (H * T // tq),), jnp.int32),
                            pltpu.VMEM((2, G, bs, D), k_pool.dtype),
                            pltpu.VMEM((2, G, bs, D), v_pool.dtype),
                            pltpu.VMEM((2, W, bs, D), k_pool.dtype),
                            pltpu.VMEM((2, W, bs, D), v_pool.dtype),
                            pltpu.VMEM((g, tq, D), jnp.float32),
                            pltpu.VMEM((g, tq, 1), jnp.float32),
                            pltpu.VMEM((g, tq, 1), jnp.float32),
                            pltpu.SemaphoreType.DMA((5, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H * g * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="block_sparse_chunk_attention",
    )(phys, start.reshape(1), tiles, q.reshape(T, H * g * D), words, lists, k_pool, v_pool)
    return out, fetched


def block_sparse_chunk_fits(pool_shape, head_dim: int, chunk: int) -> bool:
    """Whether the chunk kernel's tiles lower for this pool layout: a column
    a lane width, whole sublane tiles a block, and a query tile of whole
    lanes."""
    _, _, rows, lanes = pool_shape
    return (lanes == head_dim == _LANES and rows % 16 == 0
            and _widest_divisor(chunk, _SPARSE_QUERY_TILE) % _LANES == 0)


def block_sparse_chunk_attention(q, k_pool, v_pool, row, start, picked,
                                 head_dim: int, scale: float, body: str, sel, valid):
    """One prefill chunk's attention of ONE slot over the blocks each query
    chose. ``q``: (T, heads, group, D); ``row``: (blocks_per_slot,);
    ``start``: the chunk's first column; ``picked``: (heads, blocks_per_slot,
    T), 1 where query ``t`` of K/V head ``h`` attends the block (every live
    one for a query before ``dense_len``), as ``sel`` selects. Query ``i``
    attends columns ``<= start + i`` of its blocks. ``body``
    ``"paged_pallas"`` runs the kernel where its tiles lower; ``valid``: the
    chunk's real queries. Returns (T, heads * group * D) and the chosen
    pages the kernel's queries fetched one by one (none on the XLA body,
    which walks)."""
    with jax.named_scope("block_sparse_attention"):
        if body == "paged_pallas" and block_sparse_chunk_fits(k_pool.shape, head_dim,
                                                              q.shape[0]):
            return pallas_block_sparse_chunk_attention(q, k_pool, v_pool, row, start,
                                                       picked, scale, sel, valid)
        out = _chunk_walk_xla(jnp.moveaxis(q, 0, 2), k_pool, v_pool, row, start, picked,
                              head_dim, scale)
        return (jnp.moveaxis(out, 2, 0).reshape(q.shape[0], -1).astype(q.dtype),
                jnp.zeros((), jnp.int32))


def pallas_min_seq(head_dim: int) -> int:
    """Sequence length above which the Pallas kernels beat the XLA
    blockwise path, as a function of head_dim (VERDICT r4 #7 — the r4
    constant was tuned on head_dim 64 only).

    Measured r5 on one v5e chip, July 2026 (`scripts/attention_bench.py
    --dims 32 64 128`, 40–80 steps, fwd+bwd): at seq 2048 the two paths
    are within run-to-run noise of parity for EVERY measured head_dim (0.74×–
    1.25× across repeated runs); at ≥3072 Pallas wins clearly (1.4×–
    2.3×) and keeps growing (4×–5× at 8192); at ≤1024 XLA wins. The
    crossover therefore sits between 2k and 3k regardless of head_dim
    in [32, 128] — the threshold stays 2048 there (worst case is
    noise-level parity on one marginal shape, and every longer length
    wins). Head dims OUTSIDE the measured range — larger than 128 or
    smaller than 32 — fall back to a conservative 4096 so an unmeasured
    tiling can't silently regress.
    """
    return 2048 if 32 <= head_dim <= 128 else 4096


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(q) -> bool:
    return _on_tpu() and q.shape[2] >= pallas_min_seq(q.shape[3])


def _forward_impl(q, k, v, causal, block_q, block_k):
    if _use_pallas(q):
        from elephas_tpu.ops.attention_pallas import pallas_flash_attention

        return pallas_flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k
        )
    return _blockwise_reference(q, k, v, causal, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    return _forward_impl(q, k, v, causal, block_q, block_k)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    if _use_pallas(q):
        from elephas_tpu.ops.attention_pallas import pallas_flash_attention

        # Save (o, lse) so the backward recomputes attention weights from
        # the streamed tiles — fused Pallas dq/dk/dv, no score matrix.
        o, lse = pallas_flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            return_lse=True,
        )
        return o, (q, k, v, o, lse)
    return _blockwise_reference(q, k, v, causal, block_q, block_k), (q, k, v)


def _flash_bwd(causal, block_q, block_k, residuals, g):
    if len(residuals) == 5:  # TPU: fused Pallas backward kernels
        from elephas_tpu.ops.attention_pallas import pallas_flash_attention_bwd

        q, k, v, o, lse = residuals
        return pallas_flash_attention_bwd(
            q, k, v, o, lse, g, causal=causal, block_q=block_q, block_k=block_k
        )
    # Other backends: backward via the XLA blockwise path (same numerics).
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _blockwise_reference(q_, k_, v_, causal, block_q, block_k),
        q,
        k,
        v,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(
    q, k, v, causal: bool = True,
    block_q: int | None = None, block_k: int | None = None,
):
    """Blockwise attention with flash memory semantics at every length:
    the custom VJP recomputes attention weights in backward (never
    retaining O(seq^2) residuals), with the KERNEL chosen per shape —
    Pallas on TPU for seq >= ``pallas_min_seq(head_dim)`` where its
    fused backward wins (4-5x at 8k), XLA blockwise below, where Pallas
    launch/tiling overhead loses (scripts/attention_bench.py).

    Block sizes default to the measured per-length tiling
    (``attention_pallas.default_blocks``); pass explicitly to override.
    Differentiable. q/k/v: (batch, heads, seq, head_dim).
    """
    if block_q is None or block_k is None:
        from elephas_tpu.ops.attention_pallas import default_blocks

        dq, dk = default_blocks(q.shape[2])
        block_q = block_q if block_q is not None else dq
        block_k = block_k if block_k is not None else dk
    return _flash(q, k, v, causal, block_q, block_k)
