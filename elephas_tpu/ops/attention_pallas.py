"""Pallas TPU flash-attention forward kernel.

Blockwise causal softmax attention with *streamed* K/V: the grid is
(batch*heads, q_tiles, k_tiles) and Pallas pipelines one (block_k,
head_dim) K/V tile at a time through VMEM, so VMEM holds only the current
tiles + the (block_q, head_dim) accumulator regardless of sequence length
— the long-context regime (100k+ tokens) compiles and runs where a
whole-sequence-resident layout would VMEM-OOM. Running row-max/row-sum
live in VMEM scratch, which persists across the innermost (k) grid steps
of a given q tile. Matmuls hit the MXU with f32 accumulation; causal
tiles above the diagonal are skipped via ``pl.when`` (no FLOPs).

Backward pass: fused Pallas kernels (``pallas_flash_attention_bwd``) that
recompute attention weights from the saved (q, k, lse) residuals in the
same streamed-tile structure — dq accumulates over k tiles, dk/dv over q
tiles. The public ``flash_attention`` wrapper (ops/attention.py) wires
forward+backward into a ``jax.custom_vjp``; non-TPU backends fall back to
an XLA blockwise VJP.

Follows /opt/skills/guides/pallas_guide.md (grid/BlockSpec pipelining,
scratch accumulators, 2-D iota, preferred_element_type on MXU matmuls).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    block_q,
    block_k,
    seq_len,
    causal,
    sm_scale,
):
    """Program (b, qi, kj): fold K/V tile kj into q tile qi's accumulator."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: tile kj contributes iff its first key pos <= q tile's last pos.
    needed = jnp.logical_or(
        not causal, kj * block_k <= (qi + 1) * block_q - 1
    )

    @pl.when(needed)
    def _fold():
        q = q_ref[0, ...].astype(jnp.float32) * sm_scale  # (block_q, d)
        k_tile = k_ref[0, ...].astype(jnp.float32)  # (block_k, d)
        v_tile = v_ref[0, ...].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q,
            k_tile,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)

        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_pos < seq_len
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - shift)
        correction = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - shift), 0.0)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p,
            v_tile,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(kj == num_k - 1)
    def _finalize():
        o_ref[0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)
        # Row log-sum-exp (the backward residual). Fully-masked (padded)
        # rows get a large-negative finite value, so exp(-inf - lse) == 0
        # in the backward kernels instead of NaN.
        m = m_ref[...]
        shift = jnp.where(jnp.isfinite(m), m, 0.0)
        lse = (shift + jnp.log(jnp.maximum(l_ref[...], 1e-30)))[:, 0]
        lse_ref[0, ...] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def default_blocks(seq_len: int):
    """Measured block tiling on v5-lite (r4 sweep, fwd+bwd causal,
    bh=8, d=64): wide 1024-row q tiles beat 512 by ~1.3x at 2k-4k
    (fewer grid steps amortize the per-tile scratch init/finalize), and
    lose slightly at 8k+ where VMEM pressure bites; 512-wide k tiles
    win everywhere. scripts/attention_bench.py reproduces the table."""
    return (1024 if seq_len <= 4096 else 512), 512


def _sanitize_blocks(seq_len: int, block_q: int, block_k: int):
    """Clamp to the sequence, and keep multi-block tile sizes on the
    TPU-mappable grid (multiples of 128 on the minor-most score dim)."""
    block_q = min(block_q, max(seq_len, 8))
    block_k = min(block_k, max(seq_len, 8))
    if block_q < seq_len:
        block_q = max(128, (block_q // 128) * 128)
    if block_k < seq_len:
        block_k = max(128, (block_k // 128) * 128)
    return block_q, block_k


def _pad_reshape(q, k, v, block_q, block_k):
    batch, heads, seq_len, head_dim = q.shape
    pad_q = (-seq_len) % block_q
    pad_k = (-seq_len) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    bh = batch * heads
    qp = qp.reshape(bh, qp.shape[2], head_dim)
    kp = kp.reshape(bh, kp.shape[2], head_dim)
    vp = vp.reshape(bh, vp.shape[2], head_dim)
    return qp, kp, vp


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "return_lse")
)
def pallas_flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    return_lse: bool = False,
):
    """q, k, v: (batch, heads, seq, head_dim) -> same-shaped output.

    ``return_lse=True`` additionally returns the per-row log-sum-exp
    ``(batch, heads, seq)`` — the residual the Pallas backward needs.
    """
    batch, heads, seq_len, head_dim = q.shape
    sm_scale = 1.0 / (head_dim**0.5)

    block_q, block_k = _sanitize_blocks(seq_len, block_q, block_k)
    qp, kp, vp = _pad_reshape(q, k, v, block_q, block_k)
    bh = batch * heads
    num_q = qp.shape[1] // block_q
    num_k = kp.shape[1] // block_k

    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_len=seq_len,
        causal=causal,
        sm_scale=sm_scale,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, head_dim),
                lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, head_dim),
                lambda b, i, j: (b, j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, head_dim),
                lambda b, i, j: (b, j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, head_dim),
                lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 8, block_q),
                lambda b, i, j: (b, 0, i),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, qp.shape[1], head_dim), q.dtype),
            # lse replicated across 8 sublanes: TPU block tiling wants the
            # second-minor block dim divisible by 8, so a plain (1, block_q)
            # row block is unmappable; the 8x copy is negligible (f32 rows).
            jax.ShapeDtypeStruct((bh, 8, qp.shape[1]), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running sum
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(4 * bh * seq_len * seq_len * head_dim * (0.5 if causal else 1.0)),
            bytes_accessed=int(3 * bh * seq_len * head_dim * q.dtype.itemsize),
            transcendentals=int(bh * seq_len * seq_len),
        ),
    )(qp, kp, vp)
    out = out.reshape(batch, heads, -1, head_dim)[:, :, :seq_len]
    if return_lse:
        return out, lse[:, 0, :].reshape(batch, heads, -1)[:, :, :seq_len]
    return out


# --------------------------------------------------------------- backward


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, block_q, block_k, seq_len, causal, sm_scale,
):
    """Program (b, qi, kj): fold K/V tile kj into q tile qi's dq.

    dq_i = sm_scale * sum_j p_ij (dO_i.V_j - D_i) k_j, with
    p_ij = exp(sm_scale q_i.k_j - lse_i) and D = rowsum(dO * O)
    (precomputed, streamed in as `delta`). Same streamed-K/V structure as
    the forward: VMEM holds one K/V tile + the (block_q, d) accumulator.
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    needed = jnp.logical_or(not causal, kj * block_k <= (qi + 1) * block_q - 1)

    @pl.when(needed)
    def _fold():
        q = q_ref[0, ...].astype(jnp.float32)
        k_tile = k_ref[0, ...].astype(jnp.float32)
        v_tile = v_ref[0, ...].astype(jnp.float32)
        do = do_ref[0, ...].astype(jnp.float32)
        lse = lse_ref[0, 0, :].astype(jnp.float32)  # (block_q,)
        delta = delta_ref[0, 0, :].astype(jnp.float32)  # (block_q,)

        scores = sm_scale * jax.lax.dot_general(
            q, k_tile, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_pos < seq_len
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        p = jnp.where(valid, jnp.exp(scores - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v_tile, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k_tile, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0, ...] = (sm_scale * dq_acc_ref[...]).astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, block_q, block_k, seq_len, causal, sm_scale,
):
    """Program (b, kj, qi): fold Q/dO tile qi into k tile kj's dk/dv.

    dv_j = sum_i p_ij dO_i ; dk_j = sm_scale * sum_i p_ij (dO_i.V_j - D_i) q_i.
    Streams Q/dO tiles through VMEM with (block_k, d) accumulators.
    """
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # Causal: q tile qi contributes to k tile kj iff its last q pos >= first k pos.
    needed = jnp.logical_or(not causal, (qi + 1) * block_q - 1 >= kj * block_k)

    @pl.when(needed)
    def _fold():
        q = q_ref[0, ...].astype(jnp.float32)
        k_tile = k_ref[0, ...].astype(jnp.float32)
        v_tile = v_ref[0, ...].astype(jnp.float32)
        do = do_ref[0, ...].astype(jnp.float32)
        lse = lse_ref[0, 0, :].astype(jnp.float32)  # (block_q,)
        delta = delta_ref[0, 0, :].astype(jnp.float32)

        # (block_k, block_q): transposed scores, k-major for the accumulators.
        scores_t = sm_scale * jax.lax.dot_general(
            k_tile, q, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0
        )
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1
        )
        valid = jnp.logical_and(k_pos < seq_len, q_pos < seq_len)
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        p_t = jnp.where(valid, jnp.exp(scores_t - lse[None, :]), 0.0)
        dv_acc_ref[...] += jax.lax.dot_general(
            p_t, do, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v_tile, do, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, block_q)
        ds_t = p_t * (dp_t - delta[None, :])
        dk_acc_ref[...] += jax.lax.dot_general(
            ds_t, q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0, ...] = (sm_scale * dk_acc_ref[...]).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc_ref[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def pallas_flash_attention_bwd(
    q, k, v, o, lse, do, causal: bool = True, block_q: int = 512, block_k: int = 512
):
    """Fused dq/dk/dv with forward recompute of the attention weights from
    (q, k, lse) — the score matrix never materializes in HBM, matching the
    forward's streamed-tile memory profile. Two kernels: dq accumulates
    over k tiles; dk/dv accumulate over q tiles.
    """
    batch, heads, seq_len, head_dim = q.shape
    sm_scale = 1.0 / (head_dim**0.5)
    block_q, block_k = _sanitize_blocks(seq_len, block_q, block_k)

    # D = rowsum(dO * O): tiny elementwise reduction; XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp, kp, vp = _pad_reshape(q, k, v, block_q, block_k)
    dop, _, _ = _pad_reshape(do, k, v, block_q, block_k)
    bh = batch * heads
    padded_q = qp.shape[1]
    pad_rows = padded_q - seq_len
    # 8-sublane replication: see the forward lse out_shape note.
    lsep = jnp.broadcast_to(
        jnp.pad(
            lse.reshape(bh, seq_len).astype(jnp.float32), ((0, 0), (0, pad_rows))
        )[:, None, :],
        (bh, 8, padded_q),
    )
    deltap = jnp.broadcast_to(
        jnp.pad(
            delta.reshape(bh, seq_len).astype(jnp.float32), ((0, 0), (0, pad_rows))
        )[:, None, :],
        (bh, 8, padded_q),
    )
    num_q = padded_q // block_q
    num_k = kp.shape[1] // block_k

    q_spec = pl.BlockSpec(
        (1, block_q, head_dim), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM
    )
    row_spec = pl.BlockSpec(
        (1, 8, block_q), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM
    )
    k_spec = pl.BlockSpec(
        (1, block_k, head_dim), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM
    )

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel,
            block_q=block_q, block_k=block_k, seq_len=seq_len,
            causal=causal, sm_scale=sm_scale,
        ),
        grid=(bh, num_q, num_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, padded_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(6 * bh * seq_len * seq_len * head_dim * (0.5 if causal else 1.0)),
            bytes_accessed=int(5 * bh * seq_len * head_dim * q.dtype.itemsize),
            transcendentals=int(bh * seq_len * seq_len),
        ),
    )(qp, kp, vp, dop, lsep, deltap)

    # dk/dv: swap the streaming axes — k tiles outer, q tiles inner.
    kq_q_spec = pl.BlockSpec(
        (1, block_q, head_dim), lambda b, j, i: (b, i, 0), memory_space=pltpu.VMEM
    )
    kq_row_spec = pl.BlockSpec(
        (1, 8, block_q), lambda b, j, i: (b, 0, i), memory_space=pltpu.VMEM
    )
    kq_k_spec = pl.BlockSpec(
        (1, block_k, head_dim), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel,
            block_q=block_q, block_k=block_k, seq_len=seq_len,
            causal=causal, sm_scale=sm_scale,
        ),
        grid=(bh, num_k, num_q),
        in_specs=[kq_q_spec, kq_k_spec, kq_k_spec, kq_q_spec, kq_row_spec, kq_row_spec],
        out_specs=[kq_k_spec, kq_k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kp.shape[1], head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh, vp.shape[1], head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(8 * bh * seq_len * seq_len * head_dim * (0.5 if causal else 1.0)),
            bytes_accessed=int(5 * bh * seq_len * head_dim * q.dtype.itemsize),
            transcendentals=int(bh * seq_len * seq_len),
        ),
    )(qp, kp, vp, dop, lsep, deltap)

    dq = dq.reshape(batch, heads, -1, head_dim)[:, :, :seq_len]
    dk = dk.reshape(batch, heads, -1, head_dim)[:, :, :seq_len]
    dv = dv.reshape(batch, heads, -1, head_dim)[:, :, :seq_len]
    return dq, dk, dv


# ------------------------------------------------- paged decode attention

# Finite, so a row with no live column (a padded head row) stays finite.
_MASKED = -1e30


def _paged_decode_kernel(
    lane_ref, first_ref, table_ref, idx_ref, steps_ref,  # scalar prefetch (SMEM)
    q_ref,  # (1, pack * rows, lanes)
    kn_ref, vn_ref,  # (1, heads, 1, lanes): the new column, in every lane group
    k_hbm, v_hbm,  # the pools, left where they are
    o_ref, ko_hbm, vo_hbm,  # the lane's output; the pools again (aliased)
    k_buf, v_buf,  # (2, blocks, heads, r, lanes): a step's blocks, twice
    sem, tail_sem,  # DMAs in flight: (pool, buffer, block); (pool,)
    pos_ref,  # (pack * rows, blocks * heads * r): each score's column
    m_ref, l_ref, acc_ref,  # running max, sum, weighted values of one lane
    *, heads: int, rows: int, pack: int, head_dim: int, blocks: int,
    blocks_per_slot: int, sm_scale: float, q_per_kv: int = 1,
):
    """Grid step ``w``: ``blocks`` consecutive blocks of one lane, from its
    block ``first_ref[w]`` on, folded into the lane's running softmax; the
    step that holds the lane's last block, its tail, puts the step's new
    K/V column into that block before it is scored and sends the block
    back to the pool.

    The kernel moves the blocks itself: while step ``w`` is scored, the
    live blocks of step ``w + 1`` are on their way into the other half of
    ``k_buf`` / ``v_buf``. A block of a lane's last step that holds no live
    column is not moved; what its place in the buffer holds (zeros, or a
    block of an earlier step) is scored dead.

    A block is ``heads * r`` rows of ``pack`` columns each. Query row
    ``g * rows + h`` holds head ``h``'s query in lane group ``g`` and
    zeros elsewhere, so one ``(pack * rows, lanes) x (blocks * heads * r,
    lanes)^T`` matmul scores every column of the step: entry
    ``[(g, h), (b, h', i)]`` is column ``(b * r + i) * pack + g`` of head
    ``h'`` among the step's columns, and the entries with ``h' != h`` are
    masked away (with grouped heads, ``q_per_kv`` query heads to a K/V
    head, ``h' != h // q_per_kv``: the pool holds the K/V heads the model
    has and none is replicated). ``pos_ref`` holds that column for the
    entries a row owns and a number past every column for the rest; it is
    built once a call, and a step's mask is one comparison with the new
    column's place. The MXU takes one call a step where one-row calls,
    head by head, would starve it. Each ``g`` keeps its own running
    softmax over its columns; they merge at the tail, and lane group ``g``
    of row ``(g, h)`` of the output then holds its share of head ``h``'s
    result."""
    w = pl.program_id(0)
    r, lanes = k_buf.shape[3:]
    width = heads * r
    block_size = r * pack

    def last_block(lane):
        return jnp.minimum(idx_ref[lane] // block_size, blocks_per_slot - 1)

    def each_live_block(step, buf, act):
        """``act`` on the two copies of each block of step ``step`` that
        holds a live column, into half ``buf`` of the buffers. A loop, not
        ``blocks`` copies of its body: a step of many small blocks would
        take seconds to trace and lower."""
        lane, first = lane_ref[step], first_ref[step]

        def body(b, carry):
            phys = table_ref[lane * blocks_per_slot + first + b]
            act(pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[buf, b],
                                      sem.at[0, buf, b]))
            act(pltpu.make_async_copy(v_hbm.at[phys], v_buf.at[buf, b],
                                      sem.at[1, buf, b]))
            return carry

        live = jnp.minimum(blocks, last_block(lane) - first + 1)
        jax.lax.fori_loop(0, live, body, 0)

    def fetch(step, buf):
        each_live_block(step, buf, lambda copy: copy.start())

    buf = jax.lax.rem(w, 2)

    @pl.when(w == 0)
    def _first():
        # a place no copy has filled is scored dead: it has to be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, blocks * width), 1)
        block = sum((col >= b * width).astype(jnp.int32)
                    for b in range(1, blocks))
        row = jax.lax.broadcasted_iota(jnp.int32, (pack * rows, 1), 0)
        group = sum((row >= g * rows).astype(jnp.int32)
                    for g in range(1, pack))
        q_head = row - group * rows
        kv_head = q_head if q_per_kv == 1 else sum(
            (q_head >= k * q_per_kv).astype(jnp.int32)
            for k in range(1, heads))
        # row of the block in this query's K/V head
        own = col - block * width - kv_head * r
        pos_ref[...] = jnp.broadcast_to(jnp.where(
            (own >= 0) & (own < r),
            block * block_size + own * pack + group,
            jnp.iinfo(jnp.int32).max,
        ), pos_ref.shape)
        fetch(0, 0)

    @pl.when(w + 1 < steps_ref[0])
    def _ahead():
        fetch(w + 1, 1 - buf)

    each_live_block(w, buf, lambda copy: copy.wait())

    lane, first = lane_ref[w], first_ref[w]
    idx = idx_ref[lane]
    last = last_block(lane)
    is_tail = first + blocks > last  # the step holds the lane's last block

    @pl.when(first == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tail_copies():
        phys = table_ref[lane * blocks_per_slot + last]
        return [pltpu.make_async_copy(buffer.at[buf, last - first],
                                      pool.at[phys], tail_sem.at[n])
                for n, (buffer, pool) in enumerate(((k_buf, ko_hbm),
                                                    (v_buf, vo_hbm)))]

    @pl.when(is_tail)
    def _write():
        # the new column's place in the tail block: row at // pack, group
        # at % pack
        at = idx - last * block_size
        shape = k_buf.shape[2:]
        in_row = jax.lax.broadcasted_iota(jnp.int32, shape, 1) == at // pack
        lanes_at = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        first_lane = (at % pack) * head_dim
        here = in_row & (lanes_at >= first_lane) & (
            lanes_at < first_lane + head_dim)
        b = last - first
        k_buf[buf, b] = jnp.where(here, kn_ref[0], k_buf[buf, b])
        v_buf[buf, b] = jnp.where(here, vn_ref[0], v_buf[buf, b])
        for copy in tail_copies():
            copy.start()

    k = k_buf[buf].reshape(blocks * width, lanes)
    v = v_buf[buf].reshape(blocks * width, lanes)
    s = jax.lax.dot_general(
        q_ref[0], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    # the running max starts finite, so a dead entry's weight is
    # exp(-inf) = 0 also in a row that has met no live column yet (a
    # padded head row never does: its row is not read)
    s = jnp.where(pos_ref[...] <= idx - first * block_size, s, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(is_tail)
    def _finalize():
        groups = [slice(g * rows, (g + 1) * rows) for g in range(pack)]
        m_all = functools.reduce(jnp.maximum, [m_ref[g] for g in groups])
        share = [jnp.exp(m_ref[g] - m_all) for g in groups]
        # a group with no live column has l == 0 and share == 0
        total = sum(l_ref[g] * w_ for g, w_ in zip(groups, share))
        inv = 1.0 / jnp.maximum(total, 1e-30)
        for g, w_ in zip(groups, share):
            o_ref[0, g] = (acc_ref[g] * (w_ * inv)).astype(o_ref.dtype)
        # the tail block left while the step was scored; the next step's
        # copies may land in this half of the buffers only after it
        for copy in tail_copies():
            copy.wait()


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def pallas_paged_decode_attention(q, k_new, v_new, k_pool, v_pool, table,
                                  idx, active, blocks: int | None = None,
                                  interpret: bool = False):
    """One decode step of one layer on the paged pool, in place: each
    active lane's new K/V column goes into its tail block and its query
    attends over its blocks, columns ``<= idx``.

    ``q``: (slots, q_heads, head_dim); ``k_new``/``v_new``: (slots, heads,
    head_dim), ``q_heads`` a multiple of ``heads`` (grouped heads: query
    head ``h`` reads K/V head ``h // (q_heads // heads)``); ``k_pool``/
    ``v_pool``: pool leaves (num_blocks, heads, r, pack * head_dim),
    ``pack`` columns to a row (``ops/attention.py``), aliased to the
    returned pools; ``table``: (slots, blocks_per_slot) int32; ``idx``:
    (slots,) the column each lane writes; ``active``: (slots,) bool.
    Returns ``(out, k_pool, v_pool)``, ``out`` (slots, q_heads, head_dim)
    and zeros for an inactive lane. ``blocks``: how many of a lane's
    blocks a grid step folds; left out, ``ops.attention.
    paged_decode_blocks`` derives it from the layout (tests pass it).

    The grid runs over the active lanes' steps only, lane by lane, a step
    ``blocks`` consecutive blocks of the lane's row: their count is a
    run-time value (a dynamic grid bound) and the kernel reads each step's
    lane and first block, and the blocks' physical ids, from scalar-
    prefetch arguments, so a block that holds no live column of its lane
    is never moved and one compiled kernel serves every length. The pools
    stay in HBM: the kernel copies a step's live blocks in while it scores
    the step before, and of the pools only each active lane's tail block
    is written back.
    """
    from elephas_tpu.ops.attention import paged_decode_blocks

    slots, q_heads, head_dim = q.shape
    num_blocks, heads, r, lanes = k_pool.shape
    pack = lanes // head_dim
    block_size = r * pack
    blocks_per_slot = table.shape[1]
    if blocks is None:
        blocks = paged_decode_blocks(k_pool.shape, k_pool.dtype, head_dim,
                                     q_heads, blocks_per_slot)
    rows = -(-q_heads // 16) * 16  # whole sublane tiles for bf16 and f32
    dtype = k_pool.dtype
    # query row (g, h): head h's query in lane group g, zeros elsewhere
    qp = jnp.zeros((slots, pack, rows, pack, head_dim), dtype)
    for g in range(pack):
        qp = qp.at[:, g, :q_heads, g].set(q.astype(dtype))
    qp = qp.reshape(slots, pack * rows, lanes)
    new = [jnp.tile(x.astype(dtype), (1, 1, pack))[:, :, None, :]
           for x in (k_new, v_new)]

    idx = idx.astype(jnp.int32)
    table = jnp.clip(table.astype(jnp.int32), 0, num_blocks - 1)
    last = jnp.clip(idx // block_size, 0, blocks_per_slot - 1)
    most = -(-blocks_per_slot // blocks)  # steps that cover a whole row
    live = active[:, None] & (
        jnp.arange(most)[None] * blocks <= last[:, None])
    (work,) = jnp.nonzero(live.reshape(-1), size=live.size, fill_value=0)
    work = work.astype(jnp.int32)
    lane, first = work // most, work % most * blocks
    steps = live.sum().astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel, heads=heads, rows=rows, pack=pack,
        head_dim=head_dim, blocks=blocks, blocks_per_slot=blocks_per_slot,
        sm_scale=1.0 / (head_dim ** 0.5), q_per_kv=q_heads // heads,
    )
    lane_rows = pl.BlockSpec(
        (1, pack * rows, lanes), lambda w, lane, *_: (lane[w], 0, 0)
    )
    lane_column = pl.BlockSpec(
        (1, heads, 1, lanes), lambda w, lane, *_: (lane[w], 0, 0, 0)
    )
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=[lane_rows, lane_column, lane_column, pool, pool],
            out_specs=[lane_rows, pool, pool],
            scratch_shapes=[
                pltpu.VMEM((2, blocks, heads, r, lanes), dtype),
                pltpu.VMEM((2, blocks, heads, r, lanes), dtype),
                pltpu.SemaphoreType.DMA((2, 2, blocks)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((pack * rows, blocks * heads * r), jnp.int32),
                pltpu.VMEM((pack * rows, 1), jnp.float32),
                pltpu.VMEM((pack * rows, 1), jnp.float32),
                pltpu.VMEM((pack * rows, lanes), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, pack * rows, lanes), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operands count the scalar-prefetch arguments: the pools are 8, 9
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_attention",
    )(lane, first, table.reshape(-1), idx, steps.reshape(1), qp, *new,
      k_pool, v_pool)
    # head h: the sum over g of lane group g of row (g, h)
    out = out.reshape(slots, pack, rows, pack, head_dim)
    out = sum(out[:, g, :q_heads, g] for g in range(pack))
    # a lane the grid never visited holds whatever its output rows held
    return jnp.where(active[:, None, None], out, 0), k_pool, v_pool


# -------------------------------------------------- paged chunk attention


def _paged_chunk_kernel(
    phys_ref, steps_ref,  # scalar prefetch (SMEM)
    q_ref,  # (head_group, tile, lanes): a query in every lane group
    bound_ref,  # (pack * tile, 1): the last live column of each query row
    *refs,  # K blocks, V blocks, (head_group, r, lanes) each; out; scratch
    blocks: int, pack: int, head_dim: int, sm_scale: float,
):
    """Grid step (head group, query tile, j): fold ``blocks`` blocks of the
    slot's row, from block ``j * blocks`` on, into the running softmax of
    one tile of queries, for each K/V head of the group.

    A block is ``r`` rows of ``pack`` columns a head. Query row ``(g, i)``
    of a tile keeps query ``i`` in lane group ``g`` and zeros elsewhere
    (``_paged_decode_kernel``'s layout), so ``(pack * tile, lanes) x
    (blocks * r, lanes)^T`` scores query ``i`` against column ``pack * n +
    g`` of the step's columns in entry ``[(g, i), n]``: ``bound`` holds
    ``start + i - g`` and the entry is live where ``pack * n`` plus the
    step's first column is at most that. Each ``g`` keeps its own running
    softmax; they merge at the last step, where lane group ``g`` of row
    ``(g, i)`` holds its share of query ``i``'s result and is rolled to
    lane group 0."""
    del phys_ref  # read by the index maps only
    k_refs, v_refs = refs[:blocks], refs[blocks:2 * blocks]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * blocks:]
    j = pl.program_id(2)
    head_group, r, lanes = k_refs[0].shape
    tile = q_ref.shape[1]
    query_rows = pack * tile

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first = jax.lax.broadcasted_iota(jnp.int32, (query_rows, blocks * r), 1)
    live = first * pack <= bound_ref[...] - j * (blocks * r * pack)
    lane_group = jax.lax.broadcasted_iota(jnp.int32, (tile, lanes), 1) // head_dim
    for h in range(head_group):
        k = jnp.concatenate([ref[h] for ref in k_refs], axis=0)
        v = jnp.concatenate([ref[h] for ref in v_refs], axis=0)
        q = q_ref[h]
        q = jnp.concatenate([jnp.where(lane_group == g, q, jnp.zeros_like(q))
                             for g in range(pack)], axis=0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        # the running max starts finite, so a dead entry's weight is
        # exp(-inf) = 0 also in a row that has met no live column yet
        s = jnp.where(live, s, _NEG_INF)
        m = m_ref[h]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new

    @pl.when(j == steps_ref[0] - 1)
    def _finalize():
        groups = [slice(g * tile, (g + 1) * tile) for g in range(pack)]
        for h in range(head_group):
            m, l, acc = m_ref[h], l_ref[h], acc_ref[h]
            m_all = functools.reduce(jnp.maximum, [m[g] for g in groups])
            share = [jnp.exp(m[g] - m_all) for g in groups]
            total = sum(l[g] * w for g, w in zip(groups, share))
            inv = 1.0 / jnp.maximum(total, 1e-30)
            out = acc[groups[0]] * (share[0] * inv)
            for n, (g, w) in enumerate(zip(groups[1:], share[1:]), 1):
                out = out + pltpu.roll(acc[g] * (w * inv),
                                       lanes - n * head_dim, 1)
            o_ref[h] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_paged_chunk_attention(q, k_pool, v_pool, row, start,
                                 interpret: bool = False):
    """One prefill chunk of one layer of ONE slot against the paged pool,
    which already holds the chunk's own columns: query ``i`` attends
    columns ``<= start + i`` of the slot's blocks.

    ``q``: (q_heads, C, head_dim); ``k_pool``/``v_pool``: pool leaves
    (num_blocks, heads, r, pack * head_dim), read only, ``q_heads`` a
    multiple of ``heads``; ``row``: (blocks_per_slot,) int32; ``start``:
    scalar. Returns (q_heads, C, head_dim).

    The grid is (head groups, query tiles, steps over the row's blocks):
    the steps a call takes are a run-time value (a dynamic grid bound),
    ``(start + C) / (blocks a step * block_size)`` rounded up, and the
    blocks' physical ids are a scalar-prefetch argument that the index
    maps read. So a block past the chunk's last column is never moved, one
    compiled kernel serves every ``start``, and no score leaves VMEM.
    """
    from elephas_tpu.ops.attention import _chunk_tiles

    q_heads, chunk, head_dim = q.shape
    num_blocks, heads, r, lanes = k_pool.shape
    pack = lanes // head_dim
    block_size = r * pack
    per_kv = q_heads // heads
    head_group, tile, blocks = _chunk_tiles(heads, block_size, chunk)
    tiles = chunk // tile
    dtype = k_pool.dtype

    # every query in every lane group of its row: the kernel keeps one
    qp = jnp.tile(q.astype(dtype).reshape(heads, per_kv * chunk, head_dim),
                  (1, 1, pack))
    start = start.astype(jnp.int32)
    bound = (start + jnp.arange(chunk, dtype=jnp.int32).reshape(tiles, 1, tile)
             - jnp.arange(pack, dtype=jnp.int32)[:, None])
    bound = bound.reshape(tiles * pack * tile, 1)

    most = -(-row.shape[0] // blocks)  # steps that cover the whole row
    phys = jnp.clip(row.astype(jnp.int32), 0, num_blocks - 1)
    phys = jnp.pad(phys, (0, most * blocks - row.shape[0]), mode="edge")
    steps = jnp.clip((start + chunk - 1) // (blocks * block_size) + 1, 1, most)

    kernel = functools.partial(
        _paged_chunk_kernel, blocks=blocks, pack=pack, head_dim=head_dim,
        sm_scale=1.0 / (head_dim ** 0.5),
    )
    queries = pl.BlockSpec((head_group, tile, lanes),
                           lambda hg, t, j, *_: (hg, t, 0))
    bounds = pl.BlockSpec((pack * tile, 1), lambda hg, t, j, *_: (t % tiles, 0))
    live_blocks = [
        pl.BlockSpec(
            (None, head_group, r, lanes),
            lambda hg, t, j, phys, steps, b=b: (phys[j * blocks + b], hg, 0, 0),
        )
        for b in range(blocks)
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads // head_group, per_kv * tiles, steps),
            in_specs=[queries, bounds, *live_blocks, *live_blocks],
            out_specs=pl.BlockSpec((head_group, tile, lanes),
                                   lambda hg, t, j, *_: (hg, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((head_group, pack * tile, 1), jnp.float32),
                pltpu.VMEM((head_group, pack * tile, 1), jnp.float32),
                pltpu.VMEM((head_group, pack * tile, lanes), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, per_kv * chunk, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_chunk_attention",
    )(phys, steps.reshape(1), qp, bound, *([k_pool] * blocks),
      *([v_pool] * blocks))
    # lane group 0 of row (kv head, query head of its group, i)
    return out[:, :, :head_dim].reshape(q_heads, chunk, head_dim)


# ------------------------------------------------ latent paged attention
#
# A latent pool is ONE leaf of one head, ``(num_blocks, 1, width,
# block_size)``, a block's columns minor (``ops.attention.
# latent_leaf_shape``): a column is a key of ``width`` values whose first
# ``value_width`` are also its value. All of a layer's query heads read it
# (the absorbed form of latent attention), so a block is moved once and
# scored by every head, for key and value alike.

# VMEM the latent chunk kernel may take: all of a chunk's queries stay
# resident for a group of heads (at 2,048 queries and 4 heads 12 MB of
# queries and output, buffered twice, and 12 MB of accumulator, maximum
# and sum) beside a tile's scores and weights, past Mosaic's default
# scoped limit and well inside a v5e's 128 MiB.
_LATENT_CHUNK_VMEM = 64 << 20


def _latent_fold(q, blocks, live, value_width, sm_scale, m_ref, l_ref, acc_ref):
    """Fold a step's ``blocks`` (each (width, block_size), columns minor)
    into the running softmax of the rows of ``q`` (rows, width): the scores
    of every block side by side, dead where ``live(column index)`` is false
    (every column live where it is None), one softmax update, then each
    block's weights against its own first ``value_width`` rows."""
    s = jnp.concatenate(
        [jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         for k in blocks], axis=1) * sm_scale
    if live is not None:
        s = jnp.where(live(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)),
                      s, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    block_size = blocks[0].shape[1]
    acc = alpha * acc_ref[...]
    for b, k in enumerate(blocks):
        acc = acc + jax.lax.dot_general(
            p[:, b * block_size:(b + 1) * block_size].astype(k.dtype),
            k[:value_width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc
    m_ref[...] = m_new


def _latent_decode_kernel(
    lane_ref, first_ref, table_ref, idx_ref, steps_ref,  # scalar prefetch
    q_ref,  # (1, heads, width): every head's query of one lane
    new_ref,  # (1, width, 1): the lane's new column
    *refs,  # [the step's selection, (1, 1, columns)]; the pool; outputs; scratch
    blocks: int, blocks_per_slot: int, value_width: int, sm_scale: float,
    window=None, sparse: bool = False,
):
    """``_paged_decode_kernel`` for a latent pool: grid step ``w`` folds
    ``blocks`` consecutive blocks of one lane, from its block
    ``first_ref[w]`` on, into the running softmax of all of the lane's
    heads at once; the step that holds the lane's last block, its tail,
    puts the new column into it before it is scored and sends it back to
    the pool. The next step's live blocks are on their way while this one
    is scored; a block with no live column is not moved, and what its place
    in the buffer holds is scored dead. Under a ``window`` a column is live
    only within ``window`` of the lane's own (the token itself counted);
    ``sparse``, only where the step's selection reads 1."""
    sel_ref = refs[0] if sparse else None
    # k_hbm: the pool, left where it is; o_ref: (1, heads, value_width);
    # ko_hbm: the pool again (aliased); k_buf: (2, blocks, width, block_size),
    # a step's blocks, twice; sem, tail_sem: DMAs in flight, (buffer, block)
    # and the tail's
    (k_hbm, o_ref, ko_hbm, k_buf, sem, tail_sem,
     m_ref, l_ref, acc_ref) = refs[1 if sparse else 0:]
    w = pl.program_id(0)
    block_size = k_buf.shape[3]

    def last_block(lane):
        return jnp.minimum(idx_ref[lane] // block_size, blocks_per_slot - 1)

    def each_live_block(step, buf, act):
        lane, first = lane_ref[step], first_ref[step]

        def body(b, carry):
            phys = table_ref[lane * blocks_per_slot + first + b]
            act(pltpu.make_async_copy(k_hbm.at[phys, 0], k_buf.at[buf, b],
                                      sem.at[buf, b]))
            return carry

        live = jnp.minimum(blocks, last_block(lane) - first + 1)
        jax.lax.fori_loop(0, live, body, 0)

    buf = jax.lax.rem(w, 2)

    @pl.when(w == 0)
    def _first():
        # a place no copy has filled is scored dead: it has to be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        each_live_block(0, 0, lambda copy: copy.start())

    @pl.when(w + 1 < steps_ref[0])
    def _ahead():
        each_live_block(w + 1, 1 - buf, lambda copy: copy.start())

    each_live_block(w, buf, lambda copy: copy.wait())

    lane, first = lane_ref[w], first_ref[w]
    idx = idx_ref[lane]
    last = last_block(lane)
    is_tail = first + blocks > last

    @pl.when(first == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tail_copy():
        phys = table_ref[lane * blocks_per_slot + last]
        return pltpu.make_async_copy(k_buf.at[buf, last - first],
                                     ko_hbm.at[phys, 0], tail_sem.at[0])

    @pl.when(is_tail)
    def _write():
        b = last - first
        here = jax.lax.broadcasted_iota(
            jnp.int32, k_buf.shape[2:], 1) == idx - last * block_size
        k_buf[buf, b] = jnp.where(here, new_ref[0], k_buf[buf, b])
        tail_copy().start()

    def live(col):
        seen = col <= idx - first * block_size
        if window is not None:
            seen &= col > idx - first * block_size - window
        if sparse:
            seen &= sel_ref[0] > 0
        return seen

    _latent_fold(q_ref[0], [k_buf[buf, b] for b in range(blocks)], live,
                 value_width, sm_scale, m_ref, l_ref, acc_ref)

    @pl.when(is_tail)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        # the next step's copies may land in this half only after it left
        tail_copy().wait()


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "blocks",
                                             "window", "interpret"))
def pallas_latent_decode_attention(q, new, pool, table, idx, active,
                                   value_width: int, scale=None,
                                   blocks: int | None = None,
                                   interpret: bool = False, selected=None,
                                   window: int | None = None):
    """One decode step of one layer on a latent paged pool, in place.

    ``q``: (slots, q_heads, width); ``new``: (slots, 1, width), the step's
    column a lane; ``pool``: (num_blocks, 1, width, block_size), aliased to
    the returned pool; ``table``, ``idx``, ``active`` as
    ``pallas_paged_decode_attention`` has them. Returns ``(out, pool)``,
    ``out`` (slots, q_heads, value_width) and zeros for an inactive lane.
    The grid runs over the active lanes' steps only, a run-time count; the
    pool stays in HBM and of it only each active lane's tail block is
    written back. ``selected``: (slots, S) of 0 and 1, the columns a lane's
    query attends (a learned sparse attention's; every live one where it is
    None; dense arithmetic over the live blocks either way); ``window``:
    lane ``s`` attends columns ``idx[s] - window < c <= idx[s]`` only."""
    from elephas_tpu.ops.attention import paged_decode_blocks

    slots, q_heads, width = q.shape
    num_blocks, _, _, block_size = pool.shape
    blocks_per_slot = table.shape[1]
    if blocks is None:
        blocks = paged_decode_blocks(pool.shape, pool.dtype, width, q_heads,
                                     blocks_per_slot, latent=True)
    dtype = pool.dtype
    idx = idx.astype(jnp.int32)
    table = jnp.clip(table.astype(jnp.int32), 0, num_blocks - 1)
    last = jnp.clip(idx // block_size, 0, blocks_per_slot - 1)
    most = -(-blocks_per_slot // blocks)
    live = active[:, None] & (jnp.arange(most)[None] * blocks <= last[:, None])
    (work,) = jnp.nonzero(live.reshape(-1), size=live.size, fill_value=0)
    work = work.astype(jnp.int32)
    lane, first = work // most, work % most * blocks
    steps = live.sum().astype(jnp.int32)

    sparse = selected is not None
    kernel = functools.partial(
        _latent_decode_kernel, blocks=blocks, blocks_per_slot=blocks_per_slot,
        value_width=value_width,
        sm_scale=float(scale) if scale is not None else 1.0 / (width ** 0.5),
        window=window, sparse=sparse,
    )
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    columns = blocks * block_size
    chosen, chosen_spec = [], []
    if sparse:  # float32 rows of a step's columns: one row is one tile's
        take = min(selected.shape[1], most * columns)
        chosen = [jnp.pad(selected[:, :take].astype(jnp.float32),
                          ((0, 0), (0, most * columns - take)))[:, None]]
        chosen_spec = [pl.BlockSpec(
            (1, 1, columns),
            lambda w, lane, first, *_: (lane[w], 0, first[w] // blocks))]
    out, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, q_heads, width),
                             lambda w, lane, *_: (lane[w], 0, 0)),
                pl.BlockSpec((1, width, 1),
                             lambda w, lane, *_: (lane[w], 0, 0)),
                *chosen_spec,
                any_space,
            ],
            out_specs=[
                pl.BlockSpec((1, q_heads, value_width),
                             lambda w, lane, *_: (lane[w], 0, 0)),
                any_space,
            ],
            scratch_shapes=[
                pltpu.VMEM((2, blocks, width, block_size), dtype),
                pltpu.SemaphoreType.DMA((2, blocks)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.VMEM((q_heads, 1), jnp.float32),
                pltpu.VMEM((q_heads, 1), jnp.float32),
                pltpu.VMEM((q_heads, value_width), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, q_heads, value_width), q.dtype),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the scalar-prefetch arguments: the pool is 7, or 8
        input_output_aliases={8 if sparse else 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=("sparse_latent_decode_attention" if sparse
              else "latent_decode_attention"),
    )(lane, first, table.reshape(-1), idx, steps.reshape(1), q.astype(dtype),
      jnp.swapaxes(new.astype(dtype), 1, 2), *chosen, pool)
    # a lane the grid never visited holds whatever its output rows held
    return jnp.where(active[:, None, None], out, 0), pool


def _latent_chunk_kernel(
    phys_ref, steps_ref, start_ref, valid_ref,  # scalar prefetch (SMEM)
    qn_ref, qp_ref,  # (heads a step, C, nope), (.., C, pe): scaled queries
    uk_ref, uv_ref,  # (heads a step, nope, rank), (.., v_head, rank)
    *refs,  # the step's blocks, (rank + pe, block_size) each;
    # [the selection of the step's columns, (C, columns)]; out; scratch
    blocks: int, rank: int, tq: int, window=None, sparse: bool = False,
):
    """Grid step (head group, ``j``): fold ``blocks`` blocks of the slot's
    row, from block ``j * blocks`` on, into the running softmax of the
    chunk's queries, for each head of the group. A block is latents,
    columns minor; for each head its first ``rank`` rows go through ``W_uk``
    to the head's keys and through ``W_uv`` to its values, both still
    columns minor and both made ONCE a step, and the last rows are the
    rotary key all heads share. The queries are walked in tiles of ``tq``
    rows, and of them only the tiles that hold a live score of this step:
    from the first whose last query reaches the step's first column to the
    last that holds a row below ``valid``. A tile whose first query
    already sees the step's last column is scored unmasked; the diagonal
    crosses the others. A tile past ``valid`` is never touched: its rows
    come back zero.

    Under a ``window`` a query sees the ``window`` columns that end with its
    own and a step visits no tile whose every query has left its last column
    behind; ``sparse``, a query sees of its live columns those its row of
    the selection marks 1. Every tile is then scored masked."""
    del phys_ref  # read by the index maps only
    k_refs = refs[:blocks]
    sel_ref = refs[blocks] if sparse else None
    o_ref, m_ref, l_ref, acc_ref, key_ref, value_ref = refs[blocks + sparse:]
    j = pl.program_id(1)
    block_size = k_refs[0].shape[1]
    columns = blocks * block_size
    heads, dtype = qn_ref.shape[0], qn_ref.dtype

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def product(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=jnp.float32)

    latents = jnp.concatenate([ref[:rank] for ref in k_refs], axis=1)
    for h in range(heads):
        key_ref[h] = product(uk_ref[h], latents, ((1,), (0,))).astype(dtype)
        value_ref[h] = product(uv_ref[h], latents, ((1,), (0,))).astype(dtype)

    start = start_ref[0]
    first_col = j * columns  # of this step; its last is first_col + columns - 1

    def fold(masked: bool):
        def tile(t, carry):
            rows = pl.ds(pl.multiple_of(t * tq, tq), tq)
            rotary = jnp.concatenate([ref[rank:] for ref in k_refs], axis=1)
            if masked:  # column c of the step is live for row r of the tile
                shape = (tq, columns)
                ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                         - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
                live = ahead <= start + t * tq - first_col
                if window is not None:
                    live &= ahead > start + t * tq - first_col - window
                if sparse:
                    live &= sel_ref[rows, :].astype(jnp.int32) > 0
            for h in range(heads):
                s = (product(qn_ref[h, rows, :], key_ref[h], ((1,), (0,)))
                     + product(qp_ref[h, rows, :], rotary, ((1,), (0,))))
                if masked:
                    s = jnp.where(live, s, _NEG_INF)
                m = m_ref[h, rows, :]
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_ref[h, rows, :] = (alpha * l_ref[h, rows, :]
                                     + p.sum(axis=-1, keepdims=True))
                acc_ref[h, rows, :] = alpha * acc_ref[h, rows, :] + product(
                    p.astype(dtype), value_ref[h], ((1,), (1,)))
                m_ref[h, rows, :] = m_new
            return carry
        return tile

    # tile t holds queries start + t * tq .. start + (t + 1) * tq - 1
    first = jnp.maximum(first_col - start, 0) // tq
    whole = jnp.maximum(first_col + columns - 1 - start + tq - 1, 0) // tq
    end = (valid_ref[0] - 1) // tq + 1
    if window is not None:  # the last tile with a query this near the step
        end = jnp.minimum(end, (first_col + columns - 1 + window - 1 - start)
                          // tq + 1)
    if window is not None or sparse:
        jax.lax.fori_loop(first, end, fold(True), 0)
    else:
        jax.lax.fori_loop(first, jnp.minimum(whole, end), fold(True), 0)
        jax.lax.fori_loop(whole, end, fold(False), 0)

    @pl.when(j == steps_ref[0] - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "tq", "interpret",
                                             "window"))
def pallas_latent_chunk_attention(q, kv_b, pool, row, start, scale=None,
                                  valid=None, tq: int | None = None,
                                  interpret: bool = False, selected=None,
                                  window: int | None = None):
    """One prefill chunk of one layer of ONE slot against a latent paged
    pool, which already holds the chunk's own columns: query ``i`` of every
    head attends columns ``<= start + i`` of the slot's blocks, keys and
    values a head expanded from each step's blocks as they are read.

    ``q``: (q_heads, C, nope + pe), a head's own queries, the rotary part
    rotated; ``kv_b``: (rank, q_heads, nope + v_head); ``pool``:
    (num_blocks, 1, rank + pe, block_size), read only; ``row``:
    (blocks_per_slot,) int32; ``start``: scalar; ``valid``: scalar, the
    chunk's real queries (all of them where it is None): the rows at or
    past it come back finite and mean nothing. Returns (q_heads, C,
    v_head). The grid is (head groups, steps over the row's blocks), the
    steps a run-time count that ends with the last valid query's column;
    inside a step the queries go in tiles of ``tq`` rows
    (``ops.attention._latent_chunk_tiles``; tests pass their own), the
    live ones only. The softmax scale is folded into the queries.

    ``selected``: (C, S) int8 of 0 and 1, ``S`` at least the row's columns
    in whole steps: query ``i`` attends of its live columns those marked 1 (a
    learned sparse attention's selection: dense arithmetic over every live
    step, the exact result over the set). ``window``: query ``i`` attends
    columns ``start + i - window < c <= start + i`` only."""
    from elephas_tpu.ops.attention import _latent_chunk_tiles

    q_heads, chunk, q_width = q.shape
    num_blocks, _, width, block_size = pool.shape
    rank = kv_b.shape[0]
    pe = width - rank
    nope = q_width - pe
    v_head = kv_b.shape[2] - nope
    group, blocks, tile = _latent_chunk_tiles(q_heads, block_size, chunk)
    tq = tile if tq is None else tq
    columns = blocks * block_size
    dtype = pool.dtype
    scale = float(scale) if scale is not None else 1.0 / (q_width ** 0.5)
    q = (q.astype(jnp.float32) * scale).astype(dtype)
    uk = jnp.transpose(kv_b[..., :nope], (1, 2, 0)).astype(dtype)  # (h, nope, rank)
    uv = jnp.transpose(kv_b[..., nope:], (1, 2, 0)).astype(dtype)  # (h, v, rank)
    start = start.astype(jnp.int32)
    valid = (jnp.int32(chunk) if valid is None
             else jnp.clip(valid.astype(jnp.int32), 1, chunk))

    most = -(-row.shape[0] // blocks)
    phys = jnp.clip(row.astype(jnp.int32), 0, num_blocks - 1)
    phys = jnp.pad(phys, (0, most * blocks - row.shape[0]), mode="edge")
    steps = jnp.clip((start + valid - 1) // columns + 1, 1, most)

    def by_head(*shape):
        return pl.BlockSpec((group,) + shape, lambda g, j, *_: (g,) + (0,) * len(shape))

    live_blocks = [
        pl.BlockSpec((None, None, width, block_size),
                     lambda g, j, phys, *_, b=b: (phys[j * blocks + b], 0, 0, 0))
        for b in range(blocks)
    ]
    sparse = selected is not None
    chosen, chosen_spec = [], []
    if sparse:
        take = min(selected.shape[1], most * columns)
        chosen = [jnp.pad(selected[:, :take].astype(jnp.int8),
                          ((0, 0), (0, most * columns - take)))]
        chosen_spec = [pl.BlockSpec((chunk, columns), lambda g, j, *_: (0, j))]
    return pl.pallas_call(
        functools.partial(_latent_chunk_kernel, blocks=blocks, rank=rank, tq=tq,
                          window=window, sparse=sparse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(q_heads // group, steps),
            in_specs=[by_head(chunk, nope), by_head(chunk, pe),
                      by_head(nope, rank), by_head(v_head, rank),
                      *live_blocks, *chosen_spec],
            out_specs=by_head(chunk, v_head),
            scratch_shapes=[
                pltpu.VMEM((group, chunk, 1), jnp.float32),
                pltpu.VMEM((group, chunk, 1), jnp.float32),
                pltpu.VMEM((group, chunk, v_head), jnp.float32),
                pltpu.VMEM((group, nope, columns), dtype),
                pltpu.VMEM((group, v_head, columns), dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((q_heads, chunk, v_head), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_LATENT_CHUNK_VMEM,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=("sparse_latent_chunk_attention" if sparse
              else "latent_chunk_attention"),
    )(phys, steps.reshape(1), start.reshape(1), valid.reshape(1),
      q[..., :nope], q[..., nope:], uk, uv, *([pool] * blocks), *chosen)
