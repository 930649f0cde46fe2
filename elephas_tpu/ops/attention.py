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


# -- paged KV-cache gather/scatter ------------------------------------------
#
# The serving pool's paged layout stores K/V as physical blocks
# (num_blocks, heads, block_size, head_dim) shared across slots through a
# (max_slots, blocks_per_slot) block table. These helpers are the bridge
# between that layout and the contiguous (slots, heads, len, head_dim)
# view the dense cache-attention path consumes: gather through the table
# before the apply, scatter exactly the freshly-written columns back
# after it. Unallocated table entries carry the OUT-OF-RANGE id
# ``num_blocks``: gathers clamp (the garbage columns sit at or past
# every reader's cache index, so the causal mask hides them) and
# scatters drop (``mode="drop"``), so no index is ever negative.


def paged_to_contiguous(leaf, table):
    """Gather a paged K/V leaf into per-slot contiguous rows.

    ``leaf``: (num_blocks, heads, block_size, head_dim) physical blocks;
    ``table``: (max_slots, blocks_per_slot) int32 block ids. Returns
    (max_slots, heads, blocks_per_slot * block_size, head_dim).
    """
    slots, bps = table.shape
    _, heads, bs, head_dim = leaf.shape
    gathered = leaf[table]  # (slots, bps, heads, bs, head_dim); OOB clamps
    gathered = jnp.transpose(gathered, (0, 2, 1, 3, 4))
    return gathered.reshape(slots, heads, bps * bs, head_dim)


def slot_row_to_contiguous(leaf, row_table):
    """Gather ONE slot's blocks as a batch-1 contiguous cache row.

    ``row_table``: (blocks_per_slot,) int32 block ids for the slot.
    Returns (1, heads, blocks_per_slot * block_size, head_dim).
    """
    gathered = leaf[row_table]  # (bps, heads, bs, head_dim)
    gathered = jnp.transpose(gathered, (1, 0, 2, 3))
    heads, bps, bs, head_dim = gathered.shape
    return gathered.reshape(heads, bps * bs, head_dim)[None]


def scatter_decode_columns(pool_leaf, contiguous, table, idx, active):
    """Write each slot's just-decoded column back into its physical block.

    ``contiguous`` is the (max_slots, heads, L, head_dim) view AFTER the
    apply wrote column ``idx[s]`` for every slot s (``idx`` is the
    PRE-advance cache index vector). Inactive lanes scatter to the
    out-of-range block id and drop — their computed column is garbage by
    contract.
    """
    num_blocks, _, bs, _ = pool_leaf.shape
    written = jnp.take_along_axis(
        contiguous, idx[:, None, None, None], axis=2
    )[:, :, 0, :]  # (max_slots, heads, head_dim)
    blk = jnp.take_along_axis(table, (idx // bs)[:, None], axis=1)[:, 0]
    target = jnp.where(active, blk, num_blocks)
    return pool_leaf.at[target, :, idx % bs].set(written, mode="drop")


def scatter_prefill_columns(pool_leaf, row_table, start, chunk):
    """Write one prefill chunk's columns ``[start, start + C)`` of ONE
    slot into its physical blocks.

    ``chunk``: (heads, C, head_dim) — the freshly-computed K or V
    columns. Columns landing in unallocated blocks (right-pad garbage
    past the slot's allocation) hit the out-of-range id and drop.
    """
    bs = pool_leaf.shape[2]
    cols = start + jnp.arange(chunk.shape[1])
    target = row_table[cols // bs]
    return pool_leaf.at[target, :, cols % bs].set(
        jnp.transpose(chunk, (1, 0, 2)), mode="drop"
    )


def scatter_spec_columns(pool_leaf, contiguous, table, idx, count, active):
    """Write each slot's ``count`` freshly-computed columns
    ``[idx[s], idx[s] + count)`` back into its physical blocks — the
    multi-column sibling of ``scatter_decode_columns`` for speculative
    draft/verify windows.

    ``contiguous`` is the (max_slots, heads, L, head_dim) view AFTER an
    apply with seq == count wrote those columns (``idx`` is the
    PRE-advance cache index vector; ``count`` is static). Inactive lanes
    and columns past the row's virtual capacity scatter to the
    out-of-range block id and drop. Rejected-suffix columns are written
    too — they sit at or past every reader's causal frontier until a
    later accepted token overwrites them, so they are never attended.
    """
    num_blocks, heads, bs, head_dim = pool_leaf.shape
    slots, bps = table.shape
    cols = idx[:, None] + jnp.arange(count)[None, :]  # (slots, count)
    written = jnp.take_along_axis(
        contiguous, cols[:, None, :, None], axis=2
    )  # (slots, heads, count, head_dim)
    written = jnp.transpose(written, (0, 2, 1, 3)).reshape(
        slots * count, heads, head_dim
    )
    blk = jnp.take_along_axis(
        table, jnp.clip(cols // bs, 0, bps - 1), axis=1
    )  # (slots, count)
    ok = active[:, None] & (cols < bps * bs)
    target = jnp.where(ok, blk, num_blocks)
    return pool_leaf.at[target.reshape(-1), :, (cols % bs).reshape(-1)].set(
        written, mode="drop"
    )


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
