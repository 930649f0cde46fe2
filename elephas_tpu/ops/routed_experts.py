"""A routed expert layer's product over the experts one device holds.

The layer's router scores every token against ALL of its ``total`` experts
and picks ``top_k`` of them (``group_limited_top_k``); a device holds the
contiguous range ``first .. first + count - 1`` of the experts and computes
its own experts' part of ``sum_e w_e * expert_e(y)`` (``routed_experts``):
what expert parallelism asks of one rank. An assignment on an expert held
elsewhere adds nothing here; nothing stands in for the other ranks or for
their exchange.

The product: the (token, expert) assignments that land on held experts are
sorted by expert, the tokens gathered in that order, and each of the three
matrices of a gated feed-forward is ONE grouped matrix product over the
ragged groups (``grouped_matmul``: rows ``offsets[g] .. offsets[g + 1]``
against matrix ``g``), then the rows are weighted and added back to their
tokens. The rows are as many as there are assignments, ``tokens * top_k``:
there is no capacity, so nothing is dropped whatever the routing, and an
expert with no token, or every assignment on one expert, is exact.

Two bodies with the same arithmetic (operands as given, float32
accumulation). ``grouped_xla`` is ``jax.lax.ragged_dot``: the CPU's body and
the kernel's reference. ``grouped_pallas`` is a kernel for one TPU whose
grid runs over the (group, row tile) pairs that hold a row, a run-time
count, so that the weights of an expert with no token are never read and
the rows past the last held assignment cost nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUPED_BODIES = ("grouped_pallas", "grouped_xla")

_ROW_TILE = 256   # rows a grid step, where there are as many
_COL_TILE = 512   # output columns a grid step
_DEPTH_TILE = 1024  # of the contraction a grid step


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _divisor(n: int, most: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` up to ``most`` that divides ``n``,
    or 0 where there is none."""
    return max((t for t in range(unit, min(n, most) + 1, unit) if n % t == 0),
               default=0)


def _pallas_fits(rows: int, depth: int, cols: int, dtype) -> bool:
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (jnp.dtype(dtype).itemsize in (2, 4) and rows % sublanes == 0
            and (rows <= _ROW_TILE or rows % _ROW_TILE == 0)
            and _divisor(depth, _DEPTH_TILE) > 0 and _divisor(cols, _COL_TILE) > 0)


def grouped_body(rows: int, depth: int, cols: int, dtype, mesh=None) -> str:
    """Name of the body ``grouped_matmul`` runs for this backend and shape.
    A Pallas call is not partitioned by sharding annotations, so a mesh
    takes the XLA body."""
    if _on_tpu() and mesh is None and _pallas_fits(rows, depth, cols, dtype):
        return "grouped_pallas"
    return "grouped_xla"


# -- the router ---------------------------------------------------------------


def group_limited_top_k(p, n_group: int, topk_group: int, top_k: int, by=None):
    """``group_limited_greedy``: of ``p`` (tokens, experts) keep the
    ``topk_group`` groups whose largest entry is largest, read 0 elsewhere,
    and take the ``top_k`` largest entries; the lower index wins a tie.
    Returns ``(ids, values)``, (tokens, top_k) each; the values are ``p``'s
    own, not renormalised.

    With ``by`` (tokens, experts) the experts are SELECTED by ``by`` (its
    groups, its ``top_k`` largest: a score plus a selection bias, say) and
    WEIGHED by ``p``: the values returned are ``p`` at the chosen ids. A
    dropped group then reads minus infinity, since ``by`` may lie below
    0."""
    tokens, experts = p.shape
    chosen = p if by is None else by
    by_group = chosen.reshape(tokens, n_group, experts // n_group)
    _, best = jax.lax.top_k(by_group.max(-1), topk_group)
    kept = jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], best].set(True)
    limited = jnp.where(kept[:, :, None], by_group,
                        0.0 if by is None else -jnp.inf).reshape(p.shape)
    values, ids = jax.lax.top_k(limited, top_k)
    if by is not None:
        values = jnp.take_along_axis(p, ids, axis=1)
    return ids, values


# -- the grouped product ------------------------------------------------------


def _grouped_kernel(group_ref, tile_ref, offsets_ref, lhs_ref, rhs_ref, out_ref,
                    acc_ref, *, row_tile: int, depth_steps: int):
    """Grid step (column tile, work item ``w``, depth step): rows of row
    tile ``tile_ref[w]`` against matrix ``group_ref[w]``; at the last depth
    step the rows that belong to the group go to the output tile, the others
    keep what an earlier work item of the same tile left there."""
    w, depth = pl.program_id(1), pl.program_id(2)

    @pl.when(depth == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(depth == depth_steps - 1)
    def _store():
        group = group_ref[w]
        row = tile_ref[w] * row_tile + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _work_items(offsets, rows: int, row_tile: int):
    """The (group, row tile) pairs that hold a row, group-major, and their
    count. At most ``tiles + groups - 1`` of them are real; the rest repeat
    the last (the grid never reaches them)."""
    groups = offsets.shape[0] - 1
    tiles = rows // row_tile
    most = tiles + groups - 1
    start, end = offsets[:-1], offsets[1:]
    first_tile = start // row_tile
    n_tiles = jnp.where(end > start, (end - 1) // row_tile - first_tile + 1, 0)
    ends = jnp.cumsum(n_tiles)
    count = ends[-1]
    item = jnp.arange(most, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(ends, item, side="right"), groups - 1)
    tile = first_tile[group] + item - (ends[group] - n_tiles[group])
    last = jnp.maximum(count - 1, 0)
    group = jnp.where(item < count, group, group[last]).astype(jnp.int32)
    tile = jnp.where(item < count, tile, tile[last]).astype(jnp.int32)
    return group, jnp.clip(tile, 0, tiles - 1), count.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """``lhs``: (rows, depth), the rows of group ``g`` together and the
    groups in order; ``rhs``: (groups, depth, cols); ``group_sizes``:
    (groups,) int32, their sum at most ``rows``. Returns (rows, cols) in
    ``lhs``'s type: row ``i`` of group ``g`` is ``lhs[i] @ rhs[g]``; the rows
    past the last group read 0."""
    rows, depth = lhs.shape
    groups, _, cols = rhs.shape
    row_tile = rows if rows <= _ROW_TILE else _ROW_TILE
    col_tile, depth_tile = _divisor(cols, _COL_TILE), _divisor(depth, _DEPTH_TILE)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes.astype(jnp.int32))])
    group, tile, count = _work_items(offsets, rows, row_tile)
    kernel = functools.partial(_grouped_kernel, row_tile=row_tile,
                               depth_steps=depth // depth_tile)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(cols // col_tile, count, depth // depth_tile),
            in_specs=[
                pl.BlockSpec((row_tile, depth_tile),
                             lambda n, w, k, group, tile, offsets: (tile[w], k)),
                pl.BlockSpec((None, depth_tile, col_tile),
                             lambda n, w, k, group, tile, offsets: (group[w], k, n)),
            ],
            out_specs=pl.BlockSpec(
                (row_tile, col_tile),
                lambda n, w, k, group, tile, offsets: (tile[w], n)),
            scratch_shapes=[pltpu.VMEM((row_tile, col_tile), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, cols), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="grouped_matmul",
    )(group, tile, offsets, lhs, rhs)
    # a row no group owns holds whatever its tile held
    return jnp.where((jnp.arange(rows) < offsets[-1])[:, None], out, 0)


@functools.partial(jax.jit, static_argnames=("body",))
def grouped_matmul(lhs, rhs, group_sizes, body: str = "grouped_xla"):
    """One grouped matrix product over ragged groups (see
    ``pallas_grouped_matmul`` for the operands); ``body`` is one of
    ``GROUPED_BODIES`` (``grouped_body`` picks it)."""
    if body not in GROUPED_BODIES:
        raise ValueError(f"unknown grouped body {body!r}; expected one of "
                         f"{GROUPED_BODIES}")
    with jax.named_scope("grouped_matmul"):
        if body == "grouped_pallas":
            return pallas_grouped_matmul(lhs, rhs, group_sizes)
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                                 preferred_element_type=jnp.float32).astype(lhs.dtype)
        # past the last group the TPU's ragged_dot leaves what it finds
        return jnp.where((jnp.arange(lhs.shape[0]) < group_sizes.sum())[:, None], out, 0)


# -- the layer's routed part --------------------------------------------------


def routed_experts(y, ids, weights, gate, up, down, first: int, body=None):
    """The held experts' part of ``sum_e w_e * expert_e(y)``.

    ``y``: (tokens, d); ``ids`` / ``weights``: (tokens, top_k), the router's
    choice over ALL experts and its weights; ``gate`` / ``up``: (count, d,
    f) and ``down``: (count, f, d), the matrices of the experts ``first ..
    first + count - 1``, each a gated feed-forward ``down(silu(gate y) * (up
    y))``. Returns ``(out, load)``: ``out`` (tokens, d) float32, and ``load``
    (count,) int32, the assignments that fell on each held expert.
    """
    tokens, d = y.shape
    top_k = ids.shape[1]
    count = gate.shape[0]
    rows = tokens * top_k
    flat = ids.reshape(-1)
    local = jnp.where((flat >= first) & (flat < first + count), flat - first, count)
    order = jnp.argsort(local, stable=True)  # held assignments first, by expert
    load = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    token_of = order // top_k
    x = y[token_of]
    if body is None:  # the kernel where all three products fit it
        f = gate.shape[-1]
        body = max(grouped_body(rows, d, f, y.dtype), grouped_body(rows, f, d, y.dtype),
                   key=GROUPED_BODIES.index)
    h = (jax.nn.silu(grouped_matmul(x, gate, load, body).astype(jnp.float32))
         * grouped_matmul(x, up, load, body).astype(jnp.float32)).astype(y.dtype)
    part = grouped_matmul(h, down, load, body).astype(jnp.float32)
    # an assignment held elsewhere sorts past every group: its row reads 0
    part = part * weights.reshape(-1)[order, None].astype(jnp.float32)
    # back in the assignments' own order: a token's top_k rows are adjacent
    back = jnp.zeros((rows,), jnp.int32).at[order].set(jnp.arange(rows, dtype=jnp.int32))
    return part[back].reshape(tokens, top_k, d).sum(1), load
