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
accumulation, the depth walked in the same order). ``grouped_xla`` is
``jax.lax.ragged_dot`` over the packed rows: the CPU's body, a mesh's and
the kernel's reference. ``grouped_pallas`` is a kernel for one TPU whose
work items are the (group, row tile of that group) pairs that hold a row, a
run-time count, so that the weights of an expert with no token are never
read. What it is fed and how its grid runs follows from the rows, a shape:

- More assignments than one row tile (``_ROW_TILE``, a chunk's): the held
  assignments are laid out with every group starting at a multiple of
  ``_SUB_TILE`` (``laid_out_rows``, ``group_starts``: the gather out of the
  tokens and the gather back take other indices, the rows no further pass),
  so a group owns its row tiles. The grid is (work item, column tile, depth
  step). The kernel copies an item's rows itself, the sub-tiles that hold a
  row of the group and no other, once and at full depth, while the item
  before is multiplied; the expert's matrix streams past them block by
  block, once an item; a sub-tile past the group's last row is neither
  pushed through the MXU nor written. A product reads each touched expert's
  matrix once a row tile of its group (``weight_passes``: once, for all but
  the largest groups) and each row once.
- One row tile at most (a decode step's): the rows lie packed and all
  groups share the tile. The grid is (column tile, work item, depth step),
  the whole tile stays in VMEM at full depth from the first step on, and an
  item's result is stored under a mask of its group's rows.

The way back follows the same shape. Over many tiles a kernel
(``pallas_combine_rows``) reads the held rows alone, once, in their laid-out
order, and adds each, weighed, to its token's float32 sum in VMEM, which it
writes once; over one tile the packed rows are weighed and gathered back
into the assignments' order and summed in XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUPED_BODIES = ("grouped_pallas", "grouped_xla")

_ROW_TILE = 256   # rows a work item, where there are as many
_SUB_TILE = 128   # rows a push through the MXU; what a group of many tiles starts at
_COL_TILE = 512   # output columns a grid step
_DEPTH_TILE = 1024  # of the contraction a grid step
_VMEM_BUDGET = 12 << 20  # of the 16 MiB a v5e kernel has by default
_PIECE = 16  # rows a copy of the way back: a sub-tile's live rows, rounded up
_COMBINE_VMEM_BUDGET = 64 << 20  # of a v5e core's 128 MiB, asked for by the call
_COMBINE_SMEM_BUDGET = 512 << 10  # of a v5e core's 1 MiB


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _divisor(n: int, most: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` up to ``most`` that divides ``n``,
    or 0 where there is none."""
    return max((t for t in range(unit, min(n, most) + 1, unit) if n % t == 0),
               default=0)


def _vmem_bytes(rows: int, depth: int, cols: int, dtype) -> int:
    """What the kernel keeps in VMEM: a work item's rows at full depth and a
    block of a matrix, each twice (one in flight), the accumulator, and a
    column tile of results twice."""
    item, tile = jnp.dtype(dtype).itemsize, min(rows, _ROW_TILE)
    col_tile, depth_tile = _divisor(cols, _COL_TILE), _divisor(depth, _DEPTH_TILE)
    return (2 * item * (tile * depth + depth_tile * col_tile + tile * col_tile)
            + 4 * tile * col_tile)


def _pallas_fits(rows: int, depth: int, cols: int, dtype) -> bool:
    """``rows`` assignments: one tile's lie packed and fill whole sublanes;
    more are laid out anew (``laid_out_rows``) and may be any number."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (jnp.dtype(dtype).itemsize in (2, 4)
            and (rows > _ROW_TILE or rows % sublanes == 0)
            and _divisor(depth, _DEPTH_TILE) > 0 and _divisor(cols, _COL_TILE) > 0
            and _vmem_bytes(rows, depth, cols, dtype) <= _VMEM_BUDGET)


def _combine_tile(tokens: int, d: int, dtype) -> int:
    """The widest column tile (a multiple of 128 dividing ``d``) whose
    sums, ``tokens`` rows of float32, fit ``_COMBINE_VMEM_BUDGET`` beside a
    sub-tile of rows twice and once in float32; 0 where none does."""
    return max((c for c in range(128, d + 1, 128) if d % c == 0
                and _combine_vmem_bytes(tokens, c, dtype) <= _COMBINE_VMEM_BUDGET),
               default=0)


def _combine_vmem_bytes(tokens: int, col_tile: int, dtype) -> int:
    return col_tile * (4 * tokens + (2 * jnp.dtype(dtype).itemsize + 4) * _SUB_TILE)


def _combine_fits(tokens: int, top_k: int, d: int, dtype) -> bool:
    """The way back's kernel holds each assignment's place and weight in
    SMEM and a column tile of every token's sums in VMEM."""
    return 8 * tokens * top_k <= _COMBINE_SMEM_BUDGET and _combine_tile(tokens, d, dtype) > 0


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


def laid_out_rows(rows: int, groups: int) -> int:
    """Rows of the layout ``pallas_grouped_matmul`` takes for ``rows`` rows
    in ``groups`` groups. One tile's worth lie packed. More lie with every
    group at a multiple of ``_SUB_TILE``, which no routing needs more than
    ``groups`` sub-tiles beyond the rows' own for."""
    if rows <= _ROW_TILE:
        return rows
    return (rows // _SUB_TILE + groups) * _SUB_TILE


def group_starts(group_sizes, rows: int):
    """The row each group starts at among ``rows`` laid-out rows: one tile's
    groups packed, more rounded up to ``_SUB_TILE`` each."""
    sizes = group_sizes.astype(jnp.int32)
    if rows > _ROW_TILE:
        sizes = -(-sizes // _SUB_TILE) * _SUB_TILE
    return jnp.cumsum(sizes) - sizes


def _row_tiles(group_sizes, rows: int):
    """Row tiles each group takes where the assignments are ``rows``: one
    for a group within a tile, none for an empty one."""
    return -(-group_sizes.astype(jnp.int32) // min(rows, _ROW_TILE))


def weight_passes(group_sizes, rows: int):
    """The (group, row tile) work items the kernel makes of ``group_sizes``
    where the assignments are ``rows``: each streams one matrix of its group
    once."""
    return _row_tiles(group_sizes, rows).sum()


def _work_items(group_sizes, rows: int):
    """``(group, tile, count)``: the group of each work item and which of its
    group's row tiles it is, group-major, and how many are real; the rest
    repeat the last (the grid never reaches them)."""
    groups = group_sizes.shape[0]
    n_tiles = _row_tiles(group_sizes, rows)
    ends = jnp.cumsum(n_tiles)
    count = ends[-1]
    item = jnp.arange(-(-rows // _ROW_TILE) + groups, dtype=jnp.int32)  # at most
    item = jnp.minimum(item, jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(ends, item, side="right"), groups - 1)
    tile = item - (ends[group] - n_tiles[group])
    return group.astype(jnp.int32), tile.astype(jnp.int32), count.astype(jnp.int32)


def _one_tile_kernel(group_ref, offsets_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                     depth_tile: int, depth_steps: int):
    """Grid step (column tile, work item ``w``, depth step): every row, held
    in VMEM at full depth since the kernel's first step, against matrix
    ``group_ref[w]``; at the last depth step the rows that belong to the
    group go to the output tile, the others keep what an earlier work item
    left there."""
    w, depth = pl.program_id(1), pl.program_id(2)

    @pl.when(depth == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at = pl.multiple_of(depth * depth_tile, depth_tile)
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[:, pl.ds(at, depth_tile)], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(depth == depth_steps - 1)
    def _store():
        group = group_ref[w]
        row = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _tiles_kernel(group_ref, sub_ref, live_ref, count_ref, lhs_hbm, rhs_ref, out_hbm,
                  rows_ref, acc_ref, res_ref, rows_sem, res_sem, *,
                  depth_tile: int, depth_steps: int, col_tile: int, col_steps: int):
    """Grid step (work item ``w``, column tile, depth step). The item's rows
    are the sub-tiles ``sub_ref[w] .. sub_ref[w] + live_ref[w] - 1`` of
    ``lhs_hbm``, its group's alone: copied to ``rows_ref`` once, at full
    depth, while the item before it is multiplied; pushed through the MXU
    sub-tile by sub-tile against the depth step's block of matrix
    ``group_ref[w]``; and copied out of ``res_ref`` to their own rows of
    ``out_hbm`` a column tile at a time, behind the next column tile's
    products. A sub-tile past the group's last row is not copied, not
    multiplied and not written."""
    w, col, depth = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    subs = rows_ref.shape[1] // _SUB_TILE
    slot = w % 2

    def live(item, do):
        for s in range(subs):
            pl.when(s < live_ref[item])(functools.partial(do, s))

    def its_rows(item, s):  # where sub-tile ``s`` of the item lies in HBM
        return pl.ds(pl.multiple_of((sub_ref[item] + s) * _SUB_TILE, _SUB_TILE), _SUB_TILE)

    def rows_copy(item, slot, s):
        return pltpu.make_async_copy(
            lhs_hbm.at[its_rows(item, s)],
            rows_ref.at[slot, pl.ds(s * _SUB_TILE, _SUB_TILE)], rows_sem.at[slot, s])

    def res_copy(item, col, slot, s):
        return pltpu.make_async_copy(
            res_ref.at[slot, pl.ds(s * _SUB_TILE, _SUB_TILE)],
            out_hbm.at[its_rows(item, s),
                       pl.ds(pl.multiple_of(col * col_tile, col_tile), col_tile)],
            res_sem.at[slot, s])

    @pl.when((col == 0) & (depth == 0))
    def _rows():
        @pl.when(w == 0)
        def _first():
            live(0, lambda s: rows_copy(0, 0, s).start())

        @pl.when(w + 1 < count_ref[0])
        def _next():
            live(w + 1, lambda s: rows_copy(w + 1, 1 - slot, s).start())

        live(w, lambda s: rows_copy(w, slot, s).wait())

    def push(s):
        rows = pl.ds(s * _SUB_TILE, _SUB_TILE)
        at = pl.multiple_of(depth * depth_tile, depth_tile)
        part = jax.lax.dot_general(
            rows_ref[slot, rows, pl.ds(at, depth_tile)], rhs_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(depth == 0)
        def _set():
            acc_ref[rows, :] = part

        @pl.when(depth > 0)
        def _add():
            acc_ref[rows, :] += part

    live(w, push)

    @pl.when(depth == depth_steps - 1)
    def _store():
        step = w * col_steps + col  # the results' two buffers take turns
        mine = step % 2

        @pl.when(step >= 2)
        def _free():
            before = (step - 2) // col_steps
            live(before, lambda s: res_copy(before, 0, mine, s).wait())

        def out(s):
            rows = pl.ds(s * _SUB_TILE, _SUB_TILE)
            res_ref[mine, rows, :] = acc_ref[rows, :].astype(res_ref.dtype)
            res_copy(w, col, mine, s).start()

        live(w, out)

        @pl.when((w == count_ref[0] - 1) & (col == col_steps - 1))
        def _drain():
            @pl.when(step >= 1)
            def _other():
                before = (step - 1) // col_steps
                live(before, lambda s: res_copy(before, 0, 1 - mine, s).wait())

            live(w, lambda s: res_copy(w, 0, mine, s).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """``lhs``: (rows, depth), the rows of group ``g`` together from row
    ``group_starts(group_sizes, rows)[g]`` on; ``rhs``: (groups, depth,
    cols); ``group_sizes``: (groups,) int32. Returns (rows, cols) in
    ``lhs``'s type: row ``i`` of group ``g`` is ``lhs[i] @ rhs[g]``. Of the
    rows no group owns, one tile's (``rows <= _ROW_TILE``) read 0 and more
    hold anything."""
    rows, depth = lhs.shape
    groups, _, cols = rhs.shape
    col_tile, depth_tile = _divisor(cols, _COL_TILE), _divisor(depth, _DEPTH_TILE)
    sizes = group_sizes.astype(jnp.int32)
    group, tile, count = _work_items(sizes, rows)
    steps = dict(depth_tile=depth_tile, depth_steps=depth // depth_tile)
    params = dict(
        out_shape=jax.ShapeDtypeStruct((rows, cols), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="grouped_matmul")
    if rows <= _ROW_TILE:
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        out = pl.pallas_call(
            functools.partial(_one_tile_kernel, **steps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(cols // col_tile, count, depth // depth_tile),
                in_specs=[
                    pl.BlockSpec((rows, depth), lambda n, w, k, group, offsets: (0, 0)),
                    pl.BlockSpec((None, depth_tile, col_tile),
                                 lambda n, w, k, group, offsets: (group[w], k, n)),
                ],
                out_specs=pl.BlockSpec((rows, col_tile),
                                       lambda n, w, k, group, offsets: (0, n)),
                scratch_shapes=[pltpu.VMEM((rows, col_tile), jnp.float32)],
            ),
            **params,
        )(group, offsets, lhs, rhs)
        # a row no group owns holds whatever its tile held
        return jnp.where((jnp.arange(rows) < offsets[-1])[:, None], out, 0)
    subs = _ROW_TILE // _SUB_TILE
    sub = group_starts(sizes, rows)[group] // _SUB_TILE + tile * subs
    live = jnp.clip(-(-(sizes[group] - tile * _ROW_TILE) // _SUB_TILE), 0, subs)
    return pl.pallas_call(
        functools.partial(_tiles_kernel, col_tile=col_tile, col_steps=cols // col_tile,
                          **steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(count, cols // col_tile, depth // depth_tile),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((None, depth_tile, col_tile),
                             lambda w, n, k, group, sub, live, count: (group[w], k, n)),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, _ROW_TILE, depth), lhs.dtype),
                pltpu.VMEM((_ROW_TILE, col_tile), jnp.float32),
                pltpu.VMEM((2, _ROW_TILE, col_tile), lhs.dtype),
                pltpu.SemaphoreType.DMA((2, subs)),
                pltpu.SemaphoreType.DMA((2, subs)),
            ],
        ),
        **params,
    )(group, sub, live, count[None], lhs, rhs)


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


# -- the way back ---------------------------------------------------------------


def _combine_kernel(sizes_ref, order_ref, weight_ref, rows_hbm, out_hbm, start_ref, end_ref,
                    sums_ref, rows_ref, wide_ref, rows_sem, out_sem, *, top_k: int,
                    col_tile: int):
    """Grid step: a column tile. The live sub-tiles of ``rows_hbm`` are the
    groups' own, one after another from the first; ``start_ref`` takes the
    sorted position of each one's first row and ``end_ref`` its rows. Each
    sub-tile's live pieces are copied in while the one before is added,
    each row weighed and added to its token's row of ``sums_ref``, and the
    sums are written out once."""
    cols = pl.ds(pl.multiple_of(pl.program_id(0) * col_tile, col_tile), col_tile)

    def group(g, carry):
        sub, first = carry
        size = sizes_ref[g]

        def each(k, sub):
            start_ref[sub] = first + k * _SUB_TILE
            end_ref[sub] = jnp.minimum(size - k * _SUB_TILE, _SUB_TILE)
            return sub + 1

        return jax.lax.fori_loop(0, (size + _SUB_TILE - 1) // _SUB_TILE, each, sub), first + size

    subs, _ = jax.lax.fori_loop(0, sizes_ref.shape[0], group, (0, 0))

    def live(sub, do):
        for q in range(_SUB_TILE // _PIECE):
            pl.when(q * _PIECE < end_ref[sub])(functools.partial(do, q))

    def rows_copy(sub, slot, q):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(sub * _SUB_TILE + q * _PIECE, _PIECE), _PIECE), cols],
            rows_ref.at[slot, pl.ds(q * _PIECE, _PIECE)], rows_sem.at[slot, q])

    sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(subs > 0)
    def _first():
        live(0, lambda q: rows_copy(0, 0, q).start())

    def sub_tile(sub, carry):
        slot = sub % 2

        @pl.when(sub + 1 < subs)
        def _next():
            live(sub + 1, lambda q: rows_copy(sub + 1, 1 - slot, q).start())

        def widen(q):
            rows_copy(sub, slot, q).wait()
            piece = pl.ds(q * _PIECE, _PIECE)
            wide_ref[piece, :] = rows_ref[slot, piece, :].astype(jnp.float32)

        live(sub, widen)
        first = start_ref[sub]

        def add(r, carry):
            a = order_ref[first + r]  # the assignment: its token, its weight
            sums_ref[pl.ds(a // top_k, 1), :] += weight_ref[a] * wide_ref[pl.ds(r, 1), :]
            return carry

        return jax.lax.fori_loop(0, end_ref[sub], add, carry)

    jax.lax.fori_loop(0, subs, sub_tile, 0)
    out = pltpu.make_async_copy(sums_ref, out_hbm.at[:, cols], out_sem)
    out.start()
    out.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_combine_rows(rows, order, weights, group_sizes, interpret: bool = False):
    """The way back from the kernel's layout of many row tiles. ``rows``:
    (laid, d), the held assignments' rows as ``laid_out_rows`` /
    ``group_starts`` lay them out for ``group_sizes``; ``order``: (tokens *
    top_k,), the assignments sorted by group, the held ones first, as
    ``rows`` holds them; ``weights``: (tokens, top_k). Returns (tokens, d)
    float32: each token's sum of ``weights[t, k] * rows[its row]`` over its
    held assignments. A row no group owns is never read, whatever it holds,
    and a token with no held assignment reads 0."""
    laid, d = rows.shape
    tokens, top_k = weights.shape
    col_tile = _combine_tile(tokens, d, rows.dtype)
    subs = laid // _SUB_TILE
    return pl.pallas_call(
        functools.partial(_combine_kernel, top_k=top_k, col_tile=col_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // col_tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SMEM((subs,), jnp.int32),
                pltpu.SMEM((subs,), jnp.int32),
                pltpu.VMEM((tokens, col_tile), jnp.float32),
                pltpu.VMEM((2, _SUB_TILE, col_tile), rows.dtype),
                pltpu.VMEM((_SUB_TILE, col_tile), jnp.float32),
                pltpu.SemaphoreType.DMA((2, _SUB_TILE // _PIECE)),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_combine_vmem_bytes(tokens, col_tile, rows.dtype) + (4 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="combine_rows",
    )(group_sizes.astype(jnp.int32), order.astype(jnp.int32),
      weights.reshape(-1).astype(jnp.float32), rows)


# -- the layer's routed part --------------------------------------------------


def routed_body(tokens: int, top_k: int, d: int, f: int, dtype) -> str:
    """The body ``routed_experts`` runs for ``tokens`` of ``top_k``
    assignments over experts of widths ``d`` and ``f``: the kernel where all
    three products fit it and, over more than one row tile, the way back."""
    rows = tokens * top_k
    body = max(grouped_body(rows, d, f, dtype), grouped_body(rows, f, d, dtype),
               key=GROUPED_BODIES.index)
    if rows > _ROW_TILE and not _combine_fits(tokens, top_k, d, dtype):
        return "grouped_xla"
    return body


def combines_held_rows(rows: int, body: str) -> bool:
    """Whether the way back reads the held rows alone (the kernel's layout
    of many row tiles) or a row for every assignment."""
    return body == "grouped_pallas" and rows > _ROW_TILE


def rows_combined(load, assignments, rows: int, body: str):
    """The rows the way back reads for ``rows`` assignments in ``body``:
    the held ones, ``load``'s sum, where it reads them alone; else
    ``assignments``, those that count (padding and idle lanes left out)."""
    return load.sum() if combines_held_rows(rows, body) else assignments


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
    with jax.named_scope("sort_rows"):
        flat = ids.reshape(-1)
        local = jnp.where((flat >= first) & (flat < first + count), flat - first, count)
        order = jnp.argsort(local, stable=True)  # held assignments first, by expert
        load = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    if body is None:
        body = routed_body(tokens, top_k, d, gate.shape[-1], y.dtype)

    def expert(x):
        g = grouped_matmul(x, gate, load, body)
        with jax.named_scope("silu_mul"):
            g = jax.nn.silu(g.astype(jnp.float32))
        u = grouped_matmul(x, up, load, body)
        with jax.named_scope("silu_mul"):
            h = (g * u.astype(jnp.float32)).astype(y.dtype)
        return grouped_matmul(h, down, load, body)

    if combines_held_rows(rows, body):
        # the kernel's layout of many tiles: a group's rows from its own start
        with jax.named_scope("gather_rows"):
            laid = laid_out_rows(rows, count)
            group = local[order]
            held = group < count
            of = jnp.minimum(group, count - 1)
            offsets = jnp.cumsum(load) - load
            row = jnp.where(held, group_starts(load, laid)[of]
                            + jnp.arange(rows, dtype=jnp.int32) - offsets[of], laid)
            token_of = jnp.zeros((laid,), jnp.int32).at[row].set(order // top_k,
                                                                 mode="drop")
            x = y[token_of]
        out = expert(x)
        with jax.named_scope("combine_rows"):
            # the held rows alone, each once: an assignment held elsewhere adds 0
            return pallas_combine_rows(out, order, weights, load), load
    with jax.named_scope("gather_rows"):
        x = y[order // top_k]
    out = expert(x)
    with jax.named_scope("combine_rows"):
        # an assignment held elsewhere sorts past every group: its row reads 0
        part = out.astype(jnp.float32) * weights.reshape(-1)[order, None].astype(jnp.float32)
        # back in the assignments' own order: a token's top_k rows are adjacent
        back = jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        return part[back].reshape(tokens, top_k, d).sum(1), load
