"""State-space duality (SSD): the recurrence of a Mamba-2 mixer.

Per head ``h`` of ``H``, a state ``S_h`` of (P, N) in float32 that decays
by a step each token sets::

    S_t,h = exp(delta_t,h a_h) S_t-1,h + (delta_t,h x_t,h) B_t^T
    y_t,h = S_t,h C_t

``x_t,h`` is the head's (P,) slice of the token's inputs, ``B_t`` and
``C_t`` are (N,) and shared by every head (one group), ``a_h < 0``,
``delta >= 0``. The caller adds the skip ``D_h x_t,h``. What a serving slot
keeps of such a layer is ``S``: 4 MiB a slot at 128 heads of (64, 128).

**The state's layout.** ``state_shape(H, P, N)``: heads are packed ``pack``
to a row of 128 lanes, ``(H // pack, N, pack * P)``, element ``[g, n, i P +
p]`` being ``S_{g pack + i}[p, n]`` (``pack_state`` / ``unpack_state``).
At P = 64 a row of lanes holds two heads; a layout with P or N minor and
P = 64 would hold half-empty rows or need a transpose a head. In this one
a token's ``x`` (``(H P,)``, heads in order) is laid over the state's lanes
as it is, ``B`` and ``C`` run down its sublanes, and ``C^T S`` is one plain
product a group.

``ssd_chunk`` is the chunk form: ``T`` tokens from an initial state, of
which the first ``valid`` are real. It walks sub-chunks of ``SUB_CHUNK``
tokens (the published ``mamba_chunk_size``). With ``A_i`` the running sum
of ``delta a`` inside the sub-chunk, ``L_ij = exp(A_i - A_j)`` for ``j <=
i``::

    y_i     = sum_j (C_i . B_j) L_ij (delta x)_j + exp(A_i) S_in C_i
    S_out   = exp(A_end) S_in + sum_j exp(A_end - A_j) (delta x)_j B_j^T

A token at or past ``valid`` has its ``delta`` set to 0: it decays nothing
and adds nothing, so the state returned is the state after ``valid`` tokens
whatever the padding holds. ``ssd_step`` is the one-token form of a decode
step, for the lanes an ``active`` mask marks; the other lanes' rows come
back as they were.

Each form has two bodies with the same arithmetic: ``ssd_xla`` (the CPU's,
and the kernels' reference) and ``ssd_pallas``. The chunk kernel
(``ssd_chunk``) walks sub-chunks in order and, in each, makes ``C B^T``
once for all heads, builds a head's ``L`` from the running sums, and keeps
the whole state in VMEM from the first sub-chunk to the last, where it is
written once. The step kernel (``ssd_step``) reads each active lane's state
once and writes it once, in place; it never reads or writes an idle lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SSD_BODIES = ("ssd_pallas", "ssd_xla")

# Tokens a sub-chunk of the chunk form (``mamba_chunk_size``).
SUB_CHUNK = 256
_LANES = 128
_CHUNK_GROUPS = 4   # lane rows of the state a grid step of the chunk kernel
_STEP_BLOCK = 2 << 20  # bytes of a lane's state a grid step of the step kernel
# the chunk kernel's update of the state in float32: a single pass of the MXU
# rounds its operands to bfloat16 (0.4 % of the state on a v5e). Its products
# for y take one pass: y is rounded to bfloat16 before `out_proj` anyway.
_EXACT = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def heads_a_row(H: int, P: int) -> int:
    """Heads packed into one row of lanes: 128 // P where that divides
    ``H``, else one."""
    if P < _LANES and _LANES % P == 0 and H % (_LANES // P) == 0:
        return _LANES // P
    return 1


def state_shape(H: int, P: int, N: int) -> tuple:
    pack = heads_a_row(H, P)
    return (H // pack, N, pack * P)


def pack_state(S):
    """(..., H, P, N) -> the state's layout (..., H // pack, N, pack * P)."""
    *lead, H, P, N = S.shape
    pack = heads_a_row(H, P)
    S = S.reshape(*lead, H // pack, pack, P, N)
    return jnp.moveaxis(S, -1, -3).reshape(*lead, H // pack, N, pack * P)


def unpack_state(S, P: int):
    """The state's layout (..., G, N, pack * P) -> (..., H, P, N)."""
    *lead, G, N, width = S.shape
    pack = width // P
    S = S.reshape(*lead, G, N, pack, P)
    return jnp.moveaxis(S, -3, -1).reshape(*lead, G * pack, P, N)


def _pallas_shapes(H: int, P: int, N: int) -> bool:
    """Rows of whole lanes, ``N`` a whole number of lanes, and whole
    sublane tiles of heads a grid step's block."""
    pack = heads_a_row(H, P)
    G = H // pack
    return (pack * P == _LANES and N % _LANES == 0 and G % _CHUNK_GROUPS == 0
            and (_CHUNK_GROUPS * pack) % 8 == 0)


def ssd_body(T: int, H: int, P: int, N: int) -> str:
    """Name of the chunk form's body for this backend and these shapes: the
    kernel takes a chunk of whole sub-chunks (or one shorter than a
    sub-chunk, of whole sublane tiles)."""
    whole = T % SUB_CHUNK == 0 or (T < SUB_CHUNK and T % 8 == 0)
    if _on_tpu() and whole and _pallas_shapes(H, P, N):
        return "ssd_pallas"
    return "ssd_xla"


def ssd_step_body(H: int, P: int, N: int) -> str:
    """Name of the step form's body for this backend and these shapes."""
    if _on_tpu() and _pallas_shapes(H, P, N):
        return "ssd_pallas"
    return "ssd_xla"


def subchunks(valid, sub: int = SUB_CHUNK):
    """Sub-chunks of the chunk form that hold a valid token."""
    return -(-valid // sub)


def _inputs(x, delta, A, valid):
    """delta masked past ``valid``, ``delta x`` and ``delta a``, float32."""
    T, H = delta.shape
    live = (jnp.arange(T) < valid)[:, None]
    delta = jnp.where(live, delta.astype(jnp.float32), 0.0)
    xdt = x.astype(jnp.float32) * delta[..., None]
    return xdt, delta * A.astype(jnp.float32)[None, :]


def _running_sums(a, sub: int):
    """(T, H) -> the running sum of each sub-chunk's ``delta a``, (T, H)."""
    T, H = a.shape
    return jnp.cumsum(a.reshape(T // sub, sub, H), axis=1).reshape(T, H)


def _chunk_xla(xdt, a, B, C, s0, sub: int):
    """(T, H, P), (T, H), (T, N) x2, (H, P, N) -> (T, H, P), (H, P, N)."""
    T, H, P = xdt.shape
    n = -(-T // sub)
    pad = n * sub - T
    xdt, a, B, C = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                    for v in (xdt, a, B, C))
    tril = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    hi = jax.lax.Precision.HIGHEST

    def sub_chunk(S, xs):
        x_, a_, b_, c_ = xs                     # (sub, H, P), (sub, H), (sub, N) x2
        run = jnp.cumsum(a_, axis=0)            # (sub, H)
        end = run[-1]                           # (H,)
        cb = jnp.dot(c_, b_.T, precision=hi)    # (sub, sub): C_i . B_j
        L = jnp.where(tril, jnp.exp(jnp.where(tril, run[:, None] - run[None], 0.0)), 0.0)
        y = jnp.einsum("ijh,jhp->ihp", cb[..., None] * L, x_, precision=hi) + \
            jnp.exp(run)[..., None] * jnp.einsum("in,hpn->ihp", c_, S, precision=hi)
        w = jnp.exp(end[None] - run)[..., None]  # (sub, H, 1)
        S = jnp.exp(end)[:, None, None] * S + jnp.einsum("jhp,jn->hpn", x_ * w, b_,
                                                         precision=hi)
        return S, y

    def split(v):
        return v.reshape(n, sub, *v.shape[1:])

    S, y = jax.lax.scan(sub_chunk, s0, (split(xdt), split(a), split(B), split(C)))
    return y.reshape(n * sub, H, P)[:T], S


def _chunk_kernel(valid_ref,                 # scalar prefetch: (1,) int32
                  xdt_ref,                   # (sub, Gb * 128): delta x, this block's heads
                  acol_ref,                  # (sub, H): running sums, every head
                  arow_ref,                  # (Gb * pack, sub): running sums, transposed
                  b_ref, c_ref,              # (sub, N)
                  s0_ref,                    # (G, N, 128): read once
                  y_ref,                     # (sub, Gb * 128)
                  s_ref,                     # (G, N, 128): the state, whole chunk
                  cb_ref, bt_ref,
                  *, sub: int, groups: int, pack: int, P: int):
    from jax.experimental import pallas as pl

    s, k = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (k == 0))
    def _load():
        s_ref[...] = s0_ref[...]

    live = s * sub < valid_ref[0]

    @pl.when(live & (k == 0))
    def _shared():  # what every head of the sub-chunk shares: once
        b = b_ref[...]
        cb_ref[...] = jax.lax.dot_general(c_ref[...], b, (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        bt_ref[...] = jnp.transpose(b)

    @pl.when(live)
    def _heads():
        H = acol_ref.shape[1]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, (sub, H), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        below = rows >= cols
        owner = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // P  # head in a row
        acol, cb, c, bt = acol_ref[...], cb_ref[...], c_ref[...], bt_ref[...]
        for g in range(groups):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            x = xdt_ref[:, lanes]                         # (sub, 128)
            row_of_state = k * groups + g
            S = s_ref[row_of_state]                       # (N, 128)
            y = jnp.zeros((sub, _LANES), jnp.float32)
            into = jnp.zeros((sub, _LANES), jnp.float32)  # exp(A_i), by lane's head
            weight = jnp.zeros((sub, _LANES), jnp.float32)
            decay = jnp.zeros((1, _LANES), jnp.float32)
            for i in range(pack):
                head = row_of_state * pack + i
                col = jnp.sum(jnp.where(head_lane == head, acol, 0.0), axis=1,
                              keepdims=True)              # (sub, 1): A_i
                row = arow_ref[pl.ds(g * pack + i, 1), :]  # (1, sub): A_j
                end = jnp.min(row, axis=1, keepdims=True)  # (1, 1): non-increasing
                L = jnp.where(below, jnp.exp(jnp.where(below, col - row, 0.0)), 0.0)
                mine = owner == i
                y = jnp.where(mine, jnp.dot(cb * L, x, preferred_element_type=jnp.float32),
                              y)
                into = jnp.where(mine, jnp.exp(col), into)
                weight = jnp.where(mine, jnp.exp(end - col), weight)
                decay = jnp.where(mine, jnp.exp(end), decay)
            y_ref[:, lanes] = y + into * jnp.dot(c, S, preferred_element_type=jnp.float32)
            s_ref[row_of_state] = decay * S + jnp.dot(bt, x * weight, precision=_EXACT,
                                                      preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(live))
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("P", "sub", "interpret"))
def pallas_ssd_chunk(xdt, acol, B, C, s0, valid, P: int, sub: int = SUB_CHUNK,
                     interpret: bool = False):
    """The chunk kernel. ``xdt``: (T, H P) float32, ``delta x`` with heads
    in order; ``acol``: (T, H) float32, each sub-chunk's running sums of
    ``delta a``; ``B``, ``C``: (T, N) float32; ``s0``: (G, N, 128) in the
    state's layout; ``valid``: scalar. ``T`` a multiple of ``sub`` (or less
    than it: one sub-chunk). Returns ``y`` (T, H P) float32 (zeros in
    sub-chunks past ``valid``) and the state."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H = acol.shape
    G, N, width = s0.shape
    pack = width // P
    sub = min(sub, T)
    groups = _CHUNK_GROUPS
    heads = groups * pack
    wide = groups * _LANES
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub, groups=groups, pack=pack, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // sub, G // groups),
            in_specs=[
                pl.BlockSpec((sub, wide), lambda s, k, *_: (s, k)),
                pl.BlockSpec((sub, H), lambda s, k, *_: (s, 0)),
                pl.BlockSpec((heads, sub), lambda s, k, *_: (k, s)),
                pl.BlockSpec((sub, N), lambda s, k, *_: (s, 0)),
                pl.BlockSpec((sub, N), lambda s, k, *_: (s, 0)),
                pl.BlockSpec((G, N, width), lambda s, k, *_: (0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((sub, wide), lambda s, k, *_: (s, k)),
                pl.BlockSpec((G, N, width), lambda s, k, *_: (0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((sub, sub), jnp.float32),
                            pltpu.VMEM((N, sub), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((T, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((G, N, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=48 << 20),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ssd_chunk",
    )(jnp.reshape(valid, (1,)).astype(jnp.int32), xdt, acol, acol.T, B, C, s0)


@functools.partial(jax.jit, static_argnames=("body",))
def ssd_chunk(x, delta, A, B, C, s0, valid=None, body: str = "ssd_xla"):
    """Chunk form of one sequence. ``x``: (T, H, P); ``delta``: (T, H), the
    steps after their softplus; ``A``: (H,), each ``a_h``; ``B``, ``C``:
    (T, N); ``s0``: the state in its layout (``state_shape``); ``valid``:
    None, or a scalar, the real tokens (the rest right-padding). Returns
    ``(y, S)``: (T, H, P) float32 without the skip, and the state after
    ``valid`` tokens. ``body`` is one of ``SSD_BODIES`` (``ssd_body``)."""
    if body not in SSD_BODIES:
        raise ValueError(f"unknown SSD body {body!r}; expected one of {SSD_BODIES}")
    T, H, P = x.shape
    valid = jnp.int32(T) if valid is None else jnp.asarray(valid, jnp.int32)
    B, C = B.astype(jnp.float32), C.astype(jnp.float32)
    with jax.named_scope("ssd_chunk"):
        xdt, a = _inputs(x, delta, A, valid)
        sub = min(SUB_CHUNK, T)
        if body == "ssd_pallas":
            y, S = pallas_ssd_chunk(xdt.reshape(T, H * P), _running_sums(a, sub), B, C,
                                    s0, valid, P)
            return y.reshape(T, H, P), S
        y, S = _chunk_xla(xdt, a, B, C, unpack_state(s0, P), sub)
        return y, pack_state(S)


def _step_xla(x, delta, A, B, C, state, active):
    """(S, H, P), (S, H), (H,), (S, N) x2, (S, G, N, W), (S,)."""
    lanes, H, P = x.shape
    G, N, W = state.shape[1:]
    delta = delta.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * delta[..., None]).reshape(lanes, G, 1, W)
    decay = jnp.repeat(jnp.exp(delta * A.astype(jnp.float32)), P, axis=1).reshape(
        lanes, G, 1, W)
    new = decay * state + B.astype(jnp.float32)[:, None, :, None] * xdt
    new = jnp.where(active[:, None, None, None], new, state)
    y = (new * C.astype(jnp.float32)[:, None, :, None]).sum(2)
    return y.reshape(lanes, H, P), new


def _step_kernel(order_ref, count_ref,      # scalar prefetch: lanes, active first
                 xdt_ref, decay_ref,        # (1, Gb * W) a lane
                 b_ref, c_ref,              # (N, 1) a lane
                 s_in_ref,                  # (Gb, N, W)
                 y_ref,                     # (1, Gb * W)
                 s_ref,                     # (Gb, N, W), aliased to the input
                 *, groups: int, width: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    count = count_ref[0]

    @pl.when(i < count)
    def _step():
        b, c = b_ref[...], c_ref[...]
        for g in range(groups):
            lanes = slice(g * width, (g + 1) * width)
            S = decay_ref[:, lanes] * s_in_ref[g] + b * xdt_ref[:, lanes]
            s_ref[g] = S
            y_ref[:, lanes] = jnp.sum(S * c, axis=0, keepdims=True)

    @pl.when((count == 0) & (i == 0))
    def _idle():  # no lane is active: the one block visited goes back as it came
        s_ref[...] = s_in_ref[...]


@functools.partial(jax.jit, static_argnames=("P", "interpret"))
def pallas_ssd_step(xdt, decay, B, C, state, active, P: int, interpret: bool = False):
    """The step kernel. ``xdt``: (S, H P) float32, ``delta x``; ``decay``:
    (S, H P) float32, each head's ``exp(delta a)`` over its P lanes; ``B``,
    ``C``: (S, N) float32; ``state``: (S, G, N, W) in the state's layout;
    ``active``: (S,) bool. The grid walks the active lanes first; a step
    past the last of them visits that lane's block again, which is neither
    read nor written again. Returns ``y`` (S, H P), read only at the active
    lanes, and the state, in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, G, N, W = state.shape
    groups = max(1, min(G, _STEP_BLOCK // (N * W * 4)))
    while G % groups:
        groups -= 1
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    count = active.sum().astype(jnp.int32)[None]

    def lane(k, i, order, count):
        return order[jnp.minimum(i, jnp.maximum(count[0] - 1, 0))]

    row = pl.BlockSpec((None, 1, groups * W), lambda k, i, o, c: (lane(k, i, o, c), 0, k))
    col = pl.BlockSpec((None, N, 1), lambda k, i, o, c: (lane(k, i, o, c), 0, 0))
    block = pl.BlockSpec((None, groups, N, W), lambda k, i, o, c: (lane(k, i, o, c), k, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, groups=groups, width=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G // groups, lanes),
            in_specs=[row, row, col, col, block],
            out_specs=[row, block],
        ),
        out_shape=[jax.ShapeDtypeStruct((lanes, 1, G * W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=32 << 20),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ssd_step",
    )(order, count, xdt[:, None], decay[:, None], B[..., None], C[..., None], state)
    return y[:, 0], state


def ssd_step(x, delta, A, B, C, state, active, body: str = "ssd_xla"):
    """One token a lane: ``x`` (S, H, P); ``delta`` (S, H); ``A`` (H,);
    ``B``, ``C`` (S, N); ``state`` (S, G, N, W) float32 in the state's
    layout; ``active`` (S,) bool. Returns ``(y, state)``: (S, H, P) float32
    without the skip, and the state with the active lanes stepped, the
    others as they were."""
    if body not in SSD_BODIES:
        raise ValueError(f"unknown SSD body {body!r}; expected one of {SSD_BODIES}")
    with jax.named_scope("ssd_step"):
        if body == "ssd_pallas":
            lanes, H, P = x.shape
            delta = delta.astype(jnp.float32)
            xdt = (x.astype(jnp.float32) * delta[..., None]).reshape(lanes, H * P)
            decay = jnp.repeat(jnp.exp(delta * A.astype(jnp.float32)), P, axis=1)
            y, state = pallas_ssd_step(xdt, decay, B.astype(jnp.float32),
                                       C.astype(jnp.float32), state, active, P)
            return y.reshape(lanes, H, P), state
        return _step_xla(x, delta, A, B, C, state, active)
