"""Lightning attention: linear attention with a fixed decay a head.

Per head ``j`` with decay ``lambda_j`` in (0, 1), a state ``S`` of (D, D)
in float32::

    S_t = lambda_j S_{t-1} + k_t^T v_t
    o_t = q_t S_t          i.e. o_t = sum_{s <= t} lambda_j^(t - s) (q_t . k_s) v_s

(the caller scales ``q`` by its softmax-free scale first). The state is
what a serving slot keeps of such a layer: ``(heads, D, D)`` a slot, 2 MiB
at 32 heads of 128, whatever the sequence's length.

``lightning_chunk`` is the chunk form: ``T`` tokens from an initial state,
of which the first ``valid`` are real. It walks sub-chunks of ``sub``
tokens: inside one, ``((Q K^T) * D) V`` with ``D[i, j] = lambda^(i - j)``
below the diagonal; across them ``(Q * lambda^(i + 1)) S``; then the state
moves on by ``lambda^sub S + (K * lambda^(sub - 1 - j))^T V``. Decay powers
are taken in log space, in float32: a count of steps times ``log lambda``,
so that a power that underflows is 0 and nothing overflows. A token at or
past ``valid`` is no step at all: its key is zero and it decays nothing,
so the state returned is the state after ``valid`` tokens whatever the
padding holds (its outputs are finite and nobody reads them).
``lightning_step`` is the one-token form of a decode step, for the lanes
an ``active`` mask marks; the other rows come back as they were.

The chunk form has two bodies with the same arithmetic: ``lightning_xla``
(the CPU's, and the kernel's reference) and ``lightning_pallas``, a kernel
for one TPU that holds a head's state in VMEM across the chunk's
sub-chunks. The decode step is XLA's alone: it reads and writes each
lane's state once, and a kernel measured no faster on a v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LIGHTNING_BODIES = ("lightning_pallas", "lightning_xla")

# Tokens a sub-chunk of the chunk form.
SUB_CHUNK = 256


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lightning_body(T: int, D: int) -> str:
    """Name of the chunk form's body for this backend and these shapes: the
    kernel takes heads of a whole lane width and a chunk of whole
    sub-chunks."""
    if _on_tpu() and D % 128 == 0 and T % SUB_CHUNK == 0:
        return "lightning_pallas"
    return "lightning_xla"


def _steps(pos, valid):
    """Decay steps taken up to and including each position: one a real
    token, none a padding one."""
    return jnp.minimum(pos + 1, valid).astype(jnp.float32)


def _chunk_xla(q, k, v, log_decay, s0, valid, sub: int):
    """(H, T, D) x3, (H,), (H, D, D), scalar -> (H, T, D), (H, D, D)."""
    H, T, D = q.shape
    n = -(-T // sub)
    pad = ((0, 0), (0, n * sub - T), (0, 0))
    q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    pos = jnp.arange(n * sub)
    k = jnp.where((pos < valid)[None, :, None], k, 0.0)
    a = _steps(pos, valid)[None, :] * log_decay[:, None]  # (H, n * sub)
    base = jnp.minimum(jnp.arange(n) * sub, valid).astype(jnp.float32)
    tril = jnp.tril(jnp.ones((sub, sub), bool))

    def sub_chunk(S, xs):
        q_, k_, v_, a_, b_ = xs  # (H, sub, D) x3, (H, sub), ()
        b_ = b_ * log_decay      # (H,)
        last = a_[:, -1]
        diff = a_[:, :, None] - a_[:, None, :]
        w = jnp.where(tril, jnp.exp(jnp.where(tril, diff, 0.0)), 0.0)
        s = jnp.einsum("hid,hjd->hij", q_, k_, preferred_element_type=jnp.float32) * w
        o = jnp.einsum("hij,hjd->hid", s, v_, preferred_element_type=jnp.float32) + \
            jnp.einsum("hid,hde->hie", q_ * jnp.exp(a_ - b_[:, None])[..., None], S,
                       preferred_element_type=jnp.float32)
        kw = k_ * jnp.exp(last[:, None] - a_)[..., None]
        S = jnp.exp(last - b_)[:, None, None] * S + jnp.einsum(
            "hjd,hje->hde", kw, v_, preferred_element_type=jnp.float32)
        return S, o

    def split(x):  # (H, n * sub, ...) -> (n, H, sub, ...)
        return jnp.moveaxis(x.reshape(H, n, sub, *x.shape[2:]), 1, 0)

    S, o = jax.lax.scan(sub_chunk, s0, (split(q), split(k), split(v), split(a), base))
    return jnp.moveaxis(o, 0, 1).reshape(H, n * sub, D)[:, :T], S


def _chunk_kernel(valid_ref, decay_ref,  # scalar prefetch: (1,) int32, (H,) float32
                  q_ref, k_ref, v_ref,   # (1, T, D)
                  s0_ref,                # (1, D, D)
                  o_ref,                 # (1, T, D)
                  s_ref,                 # (1, D, D)
                  *, sub: int):
    from jax.experimental import pallas as pl

    ld = decay_ref[pl.program_id(0)]
    valid = valid_ref[0]
    T = q_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    below = rows >= cols
    at = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    at_row = jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1)

    def sub_chunk(c, S):
        off = pl.multiple_of(c * sub, sub)
        q = q_ref[0, pl.ds(off, sub), :]
        k = k_ref[0, pl.ds(off, sub), :]
        v = v_ref[0, pl.ds(off, sub), :]
        k = jnp.where(off + at < valid, k, 0.0)
        a = _steps(off + at, valid) * ld               # (sub, 1)
        a_row = _steps(off + at_row, valid) * ld       # (1, sub)
        b = jnp.minimum(off, valid).astype(jnp.float32) * ld
        last = jnp.minimum(off + sub, valid).astype(jnp.float32) * ld
        w = jnp.where(below, jnp.exp(jnp.where(below, a - a_row, 0.0)), 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * w
        o = jnp.dot(s, v, preferred_element_type=jnp.float32) + jnp.dot(
            q * jnp.exp(a - b), S, preferred_element_type=jnp.float32)
        o_ref[0, pl.ds(off, sub), :] = o
        kw = jnp.transpose(k * jnp.exp(last - a))
        return jnp.exp(last - b) * S + jnp.dot(kw, v, preferred_element_type=jnp.float32)

    s_ref[0] = jax.lax.fori_loop(0, T // sub, sub_chunk, s0_ref[0])


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def pallas_lightning_chunk(q, k, v, log_decay, s0, valid, sub: int = SUB_CHUNK,
                           interpret: bool = False):
    """The chunk kernel. Shapes as ``_chunk_xla``, all float32; ``T`` a
    multiple of ``sub`` (or less than it: one sub-chunk). A grid step is one head: its state stays in VMEM
    from the first sub-chunk to the last, and its queries, keys and values
    are read once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, T, D = q.shape
    sub = min(sub, T)
    per_head = pl.BlockSpec((1, T, D), lambda h, *_: (h, 0, 0))
    state = pl.BlockSpec((1, D, D), lambda h, *_: (h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H,),
            in_specs=[per_head, per_head, per_head, state],
            out_specs=[per_head, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((H, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((H, D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="lightning_chunk",
    )(jnp.reshape(valid, (1,)).astype(jnp.int32), log_decay.astype(jnp.float32),
      q, k, v, s0)


@functools.partial(jax.jit, static_argnames=("body",))
def lightning_chunk(q, k, v, log_decay, s0, valid=None, body: str = "lightning_xla"):
    """Chunk form. ``q``, ``k``, ``v``: (H, T, D); ``log_decay``: (H,), each
    ``log lambda_j``; ``s0``: (H, D, D); ``valid``: None, or a scalar, the
    real tokens (the rest right-padding). Returns ``(o, S)``: (H, T, D) and
    the state after ``valid`` tokens, float32. ``body`` is one of
    ``LIGHTNING_BODIES`` (``lightning_body``)."""
    if body not in LIGHTNING_BODIES:
        raise ValueError(f"unknown lightning body {body!r}; expected one of "
                         f"{LIGHTNING_BODIES}")
    q, k, v, log_decay, s0 = (jnp.asarray(x, jnp.float32)
                              for x in (q, k, v, log_decay, s0))
    valid = jnp.int32(q.shape[1]) if valid is None else jnp.asarray(valid, jnp.int32)
    with jax.named_scope("lightning_chunk"):
        if body == "lightning_pallas":
            return pallas_lightning_chunk(q, k, v, log_decay, s0, valid)
        return _chunk_xla(q, k, v, log_decay, s0, valid, min(SUB_CHUNK, q.shape[1]))


def _step_xla(q, k, v, decay, state, active):
    """(S, H, D) x3, (H,), (S, H, D, D), (S,) -> (S, H, D), (S, H, D, D)."""
    new = decay[None, :, None, None] * state + k[..., :, None] * v[..., None, :]
    new = jnp.where(active[:, None, None, None], new, state)
    return jnp.einsum("shd,shde->she", q, new, preferred_element_type=jnp.float32), new


def lightning_step(q, k, v, decay, state, active):
    """One token a lane: ``q``, ``k``, ``v`` (S, H, D); ``decay``: (H,), each
    ``lambda_j``; ``state``: (S, H, D, D) float32; ``active``: (S,) bool.
    Returns ``(o, state)``: (S, H, D) float32 and the state with the active
    lanes stepped, the others as they were."""
    q, k, v, decay = (jnp.asarray(x, jnp.float32) for x in (q, k, v, decay))
    with jax.named_scope("lightning_step"):
        return _step_xla(q, k, v, decay, state, active)
