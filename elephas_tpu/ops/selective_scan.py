"""Selective scan: the recurrence of a Mamba-1 mixer.

Per channel ``c`` of ``d`` and state ``n`` of ``N``, in float32::

    h[t, n, c] = exp(delta[t, c] * A[n, c]) * h[t-1, n, c]
                 + delta[t, c] * B[t, n] * u[t, c]
    y[t, c]    = sum_n C[t, n] * h[t, n, c] + D[c] * u[t, c]

The state is laid out ``(N, d)``, channels minor: ``d`` is a multiple of
the TPU's 128 lanes where ``N`` (16) is not, so neither the pool's state
rows nor the kernel's registers are padded.

``selective_scan`` is the chunk form: ``T`` tokens from an initial state,
of which the first ``valid`` are real. A step past ``valid`` has its
``delta`` set to 0, which holds the state (``exp(0) = 1``, nothing added),
so the state returned is the state after ``valid`` tokens whatever the
padding holds. ``selective_scan_step`` is the one-token form of a decode
step. Every step of the recurrence is computed; nothing of the state's
history is truncated.

Two bodies with the same arithmetic. ``scan_xla`` is a ``lax.scan`` over
tokens, the recurrence as written: the CPU's body, the body of shapes the
kernel does not take, and the kernel's reference. ``scan_pallas`` is a
kernel for one TPU that keeps ``h`` in registers, a block of 1,024
channels as sixteen ``(8, 128)`` tiles, and walks the tokens in order:
``exp(delta A)`` and ``delta B u``, ``T x d x N`` values each (336 MB a
layer for a chunk of 512 at ``d`` 5,120), are never written anywhere.
``B`` and ``C`` are scalars a (token, state) and are read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SCAN_BODIES = ("scan_pallas", "scan_xla")

_CHANNELS = 1024      # one (8, 128) float32 tile a state
_TIME_BLOCK = 128     # tokens a grid step
_SMEM_FLOATS = 1 << 15  # B and C together, the whole call


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_fits(batch: int, T: int, d: int, n: int) -> bool:
    return (d % _CHANNELS == 0 and T % _TIME_BLOCK == 0
            and 2 * batch * T * n <= _SMEM_FLOATS)


def scan_body(batch: int, T: int, d: int, n: int, mesh=None) -> str:
    """Name of the body ``selective_scan`` runs for this backend and
    shape. A Pallas call is not partitioned by sharding annotations, so a
    mesh takes the XLA body."""
    if _on_tpu() and mesh is None and _pallas_fits(batch, T, d, n):
        return "scan_pallas"
    return "scan_xla"


def _scan_xla(u, delta, A, B, C, D, h0):
    """(b, T, d), (b, T, d), (N, d), (b, T, N), (b, T, N), (d,), (b, N, d)."""

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs  # (b, d), (b, d), (b, N), (b, N)
        h = jnp.exp(dt_t[:, None, :] * A) * h + \
            (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, (c_t[:, :, None] * h).sum(1) + D * u_t

    time_major = [jnp.moveaxis(x, 1, 0) for x in (u, delta, B, C)]
    h, y = jax.lax.scan(step, h0, time_major)
    return jnp.moveaxis(y, 0, 1), h


def _scan_kernel(b_ref, c_ref,  # scalar prefetch (SMEM): (b * T * N,) each
                 u_ref, dt_ref,  # (Tb, 8, 128)
                 a_ref, h0_ref,  # (N, 8, 128)
                 d_ref,  # (8, 128)
                 y_ref,  # (Tb, 8, 128)
                 h_ref,  # (N, 8, 128): the carry between time blocks, and the result
                 *, n: int, T: int, unroll: int):
    from jax.experimental import pallas as pl

    tb = pl.program_id(2)
    block = u_ref.shape[0]

    @pl.when(tb == 0)
    def _first():
        h_ref[...] = h0_ref[...]

    base = (pl.program_id(0) * T + tb * block) * n
    skip = d_ref[...]

    def step(t, h):
        dt, x = dt_ref[t], u_ref[t]
        dtx, y, at = dt * x, skip * x, base + t * n
        out = []
        for k in range(n):
            hk = jnp.exp(dt * a_ref[k]) * h[k] + dtx * b_ref[at + k]
            y = y + hk * c_ref[at + k]
            out.append(hk)
        y_ref[t] = y
        return tuple(out)

    def steps(i, h):  # Mosaic unrolls a loop wholly or not at all
        for j in range(unroll):
            h = step(i * unroll + j, h)
        return h

    h = jax.lax.fori_loop(0, block // unroll, steps,
                          tuple(h_ref[k] for k in range(n)))
    for k in range(n):
        h_ref[k] = h[k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_selective_scan(u, delta, A, B, C, D, h0, interpret: bool = False):
    """The kernel. Shapes as ``_scan_xla``, all float32; ``d`` a multiple
    of 1,024 and ``T`` of 128 (``_pallas_fits``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, T, d = u.shape
    n = A.shape[0]
    tiles = d // 128

    def tiled(x):  # (..., d) -> (..., d / 128, 128): a channel block is whole tiles
        return x.reshape(*x.shape[:-1], tiles, 128)

    per_token = pl.BlockSpec((None, _TIME_BLOCK, 8, 128),
                             lambda b, j, t, *_: (b, t, j, 0))
    per_state = pl.BlockSpec((n, 8, 128), lambda b, j, t, *_: (0, j, 0))
    state = pl.BlockSpec((None, n, 8, 128), lambda b, j, t, *_: (b, 0, j, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n=n, T=T, unroll=4),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, d // _CHANNELS, T // _TIME_BLOCK),
            in_specs=[per_token, per_token, per_state, state,
                      pl.BlockSpec((8, 128), lambda b, j, t, *_: (j, 0))],
            out_specs=[per_token, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((batch, T, tiles, 128), jnp.float32),
                   jax.ShapeDtypeStruct((batch, n, tiles, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="selective_scan",
    )(B.reshape(-1), C.reshape(-1), tiled(u), tiled(delta), tiled(A),
      tiled(h0), tiled(D))
    return y.reshape(batch, T, d), h.reshape(batch, n, d)


@functools.partial(jax.jit, static_argnames=("body",))
def selective_scan(u, delta, A, B, C, D, h0, valid=None, body: str = "scan_xla"):
    """Chunk form. ``u``, ``delta``: (b, T, d); ``A``: (N, d), negative;
    ``B``, ``C``: (b, T, N); ``D``: (d,); ``h0``: (b, N, d); ``valid``:
    None, or (b,) the real tokens of each row (the rest is right-padding).
    Returns ``(y, h)``: (b, T, d) and the state after ``valid`` tokens,
    both float32. ``body`` is one of ``SCAN_BODIES`` (``scan_body``)."""
    if body not in SCAN_BODIES:
        raise ValueError(f"unknown scan body {body!r}; expected one of {SCAN_BODIES}")
    f32 = [jnp.asarray(x, jnp.float32) for x in (u, delta, A, B, C, D, h0)]
    if valid is not None:
        live = jnp.arange(u.shape[1])[None, :] < jnp.asarray(valid)[:, None]
        f32[1] = jnp.where(live[:, :, None], f32[1], 0.0)
    with jax.named_scope("selective_scan"):
        if body == "scan_pallas":
            return pallas_selective_scan(*f32)
        return _scan_xla(*f32)


def selective_scan_step(u, delta, A, B, C, D, h):
    """One token a row: ``u``, ``delta`` (b, d); ``B``, ``C`` (b, N);
    ``h`` (b, N, d). Returns ``(y, h)``, float32."""
    u, delta, A, B, C, D, h = (jnp.asarray(x, jnp.float32)
                               for x in (u, delta, A, B, C, D, h))
    h = jnp.exp(delta[:, None, :] * A) * h + (delta * u)[:, None, :] * B[:, :, None]
    return (C[:, :, None] * h).sum(1) + D * u, h
