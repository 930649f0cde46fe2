"""Long-context attention bench: Pallas flash kernels vs the XLA
blockwise fallback, fwd+bwd, on the real chip (SURVEY.md §5.7 upgrade).

Emits one JSON line per (seq_len, impl) with ms/step and achieved
throughput so the speedup is a committed artifact rather than something
each reviewer re-measures (r2 VERDICT verified 2.05x at seq 8192 by
hand — this script reproduces that table).

Usage: python scripts/attention_bench.py [--seqs 2048 4096 8192] [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def build(seq: int, impl: str, heads: int = 8, dim: int = 64, batch: int = 1):
    from elephas_tpu.ops import attention as attn

    def loss_fn(q, k, v):
        # 'pallas'/'xla_custom_vjp' force their kernel through the SHIPPED
        # custom-VJP path regardless of the public API's pallas_min_seq
        # dispatch (this script MEASURES the crossover that dispatch
        # encodes, so both arms must be what production actually runs);
        # 'xla_autodiff' is the plain-autodiff lower bound for context.
        import unittest.mock as mock

        from elephas_tpu.ops.attention_pallas import default_blocks

        bq, bk = default_blocks(q.shape[2])  # the SHIPPED per-length tiling
        if impl == "pallas":
            with mock.patch.object(attn, "_use_pallas", lambda q_: True):
                out = attn._flash(q, k, v, True, bq, bk)
        elif impl == "xla_custom_vjp":
            with mock.patch.object(attn, "_use_pallas", lambda q_: False):
                out = attn._flash(q, k, v, True, bq, bk)
        else:
            out = attn._blockwise_reference(q, k, v, True, bq, bk)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
    rng = np.random.default_rng(0)
    shape = (batch, heads, seq, dim)
    q, k, v = (
        jax.device_put(rng.normal(size=shape).astype(np.float32).astype(jnp.bfloat16))
        for _ in range(3)
    )
    return grad_fn, (q, k, v)


def build_ring(tokens_per_shard: int, impl: str, heads: int = 8, dim: int = 64,
               batch: int = 1):
    """Ring arm (VERDICT r3 #4): dense-hop vs flash-hop ring attention at
    a given tokens/shard, fwd+bwd through the shipped custom-VJP path.
    On this 1-chip env the seq axis is size 1 — the ring degenerates to
    its per-hop kernel, which is exactly what the dense-vs-flash hop
    comparison measures (rotation is ICI traffic either way)."""
    from jax.sharding import PartitionSpec as P

    from elephas_tpu.parallel.mesh import SEQ_AXIS, build_mesh
    from elephas_tpu.parallel.ring_attention import ring_attention

    n_seq = 1  # all local devices on the seq axis would also work; bench 1
    mesh = build_mesh(num_data=1, num_seq=n_seq)
    spec = P(None, None, SEQ_AXIS, None)

    def body(q_, k_, v_):
        out = ring_attention(q_, k_, v_, axis_name=SEQ_AXIS, causal=True,
                             impl=impl)
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), SEQ_AXIS)

    loss_fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
        check_vma=False,
    )
    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
    rng = np.random.default_rng(0)
    shape = (batch, heads, tokens_per_shard * n_seq, dim)
    q, k, v = (
        jax.device_put(rng.normal(size=shape).astype(np.float32).astype(jnp.bfloat16))
        for _ in range(3)
    )
    return grad_fn, (q, k, v)


def measure(fn, args, steps: int, warmup: int = 3) -> float:
    for _ in range(warmup):
        loss, grads = fn(*args)
    float(loss)  # a scalar fetch forces the chain
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, grads = fn(*args)
    float(loss)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="*", default=[2048, 4096, 8192])
    ap.add_argument("--dims", type=int, nargs="*", default=[64],
                    help="head_dims to sweep (the crossover is "
                         "shape-dependent — ops.attention.pallas_min_seq)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--impls", nargs="*",
                    default=["xla_autodiff", "xla_custom_vjp", "pallas"])
    ap.add_argument("--ring", action="store_true",
                    help="bench the ring arms (dense-hop vs flash-hop) "
                         "at --seqs tokens/shard instead of the "
                         "single-device kernels")
    args = ap.parse_args()

    print(f"devices={jax.devices()}", file=sys.stderr)
    if args.ring:
        by_seq = {}
        for seq in args.seqs:
            for impl in ("dense", "flash"):
                fn, data = build_ring(seq, impl)
                sec = measure(fn, data, args.steps)
                by_seq.setdefault(seq, {})[impl] = sec
                print(json.dumps({
                    "tokens_per_shard": seq, "ring_impl": impl,
                    "fwd_bwd_ms": round(sec * 1e3, 2),
                }), flush=True)
                del fn, data
        for seq, r in by_seq.items():
            print(json.dumps({
                "tokens_per_shard": seq,
                "speedup_flash_ring_vs_dense_ring": round(
                    r["dense"] / r["flash"], 2
                ),
            }), flush=True)
        return
    for dim in args.dims:
        by_seq = {}
        for seq in args.seqs:
            for impl in args.impls:
                fn, data = build(seq, impl, dim=dim)
                sec = measure(fn, data, args.steps)
                by_seq.setdefault(seq, {})[impl] = sec
                print(json.dumps({
                    "seq": seq, "head_dim": dim, "impl": impl,
                    "fwd_bwd_ms": round(sec * 1e3, 2),
                }), flush=True)
                del fn, data
        for seq, r in by_seq.items():
            # The threshold decision compares the two SHIPPED paths.
            if "xla_custom_vjp" in r and "pallas" in r:
                print(json.dumps({
                    "seq": seq, "head_dim": dim,
                    "speedup_pallas_vs_xla_custom_vjp": round(
                        r["xla_custom_vjp"] / r["pallas"], 2
                    ),
                }), flush=True)


if __name__ == "__main__":
    main()
