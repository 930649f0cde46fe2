"""Hybrid decoder LM: Mamba-1 mixers with an attention layer every few
layers, the block of AI21's Jamba family.

Layer ``i`` is attention where ``i % attn_period == attn_offset`` and a
Mamba-1 mixer elsewhere. Every layer is ``x + mixer(rms(x))`` then
``x + mlp(rms(x))`` with RMSNorm and a gated feed-forward
``down(silu(gate y) * (up y))``. Attention has ``num_heads`` query heads
on ``num_kv_heads`` K/V heads, no bias and **no positions of any kind**
(the mixers carry order). The logits go through the tied embedding.

Mixer (``d_inner = expand * d_model``)::

    [u, z]     = in_proj y                       (no bias)
    u          = silu(causal depthwise conv1d(u) + b_conv)
    [dt, B, C] = x_proj u                        (dt_rank, d_state, d_state)
    dt, B, C   = rms(dt), rms(B), rms(C)         (Jamba's three inner norms)
    delta      = softplus(dt_proj dt + b_dt)
    y          = selective_scan(u, delta, -exp(A_log), B, C, D)   (float32)
    out        = out_proj (y * silu(z))

``A_log`` is kept ``(d_state, d_inner)``, channels minor, the layout of the
state (``ops.selective_scan``).

``decode=True`` is the serving path. Its ``cache`` collection holds, for an
attention layer, ``cached_key`` / ``cached_value`` / ``cache_index`` as
``TransformerLM``'s do (with ``num_kv_heads`` heads), and for a mixer a
row a batch element of ``conv_state`` (the last ``d_conv - 1`` inputs of
the convolution) and of ``ssm_state`` (float32). An apply continues from
the state it is given and leaves the state after its tokens: after
``valid`` of them where ``valid`` says that the rest is right-padding
(padding run through a recurrence is not invisible, as padding K/V past
the cache index is), and unchanged for the rows that ``active`` masks
out. Given ``valid``, the apply also returns the logits of position
``valid - 1`` alone, ``(batch, 1, vocab)``: the one row a prefill chunk
samples from (the published configuration's ``num_logits_to_keep: 1``).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models import register_model
from elephas_tpu.models.decode_cache import attend_paged


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + self.eps) * scale


def gated_feed_forward(y, d_ff: int, dtype):
    """``down(silu(gate y) * (up y))``, no bias; the three ``Dense`` layers
    are the calling module's own (``gate``, ``up``, ``down``)."""
    gate = nn.Dense(d_ff, use_bias=False, dtype=dtype, name="gate")(y)
    up = nn.Dense(d_ff, use_bias=False, dtype=dtype, name="up")(y)
    return nn.Dense(y.shape[-1], use_bias=False, dtype=dtype,
                    name="down")(nn.silu(gate) * up)


def _a_log_init(key, shape):
    """Mamba's: ``A = -(1 .. d_state)`` for every channel."""
    del key
    n, d = shape
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                            (n, d))


def _rows(x, at, size: int):
    """``x[b, at[b] : at[b] + size]`` for every row ``b``."""
    return jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(row, i, size, 0))(x, at)


class MambaMixer(nn.Module):
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, valid=None):
        from elephas_tpu.ops.selective_scan import (
            scan_body,
            selective_scan,
            selective_scan_step,
        )

        b, T, _ = x.shape
        d, n, taps = self.d_inner, self.d_state, self.d_conv
        uz = nn.Dense(2 * d, use_bias=False, dtype=self.dtype, name="in_proj")(x)
        u, z = uz[..., :d], uz[..., d:]
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.02), (taps, d))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (d,))
        a_log = self.param("A_log", _a_log_init, (n, d))
        skip = self.param("D", nn.initializers.ones, (d,))

        carried = self.decode and self.has_variable("cache", "ssm_state")
        if self.decode:
            conv_state = self.variable("cache", "conv_state", jnp.zeros,
                                       (b, taps - 1, d), self.dtype)
            ssm_state = self.variable("cache", "ssm_state", jnp.zeros,
                                      (b, n, d), jnp.float32)
        if carried:
            history, h0 = conv_state.value, ssm_state.value
        else:  # a full sequence, or the pass that shapes the cache
            history = jnp.zeros((b, taps - 1, d), u.dtype)
            h0 = jnp.zeros((b, n, d), jnp.float32)

        with jax.named_scope("conv"):
            xs = jnp.concatenate([history.astype(u.dtype), u], axis=1)
            conv = sum(conv_w[k].astype(jnp.float32) * xs[:, k:k + T]
                       for k in range(taps)) + conv_b
            u = nn.silu(conv).astype(self.dtype)
        dbc = nn.Dense(self.dt_rank + 2 * n, use_bias=False, dtype=self.dtype,
                       name="x_proj")(u)
        dt = RMSNorm(self.eps, name="dt_norm")(dbc[..., :self.dt_rank])
        B = RMSNorm(self.eps, name="b_norm")(dbc[..., self.dt_rank:self.dt_rank + n])
        C = RMSNorm(self.eps, name="c_norm")(dbc[..., self.dt_rank + n:])
        dt = nn.Dense(d, dtype=self.dtype, name="dt_proj")(dt.astype(self.dtype))
        with jax.named_scope("softplus"):
            delta = nn.softplus(dt.astype(jnp.float32))
            A = -jnp.exp(a_log.astype(jnp.float32))

        if carried and T == 1 and valid is None:
            y, h = selective_scan_step(u[:, 0], delta[:, 0], A, B[:, 0], C[:, 0],
                                       skip, h0)
            y = y[:, None]
        else:
            # the kernel serves: it has no gradient, and a training mesh
            # would not partition it
            body = scan_body(b, T, d, n) if self.decode else "scan_xla"
            y, h = selective_scan(u, delta, A, B, C, skip, h0, valid, body=body)
        if carried:
            with jax.named_scope("state_write"):
                # the convolution's state: the last inputs ending at valid - 1
                at = jnp.full((b,), T, jnp.int32) if valid is None else valid
                taps_in = _rows(xs, at, taps - 1).astype(self.dtype)
                if active is not None:
                    taps_in = jnp.where(active[:, None, None], taps_in, history)
                    h = jnp.where(active[:, None, None], h, h0)
                conv_state.value, ssm_state.value = taps_in, h
        with jax.named_scope("gate"):
            gated = (y * nn.silu(z.astype(jnp.float32))).astype(self.dtype)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        name="out_proj")(gated)


def grouped_causal_attention(q, k, v, mask=None, scale=None):
    """q: (b, heads, T, hd); k, v: (b, kv_heads, L, hd), ``heads`` a
    multiple of ``kv_heads``; ``mask`` broadcastable to (b, 1, T, L), the
    causal triangle where it is None; ``scale`` the scores', ``hd ** -0.5``
    where it is None. No K/V head is copied."""
    b, heads, T, hd = q.shape
    kv_heads, L = k.shape[1], k.shape[2]
    qg = q.reshape(b, kv_heads, heads // kv_heads, T, hd)
    scores = jnp.einsum("bkgqd,bkld->bkgql", qg, k,
                        preferred_element_type=jnp.float32) * (
                            1.0 / np.sqrt(hd) if scale is None else scale)
    if mask is None:
        mask = jnp.tril(jnp.ones((T, L), bool))[None, None]
    scores = jnp.where(mask[:, :, None], scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bkgql,bkld->bkgqd", nn.softmax(scores, axis=-1).astype(v.dtype),
                     v, preferred_element_type=jnp.float32)
    return out.reshape(b, heads, T, hd).astype(q.dtype)


class GroupedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: Optional[float] = None  # the scores'; head_dim ** -0.5 where None
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None):
        b, T, d_model = x.shape

        def heads(n, name):
            y = nn.DenseGeneral((n, self.head_dim), use_bias=False, dtype=self.dtype,
                                name=name)(x)
            return jnp.transpose(y, (0, 2, 1, 3))  # (b, n, T, hd)

        q, k, v = heads(self.num_heads, "q"), heads(self.num_kv_heads, "k"), \
            heads(self.num_kv_heads, "v")
        if self.decode:
            out = self._cached(q, k, v, active, paged)
        else:
            out = grouped_causal_attention(q, k, v, scale=self.scale)
        out = jnp.transpose(out, (0, 2, 1, 3))
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False, dtype=self.dtype,
                               name="out")(out)

    def _cached(self, q, k, v, active, paged):
        """Attention over the cache, as ``TransformerLM``'s decode path has
        it for the serving pool: rows never left-padded, a write column a
        row (a scalar in a fresh cache), and over the paged pool in place
        where ``paged`` says so."""
        from elephas_tpu.ops.attention import cache_attention_mask

        b, _, T, hd = q.shape
        init_pass = not self.has_variable("cache", "cached_key")
        shape = (b, self.num_kv_heads, T, hd)
        cached_key = self.variable("cache", "cached_key", jnp.zeros, shape, self.dtype)
        cached_value = self.variable("cache", "cached_value", jnp.zeros, shape,
                                     self.dtype)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.array(0, jnp.int32))
        if init_pass:
            return grouped_causal_attention(q, k, v, scale=self.scale)
        idx = cache_index.value
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        if paged is not None:
            return attend_paged(q, k, v, cached_key, cached_value, cache_index,
                                active, paged, scale=self.scale)
        scalar = idx.ndim == 0
        idx = jnp.broadcast_to(idx, (b,))
        write = jax.vmap(lambda cache, new, i: jax.lax.dynamic_update_slice(
            cache, new, (0, i, 0)))
        ck, cv = write(cached_key.value, k, idx), write(cached_value.value, v, idx)
        cached_key.value, cached_value.value = ck, cv
        moved = idx + T if active is None else jnp.where(active, idx + T, idx)
        cache_index.value = moved[0] if scalar else moved
        return grouped_causal_attention(
            q, ck, cv, cache_attention_mask(ck.shape[2], T, idx), scale=self.scale)


class HybridLayer(nn.Module):
    kind: str  # "attention" | "mamba"
    d_ff: int
    attention: dict  # GroupedAttention's sizes
    mamba: dict      # MambaMixer's
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None, valid=None):
        y = RMSNorm(self.eps, name="mixer_norm")(x)
        if self.kind == "attention":
            with jax.named_scope("attention"):
                x = x + GroupedAttention(
                    **self.attention, dtype=self.dtype, decode=self.decode,
                    name="attention")(y, active=active, paged=paged)
        else:
            with jax.named_scope("mamba"):
                x = x + MambaMixer(
                    **self.mamba, eps=self.eps, dtype=self.dtype, decode=self.decode,
                    name="mamba")(y, active=active, valid=valid)
        with jax.named_scope("mlp"):
            y = RMSNorm(self.eps, name="mlp_norm")(x)
            return x + gated_feed_forward(y, self.d_ff, self.dtype)


class JambaLM(nn.Module):
    vocab_size: int = 65536
    d_model: int = 256
    num_layers: int = 4
    num_heads: int = 4
    num_kv_heads: int = 1
    head_dim: Optional[int] = None  # d_model // num_heads
    d_ff: int = 1024
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 16
    expand: int = 2
    attn_period: int = 4
    attn_offset: int = 1
    rms_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.float32
    attention: str = "dense"  # the one full-sequence form; the engine sets it
    decode: bool = False

    def layer_kind(self, i: int) -> str:
        return "attention" if i % self.attn_period == self.attn_offset else "mamba"

    @nn.compact
    def __call__(self, tokens, train: bool = False, pad_offset=None, active=None,
                 paged=None, valid=None):
        del train, pad_offset  # no dropout; serving rows are never left-padded
        if not self.decode and (active is not None or paged is not None
                                or valid is not None):
            raise ValueError("active / paged / valid are the decode=True path's")
        embed = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")
        x = embed(tokens.astype(jnp.int32)).astype(self.dtype)
        attention = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                         head_dim=self.head_dim or self.d_model // self.num_heads)
        mamba = dict(d_inner=self.expand * self.d_model, d_state=self.d_state,
                     d_conv=self.d_conv, dt_rank=self.dt_rank)
        for i in range(self.num_layers):
            x = HybridLayer(self.layer_kind(i), self.d_ff, attention, mamba,
                            eps=self.rms_eps, dtype=self.dtype, decode=self.decode,
                            name=f"Layer_{i}")(x, active=active, paged=paged,
                                               valid=valid)
        if valid is not None:  # the one row a prefill chunk samples from
            x = _rows(x, valid - 1, 1)
        with jax.named_scope("lm_head"):
            x = RMSNorm(self.rms_eps, name="final_norm")(x)
            return jnp.einsum("btd,vd->btv", x.astype(self.dtype),
                              embed.embedding.astype(self.dtype),
                              preferred_element_type=jnp.float32)


@register_model("jamba_lm")
def build_jamba_lm(vocab_size=65536, d_model=256, num_layers=4, num_heads=4,
                   num_kv_heads=1, head_dim=None, d_ff=1024, d_state=16, d_conv=4,
                   dt_rank=16, expand=2, attn_period=4, attn_offset=1, rms_eps=1e-6,
                   max_seq_len=262144, dtype="float32"):
    if num_heads % num_kv_heads:
        raise ValueError(f"num_heads ({num_heads}) must be a multiple of "
                         f"num_kv_heads ({num_kv_heads})")
    if not any(i % attn_period == attn_offset for i in range(num_layers)):
        raise ValueError("no attention layer: the serving pool reads a row's "
                         "length from an attention layer's cache index")
    return JambaLM(vocab_size=vocab_size, d_model=d_model, num_layers=num_layers,
                   num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
                   d_ff=d_ff, d_state=d_state, d_conv=d_conv, dt_rank=dt_rank,
                   expand=expand, attn_period=attn_period, attn_offset=attn_offset,
                   rms_eps=rms_eps, max_seq_len=max_seq_len, dtype=jnp.dtype(dtype))
