"""Hybrid decoder LM with routed experts: Granite 4.0-H's block. Layer
``l`` is a Mamba-2 mixer or grouped attention by ``layer_types[l]``; every
layer has a routed expert layer and a shared expert after it.

``rms`` is RMSNorm with a learned scale; no projection has a bias; the
head is the tied embedding::

    h_0    = embedding_multiplier * E[token]
    h'     = h + residual_multiplier * mixer(rms(h))
    h_next = h' + residual_multiplier * (shared(b) + routed(b)),  b = rms(h')
    logits = rms(h_L) E^T / logits_scaling

Mamba-2 mixer (``num_heads`` heads of ``head_dim``, a state of
``d_state``, one group, ``d_inner = num_heads * head_dim``)::

    [z, xBC, dt] = in_proj y                          d_inner | d_inner + 2 N | heads
    xBC          = silu(causal depthwise conv(xBC) + b_conv)   every channel
    [x, B, C]    = split(xBC)
    delta        = softplus(dt + dt_bias);  a = -exp(A_log)
    y            = ssd(x, delta, a, B, C) + D x                (``ops.ssd``)
    out          = out_proj(norm_w * rms(y * silu(z)))       the gate before the norm

Attention: ``num_heads`` query heads on ``num_kv_heads`` K/V heads, no
positions, scores ``q . k * attention_multiplier``. Expert layer: ``r =
W_r b`` over all ``n_experts``, the ``top_k`` largest, weighted by a softmax
over the chosen (``RoutedExperts`` with one group and the weights
renormalised); the module holds ``experts_held = (first, count)`` of them
and computes their part; the shared expert is a gated feed-forward.

``decode=True`` is the serving path. A mixer's ``cache`` holds a row a
batch element of ``conv_state`` (the last ``d_conv - 1`` inputs of the
convolution, every channel of ``xBC``) and of ``ssd_state`` (float32, in
``ops.ssd``'s layout); an attention layer's ``cached_key`` /
``cached_value`` / ``cache_index``. Over the paged pool a chunk continues
from the slot's rows and leaves both after ``valid`` tokens; a decode step
steps the active lanes' rows alone. Each mixer sows into ``counters`` the
bytes of the state it read and wrote (``ssd_state_bytes``) and the
sub-chunks of the chunk form that held a valid token (``ssd_subchunks``,
none on a decode step); the routed layers sow ``RoutedExperts``' own. Given
``valid``, the apply returns the logits of position ``valid - 1`` alone.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elephas_tpu.models import register_model
from elephas_tpu.models.jamba import GroupedAttention, RMSNorm, _rows
from elephas_tpu.models.latent_moe import GatedFeedForward, RoutedExperts

MAMBA, ATTENTION = "mamba", "attention"


def _a_log_init(key, shape):
    """Mamba-2's: ``a_h = -(1 .. heads)``."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    d_state: int
    d_conv: int = 4
    eps: float = 1e-5
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, y, active=None, valid=None):
        from elephas_tpu.ops.ssd import (
            ssd_body,
            ssd_chunk,
            ssd_step,
            ssd_step_body,
            state_shape,
            subchunks,
        )

        b, T, d_model = y.shape
        H, P, N, taps = self.num_heads, self.head_dim, self.d_state, self.d_conv
        inner, channels = H * P, H * P + 2 * N
        with jax.named_scope("in_proj"):
            zxd = nn.Dense(inner + channels + H, use_bias=False, dtype=self.dtype,
                           name="in_proj")(y)
            z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + channels], \
                zxd[..., inner + channels:]
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.02), (taps, channels))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (channels,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (H,))
        a_log = self.param("A_log", _a_log_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,))

        carried = self.decode and self.has_variable("cache", "ssd_state")
        if self.decode:
            conv_state = self.variable("cache", "conv_state", jnp.zeros,
                                       (b, taps - 1, channels), self.dtype)
            ssd_state = self.variable("cache", "ssd_state", jnp.zeros,
                                      (b,) + state_shape(H, P, N), jnp.float32)
        if carried:
            history, s0 = conv_state.value, ssd_state.value
        else:  # a full sequence, or the pass that shapes the cache
            history = jnp.zeros((b, taps - 1, channels), xbc.dtype)
            s0 = jnp.zeros((b,) + state_shape(H, P, N), jnp.float32)

        with jax.named_scope("conv"):
            xs = jnp.concatenate([history.astype(xbc.dtype), xbc], axis=1)
            conv = sum(conv_w[k].astype(jnp.float32) * xs[:, k:k + T]
                       for k in range(taps)) + conv_b.astype(jnp.float32)
            xbc = nn.silu(conv)
            x = xbc[..., :inner].astype(self.dtype).reshape(b, T, H, P)
            B, C = xbc[..., inner:inner + N], xbc[..., inner + N:]
            delta = nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            A = -jnp.exp(a_log.astype(jnp.float32))

        with jax.named_scope("ssd"):
            state_bytes = 2 * s0[0].size * 4  # read and written, one row
            if carried and active is not None:
                if T != 1:
                    raise ValueError("a decode step is one token a lane")
                out, s = ssd_step(x[:, 0], delta[:, 0], A, B[:, 0], C[:, 0], s0, active,
                                  body=ssd_step_body(H, P, N))
                out = out[:, None]
                moved, walked = active.sum() * state_bytes, jnp.zeros(())
            else:
                rows = jnp.full((b,), T, jnp.int32) if valid is None else valid
                body = ssd_body(T, H, P, N) if self.decode else "ssd_xla"
                if b == 1:  # a prefill chunk: one slot's, the kernel's
                    out, s = ssd_chunk(x[0], delta[0], A, B[0], C[0], s0[0], rows[0],
                                       body=body)
                    out, s = out[None], s[None]
                else:
                    out, s = jax.vmap(lambda *a: ssd_chunk(*a, body=body))(
                        x, delta, jnp.broadcast_to(A, (b, H)), B, C, s0, rows)
                moved, walked = b * state_bytes, subchunks(rows).sum()
            out = out + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        if carried:
            with jax.named_scope("state_write"):
                at = jnp.full((b,), T, jnp.int32) if valid is None else valid
                taps_in = _rows(xs, at, taps - 1).astype(self.dtype)
                if active is not None:
                    taps_in = jnp.where(active[:, None, None], taps_in, history)
                conv_state.value, ssd_state.value = taps_in, s
            self.sow("counters", "ssd_state_bytes", jnp.asarray(moved, jnp.float32))
            self.sow("counters", "ssd_subchunks", jnp.asarray(walked, jnp.float32))
        with jax.named_scope("gate_norm"):
            gated = out.reshape(b, T, inner) * nn.silu(z.astype(jnp.float32))
            scale = self.param("norm", nn.initializers.ones, (inner,))
            gated = gated * jax.lax.rsqrt((gated * gated).mean(-1, keepdims=True) + self.eps)
            gated = (gated * scale.astype(jnp.float32)).astype(self.dtype)
        with jax.named_scope("out_proj"):
            return nn.Dense(d_model, use_bias=False, dtype=self.dtype, name="out_proj")(gated)


class GraniteLayer(nn.Module):
    kind: str
    residual: float
    mamba: dict      # Mamba2Mixer's sizes
    attention: dict  # GroupedAttention's, with its scale
    experts: dict    # RoutedExperts', and the shared expert's width
    eps: float = 1e-5
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, active=None, paged=None, valid=None, live=None):
        y = RMSNorm(self.eps, name="mixer_norm")(x).astype(self.dtype)
        if self.kind == ATTENTION:
            with jax.named_scope("attention"):
                mixed = GroupedAttention(**self.attention, dtype=self.dtype,
                                         decode=self.decode, name="attention")(
                    y, active=active, paged=paged)
        else:
            with jax.named_scope("mamba2"):
                mixed = Mamba2Mixer(**self.mamba, eps=self.eps, dtype=self.dtype,
                                    decode=self.decode, name="mamba")(
                    y, active=active, valid=valid)
        x = x + (self.residual * mixed.astype(jnp.float32)).astype(x.dtype)
        with jax.named_scope("mlp"):
            y = RMSNorm(self.eps, name="mlp_norm")(x).astype(self.dtype)
            experts = dict(self.experts)
            with jax.named_scope("shared"):
                shared = GatedFeedForward(experts.pop("shared_d_ff"), self.dtype,
                                          name="shared")(y)
            b, T, d = y.shape
            with jax.named_scope("experts"):
                routed = RoutedExperts(**experts, dtype=self.dtype, name="experts")(
                    y.reshape(b * T, d), None if live is None else live.reshape(b * T))
            ff = shared.astype(jnp.float32) + routed.reshape(b, T, d).astype(jnp.float32)
            # the layer's output, whole: XLA would otherwise rebuild the residual
            # stream in each later layer's fusions from every branch before it
            return jax.lax.optimization_barrier(x + (self.residual * ff).astype(x.dtype))


class GraniteHybridLM(nn.Module):
    vocab_size: int = 100352
    d_model: int = 256
    layer_types: Tuple[str, ...] = (MAMBA, ATTENTION)
    num_heads: int = 4
    num_kv_heads: int = 1
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    d_state: int = 16
    d_conv: int = 4
    n_experts: int = 8
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    moe_d_ff: int = 64
    shared_d_ff: int = 128
    top_k: int = 2
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    attention_multiplier: Optional[float] = None  # head_dim ** -0.5
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.float32
    attention: str = "dense"  # the one full-sequence form; the engine sets it
    decode: bool = False

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pad_offset=None, active=None,
                 paged=None, valid=None):
        del train, pad_offset  # no dropout; serving rows are never left-padded
        if not self.decode and (active is not None or paged is not None
                                or valid is not None):
            raise ValueError("active / paged / valid are the decode=True path's")
        b, T = tokens.shape
        embed = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")
        x = (embed(tokens.astype(jnp.int32)).astype(jnp.float32)
             * self.embedding_multiplier).astype(self.dtype)
        mamba = dict(num_heads=self.mamba_heads, head_dim=self.mamba_head_dim,
                     d_state=self.d_state, d_conv=self.d_conv)
        attention = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                         head_dim=self.d_model // self.num_heads,
                         scale=self.attention_multiplier)
        experts = dict(n_routed_experts=self.n_experts,
                       experts_held=tuple(self.experts_held or (0, self.n_experts)),
                       moe_d_ff=self.moe_d_ff, top_k=self.top_k, n_group=1, topk_group=1,
                       routed_scaling_factor=1.0, norm_topk_prob=True,
                       shared_d_ff=self.shared_d_ff)
        live = None
        if valid is not None:
            live = jnp.arange(T)[None, :] < valid[:, None]
        elif active is not None:
            live = jnp.broadcast_to(active[:, None], (b, T))
        for i, kind in enumerate(self.layer_types):
            x = GraniteLayer(kind, self.residual_multiplier, mamba, attention, experts,
                             eps=self.rms_eps, dtype=self.dtype, decode=self.decode,
                             name=f"Layer_{i}")(x, active=active, paged=paged, valid=valid,
                                                live=live)
        if valid is not None:  # the one row a prefill chunk samples from
            x = _rows(x, valid - 1, 1)
        with jax.named_scope("lm_head"):
            x = RMSNorm(self.rms_eps, name="final_norm")(x)
            logits = jnp.einsum("btd,vd->btv", x.astype(self.dtype),
                                embed.embedding.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            return logits / self.logits_scaling


@register_model("granite_hybrid_lm")
def build_granite_hybrid_lm(vocab_size=100352, d_model=256, layer_types=(MAMBA, ATTENTION),
                            num_heads=4, num_kv_heads=1, mamba_heads=4, mamba_head_dim=16,
                            d_state=16, d_conv=4, n_experts=8, experts_held=None,
                            moe_d_ff=64, shared_d_ff=128, top_k=2,
                            embedding_multiplier=12.0, residual_multiplier=0.22,
                            logits_scaling=16.0, attention_multiplier=None, rms_eps=1e-5,
                            max_seq_len=131072, dtype="float32"):
    layer_types = tuple(layer_types)
    if set(layer_types) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types are {MAMBA!r} or {ATTENTION!r}, got {layer_types}")
    if ATTENTION not in layer_types:
        raise ValueError("no attention layer: the serving pool reads a row's length "
                         "from an attention layer's cache index")
    if d_model % num_heads or num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} heads on {num_kv_heads} K/V heads do not divide "
                         f"d_model {d_model}")
    first, count = experts_held or (0, n_experts)
    if first < 0 or count < 1 or first + count > n_experts or top_k > n_experts:
        raise ValueError(f"experts_held {(first, count)} is no range of the {n_experts} "
                         f"experts, or top_k {top_k} is more than they are")
    return GraniteHybridLM(
        vocab_size=vocab_size, d_model=d_model, layer_types=layer_types,
        num_heads=num_heads, num_kv_heads=num_kv_heads, mamba_heads=mamba_heads,
        mamba_head_dim=mamba_head_dim, d_state=d_state, d_conv=d_conv, n_experts=n_experts,
        experts_held=(first, count), moe_d_ff=moe_d_ff, shared_d_ff=shared_d_ff,
        top_k=top_k, embedding_multiplier=embedding_multiplier,
        residual_multiplier=residual_multiplier, logits_scaling=logits_scaling,
        attention_multiplier=attention_multiplier, rms_eps=rms_eps,
        max_seq_len=max_seq_len, dtype=jnp.dtype(dtype))
