"""Speculative decoding: draft-and-verify serving decode, token-identical
by construction.

The decode hot path pays one dense target forward per emitted token. A
``SpeculativeDecoder`` instead drafts ``gamma`` tokens per slot with a
cheap ``DraftSource`` and verifies the whole window in ONE batched
target forward (seq = gamma + 1 through the same paged pool), emitting
between 1 and gamma + 1 tokens per window.

**Identity discipline.** Acceptance is EXACT-MATCH, not the
probabilistic Leviathan/Chen rule: the verify forward samples the
target's own token at every window position (greedy argmax, or
position-keyed categorical — see ``sample_tokens_at``), a draft token is
accepted iff it EQUALS the target's sample at that position, and the
emitted tokens are always the target's samples ``tgt[:a+1]`` (``a`` =
length of the matching prefix). The emitted stream is therefore
byte-identical to plain decode for greedy AND temperature-matched
sampling — the draft only decides how many target samples one forward
yields, never what they are. Position-keyed sampling
(``fold_in(base_key, position)``) is what makes the temperature case
hold: plain decode, chunked prefill, and the verify window all draw the
same random number for the same stream position.

**Cache story.** No new layout: the verify forward gathers the paged
pool contiguous exactly like plain decode, writes all gamma + 1 columns
back through ``scatter_spec_columns``, and ROLLS BACK rejected suffixes
device-side by resetting every index leaf to ``idx0 + a + 1`` — the
rejected columns' K/V stay in place as garbage at-or-past the causal
frontier, overwritten before any query can attend them (the same
discipline right-padded chunk prefill already relies on). Block backing
and copy-on-write stay host-side in the scheduler, on the existing
refcount machinery.

Two ``DraftSource`` flavors:

- ``SelfDraftSource(layers)`` — the first K transformer layers of the
  TARGET (flax auto-naming makes ``Block_0..Block_{K-1}`` +
  ``tok_embed``/``pos_embed``/``LayerNorm_0``/``lm_head`` a valid
  K-layer param tree inside the full tree): zero extra weights, zero
  extra cache — the draft reads the target's own paged pool, and its
  first-K-layer K/V writes are bit-identical to what verify rewrites.
- ``DraftModelSource(module, client)`` — a separate small model whose
  params are pulled version-gated from a ``ShardedParameterClient``
  (the PS group delivers the draft like any other artifact — the bridge
  toward live model delivery). It keeps its own contiguous decode cache
  filled by a third compiled program riding every prefill chunk, and
  requires ``prefix_cache=False`` (a prefix-matched admission fills the
  target pool by refcount, which would leave the draft cache cold).

A failed draft-params pull degrades to plain decode for that dispatch
(``spec_fallback`` flight kind) instead of erroring — identity is
unaffected because the plain path samples the same position keys.

Compiled-program story: exactly ONE draft program and ONE verify
program after warmup (``draft_traces``/``verify_traces``), plus one
draft-prefill program for model sources.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from elephas_tpu import obs
from elephas_tpu.models.decode_cache import (
    INDEX,
    KV,
    first_index,
    has_state,
    leaf_kind,
)

__all__ = [
    "DraftSource",
    "SelfDraftSource",
    "DraftModelSource",
    "SpeculativeDecoder",
]



def _renest(template, tree):
    """Rebuild ``tree``'s leaves in ``template``'s container structure.

    Flax applies may hand back a different mapping container than the
    cache we persist (dict vs FrozenDict); both flatten leaves in the
    same sorted-key order, so re-nesting pins the compiled programs'
    output treedef to the input's — the donated-cache round trip never
    changes structure, so it never retraces."""
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template),
        jax.tree_util.tree_leaves(tree),
    )


class DraftSource:
    """Where draft tokens come from. ``bind(engine)`` is called once by
    the ``SpeculativeDecoder``; ``params()`` is called at every dispatch
    and may raise — the decoder degrades to plain decode for that step
    (``spec_fallback``)."""

    kind = "abstract"

    def bind(self, engine) -> None:
        raise NotImplementedError

    def params(self):
        raise NotImplementedError


class SelfDraftSource(DraftSource):
    """Shallow-stack self-draft: the target's first ``layers`` blocks,
    same embeddings, the target's own final norm + lm_head on top. Zero
    extra weights (the draft param tree is a subtree of the target's —
    flax reads only what the K-layer module names) and zero extra cache
    (drafting reads/extends the target's paged pool; its layer-i K/V
    equals what verify writes for the accepted prefix)."""

    kind = "self"

    def __init__(self, layers: int):
        self.layers = int(layers)  # host-ok: constructor arg
        self.module = None
        self._engine = None

    def bind(self, engine) -> None:
        target = engine.decode_module
        if not 1 <= self.layers < target.num_layers:
            raise ValueError(
                f"draft_layers ({self.layers}) must be in "
                f"[1, num_layers={target.num_layers})"
            )
        self.module = dataclasses.replace(target, num_layers=self.layers)
        self._engine = engine

    def params(self):
        return self._engine.params  # the full tree; flax reads the subtree


class DraftModelSource(DraftSource):
    """A separate small draft model, params delivered by the sharded
    parameter-server group: ``client.get_parameters()`` is version-gated
    at the wire layer (an unchanged pull costs a not-modified frame per
    shard), and ``refresh_every`` bounds how many speculation windows
    reuse one pulled tree before re-asking. A pull failure raises out of
    ``params()`` — the decoder's fallback path turns it into one plain
    decode step, never an error."""

    kind = "model"

    def __init__(self, module, client, refresh_every: int = 1,
                 subscribed: bool = False):
        if refresh_every < 1:
            raise ValueError(
                f"refresh_every must be >= 1, got {refresh_every}"
            )
        self._raw_module = module
        self.client = client
        self.refresh_every = int(refresh_every)  # host-ok: constructor arg
        # subscribed=True hands the pull cadence to the engine's
        # WeightSubscriber: ``params()`` never self-polls (beyond the
        # one cold-start pull) and ``refresh()`` is driven at the
        # subscriber's step cadence — ONE version-gated poll per window
        # refreshes target and draft instead of two.
        self.subscribed = bool(subscribed)
        self.module = None
        self._engine = None
        self._cached = None
        self._windows = 0
        self.pulls = 0

    def bind(self, engine) -> None:
        target = engine.decode_module
        module = self._raw_module
        if module.vocab_size != target.vocab_size:
            raise ValueError(
                f"draft model vocab_size ({module.vocab_size}) must match "
                f"the target's ({target.vocab_size})"
            )
        if module.max_seq_len < engine.pool.virtual_len:
            raise ValueError(
                f"draft model max_seq_len ({module.max_seq_len}) must "
                f"cover the pool's virtual row "
                f"({engine.pool.virtual_len} columns)"
            )
        self.module = dataclasses.replace(
            module, decode=True, attention="dense"
        )
        self._engine = engine

    def params(self):
        if self.subscribed:
            # Subscriber-owned cadence: serve the cache; the engine's
            # WeightSubscriber calls refresh() between decode windows
            # (the draft rides the target's poll — no double-polling
            # the PS group). Cold start still pulls once: a spec
            # window must never run on a None tree.
            self._windows += 1
            if self._cached is None:
                self._cached = self.client.get_parameters()
                self.pulls += 1
            return self._cached
        take = (self._cached is None
                or self._windows % self.refresh_every == 0)
        self._windows += 1
        if take:
            tree = self.client.get_parameters()
            self._cached = tree
            self.pulls += 1
        return self._cached

    def refresh(self) -> None:
        """Re-pull the draft tree NOW — the ``WeightSubscriber``'s hook,
        called at its own (version-gated) cadence right after the target
        pull, so one subscriber tick refreshes both models. Runs at a
        decode-step boundary, never mid-verify (the hook fires under the
        engine's step lock). A pull failure propagates to the caller,
        which degrades exactly like a failed target pull."""
        self._cached = self.client.get_parameters()
        self.pulls += 1


class SpeculativeDecoder:
    """Drafts ``gamma`` tokens per slot, verifies them in one batched
    target forward over the paged pool, and hands the scheduler a
    ``(last, emitted, accepted)`` device triple per window:

    - ``last``     — (max_slots,) the target sample at each lane's
                     accepted frontier; chains as the next window's
                     device ``prev_tokens`` (lookahead preserved),
    - ``emitted``  — (max_slots, gamma + 1) the target's samples; the
                     harvest appends ``emitted[s, :accepted[s] + 1]``,
    - ``accepted`` — (max_slots,) matching-prefix lengths in [0, gamma].

    ``dispatch`` returns None when the draft source cannot produce
    params (``spec_fallback`` flight note recorded) — the scheduler
    falls back to one plain decode step.
    """

    def __init__(self, engine, source: DraftSource, gamma: int = 4):
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if has_state(engine.pool.cache):
            raise NotImplementedError(
                "speculative decoding is not built for a model with "
                "per-slot state: a verify window runs its draft tokens "
                "through the recurrence, and a rejected draft cannot be "
                "rolled back out of the state (K/V past the accepted "
                "column is merely never read)"
            )
        self.engine = engine
        self.source = source
        self.gamma = int(gamma)  # host-ok: constructor arg
        source.bind(engine)
        self.draft_traces = 0
        self.verify_traces = 0
        self.draft_prefill_traces = 0
        self.windows = 0
        self.fallbacks = 0
        self._draft_cache = None
        if source.kind == "model":
            from elephas_tpu.models.transformer import make_decode_cache

            pool = engine.pool
            cache = make_decode_cache(
                source.module, pool.max_slots, pool.virtual_len
            )

            def vectorize(path, leaf):
                if leaf_kind(path) == INDEX:
                    return jnp.zeros((pool.max_slots,), jnp.int32)
                return leaf

            self._draft_cache = jax.tree_util.tree_map_with_path(
                vectorize, cache
            )
        self.make_jits()

    # -- compilation ---------------------------------------------------------

    def make_jits(self, p_sh=None, pool_sh=None, repl=None):
        """(Re)build the compiled draft/verify programs. With shardings
        (self-draft under ``shard_serving``) the same programs lower via
        GSPMD over the mesh — still exactly one compile each."""
        draft_in = draft_out = verify_in = verify_out = None
        if p_sh is not None:
            verify_in = (p_sh, pool_sh) + (repl,) * 6
            verify_out = (repl, repl, repl, pool_sh)
            draft_in = (p_sh, pool_sh) + (repl,) * 7
            draft_out = (repl, repl)
        if self.source.kind == "self":
            self._jit_draft = jax.jit(
                self._draft_self_impl,
                in_shardings=draft_in, out_shardings=draft_out,
            )
        else:
            # The draft model's own contiguous cache is donated (argnum
            # 1) — it is rewritten every window, like the pool is.
            self._jit_draft = jax.jit(
                self._draft_model_impl, donate_argnums=(1,),
            )
            self._jit_draft_prefill = jax.jit(
                self._draft_prefill_impl, donate_argnums=(1,),
            )
        self._jit_verify = jax.jit(
            self._verify_impl, donate_argnums=(1,),
            in_shardings=verify_in, out_shardings=verify_out,
        )

    # -- compiled bodies -----------------------------------------------------

    def _draft_steps(self, module, params, dcache, t0, idx0, active_mask,
                     pad, rng, write_tail):
        """gamma autoregressive draft steps under one program: first
        apply establishes the flax cache container for the scan carry
        (the ``generate`` idiom), ``lax.scan`` runs the rest. The token
        drafted at window offset j is sampled at pad-free stream
        position ``idx0 - pad + 1 + j`` — the exact key plain decode
        would use for that position.

        ``write_tail`` runs ONE extra step feeding the final draft back
        so its K/V lands in ``dcache`` (sample discarded). A persistent
        draft-model cache needs it: after an accept-all window the next
        window's frontier sits past the last draft's column, and without
        the tail write that column would be attended as garbage —
        silently sinking the accept rate (never identity). Self-draft
        skips it: the pool columns it reads are rewritten by verify."""
        from elephas_tpu.models.transformer import sample_tokens_at

        eng = self.engine

        def one(tok, dc, j):
            logits, mutated = module.apply(
                {"params": params, "cache": dc}, tok[:, None],
                pad_offset=pad, active=active_mask, mutable=["cache"],
            )
            nxt = sample_tokens_at(
                logits[:, -1], rng, idx0 - pad + 1 + j,
                eng._greedy, eng.top_k, eng.temperature,
            )
            return nxt, mutated["cache"]

        d0, dc = one(t0, dcache, jnp.int32(0))

        def body(carry, j):
            tok, dc = carry
            nxt, dc = one(tok, dc, j)
            return (nxt, dc), nxt

        steps = self.gamma + 1 if write_tail else self.gamma
        if steps > 1:
            (_, dc), rest = jax.lax.scan(
                body, (d0, dc), jnp.arange(1, steps)
            )
            drafts = jnp.concatenate(
                [d0[:, None], rest.T], axis=1
            )[:, :self.gamma]
        else:
            drafts = d0[:, None]
        return drafts, dc

    def _draft_self_impl(self, params, cache, table, prev_tokens,
                         override_vals, override_mask, active_mask, pad,
                         rng):
        """Self-draft: gather the first K blocks' paged K/V contiguous
        and run the K-layer module over them. The pool itself is
        untouched — verify rewrites every layer's columns, and the
        draft's layer-i K/V would be bit-identical anyway (same params,
        same inputs)."""
        self.draft_traces += 1
        from elephas_tpu.utils.compiler import note_retrace

        note_retrace("serving_draft", count=self.draft_traces)
        from elephas_tpu.ops.attention import paged_to_contiguous

        idx0 = first_index(cache)
        module = self.engine.decode_module
        head_dim = module.d_model // module.num_heads

        def to_contig(path, leaf):
            if leaf_kind(path) == KV:
                return paged_to_contiguous(leaf, table, head_dim)
            return leaf

        dcache = {"pos_index": cache["pos_index"]}
        for i in range(self.source.layers):
            name = f"Block_{i}"
            dcache[name] = jax.tree_util.tree_map_with_path(
                to_contig, cache[name]
            )
        t0 = jnp.where(override_mask, override_vals, prev_tokens)
        drafts, _ = self._draft_steps(
            self.source.module, params, dcache, t0, idx0, active_mask,
            pad, rng, write_tail=False,
        )
        return t0, drafts

    def _draft_model_impl(self, dparams, dcache, cache, prev_tokens,
                          override_vals, override_mask, active_mask, pad,
                          rng):
        """Draft-model drafting through the source's OWN contiguous
        cache. Its index leaves are overwritten with the target pool's
        pre-window frontier at entry — the draft cache needs no
        persistent rollback state, the target's index vector IS the
        truth (rejected-suffix columns in the draft cache are garbage
        at-or-past that frontier, overwritten by the next window's scan
        before anything attends them)."""
        self.draft_traces += 1
        from elephas_tpu.utils.compiler import note_retrace

        note_retrace("serving_draft", count=self.draft_traces)

        idx0 = first_index(cache)

        def reset_idx(path, leaf):
            if leaf_kind(path) == INDEX:
                return idx0
            return leaf

        dc = jax.tree_util.tree_map_with_path(reset_idx, dcache)
        t0 = jnp.where(override_mask, override_vals, prev_tokens)
        drafts, dc_out = self._draft_steps(
            self.source.module, dparams, dc, t0, idx0, active_mask, pad,
            rng, write_tail=True,
        )
        return t0, drafts, _renest(dcache, dc_out)

    def _draft_prefill_impl(self, dparams, dcache, tokens, slot, start,
                            valid):
        """One prompt chunk through the DRAFT model (model sources
        only), mirroring the engine's paged chunk prefill: batch-1 row
        view at ``start``, dense cache-attention apply, row written back
        whole, index leaves advanced to ``start + valid``. Rides every
        target prefill chunk so the draft cache is warm when the slot
        joins the decode batch."""
        self.draft_prefill_traces += 1
        from elephas_tpu.utils.compiler import note_retrace

        note_retrace("serving_draft_prefill",
                     count=self.draft_prefill_traces)

        def to_row(path, leaf):
            kind = leaf_kind(path)
            if kind == KV:
                return jax.lax.dynamic_index_in_dim(leaf, slot, axis=0,
                                                    keepdims=True)
            if kind == INDEX:
                return jnp.full((1,), start, jnp.int32)
            return leaf

        row_cache = jax.tree_util.tree_map_with_path(to_row, dcache)
        _, mutated = self.source.module.apply(
            {"params": dparams, "cache": row_cache}, tokens,
            mutable=["cache"],
        )

        def back(path, leaf, mut):
            kind = leaf_kind(path)
            if kind == KV:
                return jax.lax.dynamic_update_slice(
                    leaf, mut.astype(leaf.dtype), (slot, 0, 0, 0)
                )
            # Index leaves: the slot advances to its true prefilled
            # depth (right-pad tail is garbage); others untouched.
            return leaf.at[slot].set(start + valid)

        new = jax.tree_util.tree_map_with_path(back, dcache,
                                               mutated["cache"])
        return _renest(dcache, new)

    def _verify_impl(self, params, cache, table, t0, drafts, active_mask,
                     pad, rng):
        """ONE batched target forward over the whole window: apply the
        UNCHANGED decode module with seq = gamma + 1 (causal-within-
        window attention falls out of ``cache_attention_mask``), sample
        the target's token at every position with the position-keyed
        sampler, accept the longest draft prefix that matches, and roll
        every index leaf to ``idx0 + accepted + 1`` — rejected columns'
        K/V stay as causally-invisible garbage, no block churn."""
        self.verify_traces += 1
        from elephas_tpu.utils.compiler import note_retrace

        note_retrace("serving_verify", count=self.verify_traces)
        from elephas_tpu.models.transformer import sample_tokens_at
        from elephas_tpu.ops.attention import (
            paged_to_contiguous,
            scatter_spec_columns,
        )

        eng = self.engine
        W = self.gamma + 1
        idx0 = first_index(cache)
        head_dim = eng.decode_module.d_model // eng.decode_module.num_heads

        def to_contig(path, leaf):
            if leaf_kind(path) == KV:
                return paged_to_contiguous(leaf, table, head_dim)
            return leaf

        contig = jax.tree_util.tree_map_with_path(to_contig, cache)
        tokens_in = jnp.concatenate([t0[:, None], drafts], axis=1)
        logits, mutated = eng.decode_module.apply(
            {"params": params, "cache": contig}, tokens_in,
            pad_offset=pad, active=active_mask, mutable=["cache"],
        )
        S = tokens_in.shape[0]
        positions = (idx0[:, None] - pad[:, None] + 1
                     + jnp.arange(W)[None, :])
        tgt = sample_tokens_at(
            logits.reshape(S * W, -1), rng, positions.reshape(-1),
            eng._greedy, eng.top_k, eng.temperature,
        ).reshape(S, W)
        match = (drafts == tgt[:, :-1]).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        last = jnp.take_along_axis(tgt, accepted[:, None], axis=1)[:, 0]
        frontier = jnp.where(active_mask, idx0 + accepted + 1, idx0)

        def back(path, pool_leaf, mut_leaf):
            if leaf_kind(path) == KV:
                return scatter_spec_columns(
                    pool_leaf, mut_leaf, table, idx0, W, active_mask
                )
            # Index leaves (cache_index AND pos_index): the device-side
            # rollback — rejected suffixes never advance the frontier.
            return frontier

        new_cache = jax.tree_util.tree_map_with_path(
            back, cache, mutated["cache"]
        )
        return last, tgt, accepted, new_cache

    # -- scheduler-facing closures -------------------------------------------

    def dispatch(self, cache, prev_tokens, override_vals, override_mask,
                 active_mask, pad):
        """One speculation window (draft + verify, both non-blocking
        dispatches; the pool is swapped to verify's donated output).
        Returns ``(last, emitted, accepted)`` device values, or None
        when the draft source failed to produce params — the caller
        runs one plain decode step instead."""
        eng = self.engine
        try:
            sparams = self.source.params()
        except Exception as err:
            self.fallbacks += 1
            obs.default_flight_recorder().note(
                "spec_fallback", "warn", source=self.source.kind,
                error=repr(err),
            )
            return None
        table = eng.pool.device_table()
        t0c = eng.clock()
        if self.source.kind == "self":
            t0, drafts = self._jit_draft(
                sparams, cache, table, prev_tokens, override_vals,
                override_mask, active_mask, pad, eng._rng,
            )
        else:
            t0, drafts, new_draft_cache = self._jit_draft(
                sparams, self._draft_cache, cache, prev_tokens,
                override_vals, override_mask, active_mask, pad, eng._rng,
            )
            self._draft_cache = new_draft_cache
        t1c = eng.clock()
        eng.tracer.record("spec/draft", t0c, t1c, gamma=self.gamma)
        last, emitted, accepted, new_cache = self._jit_verify(
            eng.params, cache, table, t0, drafts, active_mask, pad,
            eng._rng,
        )
        eng.pool.swap(new_cache)
        t2c = eng.clock()
        eng.tracer.record("spec/verify", t1c, t2c, gamma=self.gamma)
        self.windows += 1
        return last, emitted, accepted

    def prefill_chunk(self, tokens, slot, start, valid) -> None:
        """Model sources: land one prompt chunk in the draft cache
        (rides the scheduler's target prefill chunk). A params failure
        leaves the draft cache cold for this chunk — acceptance drops,
        identity doesn't."""
        if self.source.kind != "model":
            return
        try:
            dparams = self.source.params()
        except Exception as err:
            self.fallbacks += 1
            obs.default_flight_recorder().note(
                "spec_fallback", "warn", source=self.source.kind,
                where="prefill", error=repr(err),
            )
            return
        self._draft_cache = self._jit_draft_prefill(
            dparams, self._draft_cache, tokens, slot, start, valid,
        )

    def stats(self) -> dict:
        return {
            "draft_traces": self.draft_traces,
            "verify_traces": self.verify_traces,
            "draft_prefill_traces": self.draft_prefill_traces,
            "spec_windows": self.windows,
            "spec_fallbacks": self.fallbacks,
            "spec_source": self.source.kind,
            "spec_gamma": self.gamma,
        }
