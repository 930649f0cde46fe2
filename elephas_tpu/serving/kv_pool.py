"""Paged KV-cache pool for continuous batching.

``PagedKVPool`` owns ONE cache pytree of fixed shape, so the compiled
programs' operand shapes never change as sequences come and go: per
layer, K/V leaves of ``num_blocks`` physical blocks of ``block_size``
columns (the vLLM layout) and per-slot ``cache_index``/``pos_index``
``(max_slots,)`` vectors; a model with per-slot state (a recurrence's, a
convolution's) keeps one row a slot in the same tree, and a window layer's
latent a ring of blocks a slot, bounded by the window and not by the
sequence (``models.decode_cache``: four kinds of leaf). Slots reach their
blocks through a reference-counted ``BlockTable``; a ``PrefixCache``
admits already-resident prompt prefixes by bumping refcounts instead of
re-prefilling, evicts unreferenced prefixes LRU-first under allocation
pressure, and any shared boundary a fork creates is copied on write.

The cache pytree is DONATED to every program that rewrites it (the
engine's chunk-prefill and decode programs, the block copies and state
clears here), so XLA updates the pool in place instead of materializing
a copy of every layer's K/V each token. Donation makes the OLD buffers
poison: any read through a stale reference raises, so ``self._cache`` is
private and the ``cache`` property guards every access with an explicit
use-after-donate check (a stale read would otherwise surface as an
opaque ``Array has been deleted`` deep inside XLA).

Per-slot state the model consumes each step:

- ``cache_index``/``pos_index``: the column the slot's next token
  writes (advanced by the apply itself, per row, ONLY for rows the
  decode step's ``active`` mask marks occupied; free slots' vectors
  freeze so they can't march past ``max_len`` between admissions),
- ``pad``: always zero. Prompts are never left-padded (shared prefixes
  must land at identical cache columns in every slot); the vector is an
  operand of the decode and speculation programs all the same.

Inactive slots ride along in the decode batch (their logits are
discarded and their blocks rebound at admission): the price of a
fixed-shape program, and exactly the slot semantics of continuous
batching servers (Orca-style iteration-level scheduling).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models.decode_cache import (
    INDEX,
    KV,
    STATE,
    WINDOW,
    has_state,
    leaf_kind,
    leaves_of_kind,
    state_bytes,
)


class DonatedBufferError(RuntimeError):
    """A pool cache reference was read after its buffers were donated."""


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block(cache, src, dst):
    """Copy physical block ``src`` over ``dst`` in every K/V leaf — the
    device half of copy-on-write. The cache is donated (one block copied
    in place, not a whole-pool copy)."""

    def cp(path, leaf):
        if leaf_kind(path) == KV:
            return leaf.at[dst].set(leaf[src])
        return leaf

    return jax.tree_util.tree_map_with_path(cp, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_state_row(cache, slot):
    """Zero ``slot``'s row of every state leaf and its ring of every window
    leaf, in place on the donated cache: a released slot keeps nothing of
    the request it served."""

    def clear(path, leaf):
        if leaf_kind(path) in (STATE, WINDOW):
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.zeros((1,) + leaf.shape[1:], leaf.dtype), slot, 0)
        return leaf

    return jax.tree_util.tree_map_with_path(clear, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_imported_blocks(cache, ids, payload, slot, next_col):
    """Scatter imported handoff block data into the paged cache and set
    ``slot``'s index vectors to the handoff's write frontier — the device
    half of ``PagedKVPool.import_blocks``. ``payload`` is a tuple of
    ``(n, heads, rows, lanes)`` block uploads, one per K/V
    leaf in tree order; the cache is donated (n block rows written in
    place, not a whole-pool copy). Retraces per distinct block count —
    bounded by ``blocks_per_slot``, and warmed by the first handoffs."""
    it = iter(payload)

    def put(path, leaf):
        kind = leaf_kind(path)
        if kind == KV:
            return leaf.at[ids].set(next(it).astype(leaf.dtype))
        if kind == INDEX:
            return leaf.at[slot].set(next_col.astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(put, cache)


class BlockTable:
    """Host-side ``slot -> physical block ids`` map with a lazily
    uploaded device mirror.

    Rows are ``-1`` where unallocated. The device mirror substitutes the
    OUT-OF-RANGE id ``num_blocks`` for ``-1`` so compiled gathers clamp
    and scatters drop (never a negative index), and is re-uploaded only
    when a row changed (the dirty flag) — steady-state decode reuses the
    same device array every step.
    """

    def __init__(self, max_slots: int, blocks_per_slot: int,
                 num_blocks: int):
        self.num_blocks = num_blocks
        self.rows = np.full((max_slots, blocks_per_slot), -1, np.int32)
        self._dev = None  # None = dirty, rebuild on next device() read
        self.sharding = None  # set by shard_serving (replicated)

    def set(self, slot: int, index: int, block: int) -> None:
        self.rows[slot, index] = block
        self._dev = None

    def clear_row(self, slot: int) -> None:
        self.rows[slot, :] = -1
        self._dev = None

    def invalidate(self) -> None:
        self._dev = None

    def device(self):
        if self._dev is None:
            host = np.where(self.rows < 0, self.num_blocks, self.rows)
            dev = jnp.asarray(  # host table → device upload
                host.astype(np.int32)
            )
            if self.sharding is not None:
                dev = jax.device_put(dev, self.sharding)
            self._dev = dev
        return self._dev


class _PrefixEntry:
    __slots__ = ("tokens", "blocks", "recency")

    def __init__(self, tokens, blocks, recency):
        self.tokens = tokens
        self.blocks = blocks
        self.recency = recency


class PrefixCache:
    """Resident-prefix index: token chains → the physical blocks that
    already hold their K/V.

    Entries are keyed by the exact token tuple of a FULL-block prefix
    (the dict's tuple hash IS the token-hash chain; tuple equality keeps
    collisions impossible, so a hit can never silently serve the wrong
    prefix). Every full-block prefix of an inserted chain gets its own
    entry — a new prompt can resume from ANY block boundary of an old
    conversation, not only its full length. Each entry holds one
    reference on each of its blocks (the pool's refcounts), so resident
    prefixes pin their blocks until evicted.

    Eviction is LRU over entries, triggered by the pool on allocation
    pressure; ``match`` is capped one token short of the prompt so at
    least one suffix token always prefills (matched blocks are full and
    are never written by the sharer — the copy-on-write boundary is
    block-aligned by construction).
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._entries: Dict[Tuple[int, ...], _PrefixEntry] = {}
        self._tick = 0
        self.hits_total = 0
        self.lookups_total = 0
        self.tokens_saved_total = 0
        self.evictions_total = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hit_rate(self) -> Optional[float]:
        if not self.lookups_total:
            return None
        return self.hits_total / self.lookups_total

    def match(self, prompt: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest resident full-block prefix STRICTLY shorter than the
        prompt; returns ``(matched_token_count, block_ids)`` (0, [] on a
        miss). Bumps recency and the hit counters."""
        self.lookups_total += 1
        bs = self.block_size
        prompt = tuple(prompt)
        for k in range((len(prompt) - 1) // bs, 0, -1):
            entry = self._entries.get(prompt[:k * bs])
            if entry is not None:
                self._tick += 1
                entry.recency = self._tick
                self.hits_total += 1
                self.tokens_saved_total += k * bs
                return k * bs, list(entry.blocks)
        return 0, []

    def insert(self, chain: Sequence[int], blocks: Sequence[int],
               incref) -> int:
        """Register every full-block prefix of ``chain`` (``blocks[i]``
        holds tokens ``[i*bs, (i+1)*bs)``), taking one reference per
        entry per block via ``incref``. Token chains already resident
        keep their existing entry (the old blocks hold identical K/V).
        Returns the number of entries added."""
        bs = self.block_size
        chain = tuple(chain)
        added = 0
        for k in range(1, min(len(chain) // bs, len(blocks)) + 1):
            key = chain[:k * bs]
            if key in self._entries:
                continue
            held = tuple(blocks[:k])
            for b in held:
                incref(b)
            self._tick += 1
            self._entries[key] = _PrefixEntry(key, held, self._tick)
            added += 1
        return added

    def evict_lru(self, decref) -> Optional[_PrefixEntry]:
        """Drop the least-recently-used entry, releasing its block
        references through ``decref``. Returns it (None when empty)."""
        if not self._entries:
            return None
        # over the entries, not the keys: a lookup by key hashes the whole
        # token chain again, 16k tokens deep for a long document's
        entry = min(self._entries.values(), key=lambda e: e.recency)
        del self._entries[entry.tokens]
        for b in entry.blocks:
            decref(b)
        self.evictions_total += 1
        return entry


class PagedKVPool:
    """Fixed-shape paged KV cache + slot bookkeeping for the serving
    engine: fixed-size physical blocks shared across slots through a
    ``BlockTable``, reference-counted, with a ``PrefixCache`` so prompts
    whose prefix is already resident admit by bumping refcounts instead
    of re-prefilling.

    ``decode_module``: a language model with ``decode=True``.
    ``max_slots``: decode batch width (concurrent sequences).
    ``max_len``: cache columns a sequence may fill, prompt and generated
    tokens together.

    Layout: every K/V leaf is ``num_blocks`` physical blocks of
    ``block_size`` columns, ``(num_blocks, heads, rows, lanes)`` with a
    few columns packed to a row (``ops.attention.pool_leaf_shape``: rows
    128 lanes wide keep the device layout row-major, a block one
    contiguous piece of memory); a slot's logical cache row is the
    concatenation of its table row's blocks — a VIRTUAL length
    ``blocks_per_slot * block_size >= max_len`` (ceil, so ``block_size``
    need not divide ``max_len``). The compiled decode and chunk-prefill
    programs write and attend on the blocks in place through the table;
    speculative windows gather their rows through it, run the dense
    cache-attention apply that ``generate()`` runs, and set back exactly
    the blocks they wrote (``ops.attention`` paged helpers).

    Invariants the allocator maintains (and tests pin):

    - a block is in the free list iff its refcount is 0;
    - a slot's row references each of its blocks exactly once, a prefix
      cache entry once per entry containing it;
    - ``release`` decrefs, never abandons — double-releasing a block
      raises ``RuntimeError`` loudly;
    - allocation under pressure evicts UNREFERENCED-by-slots prefix
      entries LRU-first (flight kind ``prefix_evict``), and with the
      default ``num_blocks = max_slots * blocks_per_slot`` sizing can
      never dead-end (live slots need at most that many blocks).

    Writes never touch a shared block in normal serving: prefix matches
    cover full blocks only and prefill resumes at the block-aligned
    boundary. ``ensure_writable`` is the copy-on-write safety net for
    explicit ``fork_slot`` aliases (tests, speculative decoding).

    The live cache is read through the ``cache`` property and replaced
    with ``swap(new_cache)`` after every donating program. The property
    refuses to hand out donated (deleted) buffers — the failure mode
    donation introduces is a stale alias kept across a swap, and that
    must fail loudly at the POOL boundary, not as a deep XLA error.
    """

    def __init__(self, decode_module, max_slots: int, max_len: int,
                 block_size: int, num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 virtual_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None):
        from elephas_tpu.models.transformer import make_paged_decode_cache

        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.max_slots = max_slots
        self.max_len = max_len
        self.block_size = block_size
        # Virtual row length: enough blocks for max_len columns AND for
        # the widest prefill-chunk write window (a chunk starting at the
        # last prompt column must slice/scatter without clamping).
        need = max(max_len, virtual_len or 0)
        self.blocks_per_slot = -(-need // block_size)
        self.virtual_len = self.blocks_per_slot * block_size
        self.num_blocks = (
            num_blocks if num_blocks is not None
            else max_slots * self.blocks_per_slot
        )
        if self.num_blocks < self.blocks_per_slot:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) cannot back even one "
                f"slot ({self.blocks_per_slot} blocks per slot)"
            )
        # ``prefill_chunk``: the widest chunk a program writes at once, which
        # a window layer's ring has to hold beside the window behind it
        self._cache = make_paged_decode_cache(
            decode_module, max_slots, self.num_blocks, block_size,
            prefill_chunk=prefill_chunk or max_len,
        )
        # A model with per-slot state (a recurrence's, a convolution's):
        # the state rows ride in the same cache tree, one a slot, and what
        # rests on "a block of K/V is all there is to a prefix" is off or
        # refused, by name (``_refuse_state``).
        self.stateful = has_state(self._cache)
        self.state_bytes = state_bytes(self._cache)
        self.state_resets = 0  # rows zeroed: at admission and at release
        # A window layer's latent: a ring of blocks a slot, outside the block
        # table. What a slot holds of it never passes ``window_columns``,
        # however long its sequence; like state, it is the slot's own, so a
        # resident prefix says nothing of it (``_refuse_state``).
        rings = [leaf for _, leaf in leaves_of_kind(self._cache, WINDOW)]
        self.windowed = bool(rings)
        self.window_columns = max(
            (leaf.shape[1] * leaf.shape[-1] for leaf in rings), default=0)
        self.window_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in rings)
        self._cols: Dict[int, int] = {}  # columns each live slot has backed
        # Prompts are never left-padded (shared prefixes must land at
        # identical cache columns in every slot): the pad vector stays
        # zero, an operand the decode and speculation programs still take.
        self._pad = jnp.zeros((max_slots,), jnp.int32)
        self._free: List[int] = list(range(max_slots))
        self.admitted_total = 0  # lifetime admissions (slot reuse visible)
        self.table = BlockTable(max_slots, self.blocks_per_slot,
                                self.num_blocks)
        self._ref = np.zeros((self.num_blocks,), np.int64)
        self._free_blocks: List[int] = list(range(self.num_blocks))
        # With state, a resident block of K/V says nothing of the state the
        # other layers held at its last column: nothing is adopted.
        self.prefix = (PrefixCache(block_size)
                       if prefix_cache and not self.stateful
                       and not self.windowed else None)
        # Lazy process-registry mirror (same latch-False idiom as
        # ServingMetrics): the fleet aggregator federates these from
        # /metrics scrapes without the pool knowing it's being watched.
        self._mirror = None
        self._pushed_hits = 0
        self._pushed_lookups = 0
        # Per-tenant cost attribution (obs/tenancy.py): the scheduler
        # names each slot's owning tenant before binding any blocks,
        # and the pool integrates block-seconds (elapsed wall seconds
        # x resident block count) into the attached CostLedger at
        # every block-count change and at release — each integration
        # window therefore has a constant block count, so occupancy
        # bills exactly from the first prefix-bound instant to the
        # final decref. Unattached (no ledger), all of this is dead
        # dict lookups — the ≤2% overhead ceiling stays intact.
        self._costs = None
        self._cost_clock = None
        self._owner: Dict[int, Optional[str]] = {}
        self._billed_at: Dict[int, float] = {}

    # -- donation-guarded cache access -------------------------------------

    @staticmethod
    def _guard(tree, name: str):
        # One leaf suffices: every leaf of a donated pytree is deleted
        # by the same program call.
        leaf = jax.tree_util.tree_leaves(tree)[0]
        if getattr(leaf, "is_deleted", lambda: False)():
            raise DonatedBufferError(
                f"KV pool {name} was donated to a compiled program and "
                "its buffers are gone; use the value returned by that "
                "program (the engine swaps it back via pool.swap)"
            )
        return tree

    @property
    def cache(self):
        """The live cache pytree (raises ``DonatedBufferError`` if the
        held buffers were donated without a ``swap``)."""
        return self._guard(self._cache, "cache")

    @property
    def pad(self):
        """Per-slot left-pad counts (all zero: nothing pads a prompt),
        same donation guard as ``cache``."""
        return self._guard(self._pad, "pad")

    def swap(self, new_cache, new_pad=None) -> None:
        """Install the cache (and optionally pad) a donating program
        returned. The old references are dead the moment the program was
        dispatched — this is the only legal way to keep the pool live."""
        self._cache = new_cache
        if new_pad is not None:
            self._pad = new_pad

    # -- slot bookkeeping --------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.max_slots - len(self._free)

    def active_slots(self) -> List[int]:
        """Occupied slot ids, ascending (the decode step's active mask)."""
        free = set(self._free)
        return [s for s in range(self.max_slots) if s not in free]

    def acquire(self) -> Optional[int]:
        """Claim a free slot id, or None when the pool is saturated."""
        if not self._free:
            return None
        return self._free.pop()

    # -- block accounting ----------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free_blocks)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self._free_blocks)

    def _incref(self, block: int) -> None:
        self._ref[block] += 1

    def _decref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise RuntimeError(
                f"KV block {block} double-released: refcount is already 0 "
                "(a slot row or prefix entry decref'd a block it did not "
                "hold — allocator bookkeeping is corrupt)"
            )
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free_blocks.append(block)

    def _alloc_block(self) -> int:
        """Claim a free block (refcount 1). Under pressure, evict
        least-recently-used prefix-cache entries until one frees — with
        default sizing this always terminates before the cache empties."""
        from elephas_tpu import obs

        while not self._free_blocks:
            entry = (self.prefix.evict_lru(self._decref)
                     if self.prefix is not None else None)
            if entry is None:
                raise RuntimeError(
                    f"out of KV blocks ({self.num_blocks} total, "
                    f"{self.max_slots} slots x {self.blocks_per_slot} "
                    "blocks/slot needed worst-case) and no evictable "
                    "prefix entries — num_blocks is undersized"
                )
            obs.default_flight_recorder().note(
                "prefix_evict", "info", blocks=len(entry.blocks),
                tokens=len(entry.tokens),
                resident=len(self.prefix),
            )
        block = self._free_blocks.pop()
        self._incref(block)
        return block

    # -- per-tenant occupancy billing ----------------------------------------

    def attach_cost_ledger(self, ledger, clock=None) -> None:
        """Wire a ``CostLedger`` so slot block occupancy bills to each
        slot's owning tenant as KV block-seconds. ``clock`` defaults to
        the ledger's own clock (the engine injects its clock so the
        fake clocks benchmarks and tests drive stay deterministic)."""
        self._costs = ledger
        self._cost_clock = clock if clock is not None else ledger.clock

    def set_slot_owner(self, slot: int, tenant: Optional[str]) -> None:
        """Name the tenant billed for ``slot``'s block occupancy from
        this instant on. The scheduler calls this BEFORE
        ``admit_prefix`` so prefix-bound blocks bill from their first
        resident moment, not from first decode."""
        self._owner[slot] = tenant
        if self._cost_clock is not None:
            self._billed_at[slot] = self._cost_clock()

    def _bill_slot(self, slot: int, *, cow: bool = False) -> None:
        """Integrate ``slot``'s occupancy since its last bill into the
        attached ledger: elapsed seconds x blocks currently resident.
        Called before every block-count change (``ensure_cols`` runs it
        each decode step, making it the steady-state integrator) and on
        release (the closing bill). ``cow=True`` additionally counts a
        copy-on-write block copy against the owning tenant."""
        if self._costs is None:
            return
        last = self._billed_at.get(slot)
        if last is None:
            return  # slot never owned: nothing to attribute
        now = self._cost_clock()
        blocks = int((self.table.rows[slot] >= 0).sum())  # host-ok: numpy table
        seconds = (now - last) * blocks
        self._billed_at[slot] = now
        if seconds > 0.0 or cow:
            self._costs.record_block_seconds(
                self._owner.get(slot), seconds, cow=cow)

    def assert_block_invariants(self) -> None:
        """Free-list/refcount conservation — every block is either free
        (refcount 0) or accounted for by exactly its refcount many
        holders (slot rows + prefix entries). Tests call this after
        seeded churn; it is NOT on the hot path."""
        free = set(self._free_blocks)
        assert len(free) == len(self._free_blocks), "free list has dupes"
        holders = np.zeros((self.num_blocks,), np.int64)
        for row in self.table.rows:
            for b in row:
                if b >= 0:
                    holders[b] += 1
        if self.prefix is not None:
            for entry in self.prefix._entries.values():
                for b in entry.blocks:
                    holders[b] += 1
        for b in range(self.num_blocks):
            assert (b in free) == (self._ref[b] == 0), (
                f"block {b}: ref={self._ref[b]} vs free={b in free}")
            assert self._ref[b] == holders[b], (
                f"block {b}: ref={self._ref[b]} != holders={holders[b]}")

    # -- slot lifecycle ------------------------------------------------------

    def _refuse_state(self, what: str) -> None:
        if self.stateful:
            raise NotImplementedError(
                f"{what} is not built for a model with per-slot state: the "
                "pool's blocks hold the K/V of its attention layers only, and "
                "the recurrent and convolution state of the other layers "
                "would be left behind"
            )
        if self.windowed:
            raise NotImplementedError(
                f"{what} is not built for a model with window layers: the "
                "pool's blocks hold the latents and index keys of its full "
                "layers only, and a window layer's last columns live in a "
                "ring of the slot's own, outside the block table, which "
                "would be left behind"
            )

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes one cached token holds over every K/V leaf: a block's
        bytes over the columns it holds, all layers. A latent leaf counts
        once, as key and value in one."""
        return sum(leaf.size // leaf.shape[0] * leaf.dtype.itemsize
                   for _, leaf in leaves_of_kind(self._cache, KV)
                   ) // self.block_size

    def state_signals(self) -> dict:
        """What the ``step`` event says of the state rows: a row is in use
        from its slot's admission to its release."""
        out = {
            "state_slots_in_use": self.active_count if self.stateful else 0,
            "state_slots_total": self.max_slots if self.stateful else 0,
            "state_bytes": self.state_bytes,
        }
        if self.windowed:
            # of each window layer: the columns the live slots hold, against
            # the bound (a slot's ring, times the slots)
            out["window_columns_resident"] = sum(
                min(cols, self.window_columns) for cols in self._cols.values())
            out["window_columns_bound"] = self.max_slots * self.window_columns
            out["window_columns_per_slot"] = self.window_columns
            out["window_bytes"] = self.window_bytes
        return out

    def admit_prefix(self, slot: int, prompt: Sequence[int]) -> int:
        """Bind the longest resident prefix of ``prompt`` to ``slot``
        (bump refcounts, no device work, no prefill compute). Returns
        the matched token count — prefill resumes at that column.
        A stateful pool matches nothing: prefill starts at column 0, where
        the chunk program hands the module a zeroed state row. A pool with
        window layers matches nothing either: the ring is the slot's own."""
        if self.stateful:
            self.state_resets += 1
        if self.prefix is None:
            return 0
        self._bill_slot(slot)  # close the zero-block window pre-bind
        matched, blocks = self.prefix.match(prompt)
        for i, b in enumerate(blocks):
            self._incref(b)
            self.table.set(slot, i, b)
        self._mirror_push()
        return matched

    def commit_prefix(self, slot: int, prompt: Sequence[int]) -> None:
        """Publish ``slot``'s freshly-prefilled prompt to the prefix
        cache (full blocks only) so requests arriving DURING this
        conversation can share it — not just after release."""
        if self.prefix is None:
            return
        row = self.table.rows[slot]
        nfull = len(prompt) // self.block_size
        blocks = [int(row[i]) for i in range(nfull)]  # host-ok: numpy table
        assert all(b >= 0 for b in blocks), (
            f"slot {slot}: prompt columns not fully backed at commit")
        self.prefix.insert(tuple(prompt)[:nfull * self.block_size],
                           blocks, self._incref)
        self._mirror_push()

    def ensure_cols(self, slot: int, upto: int) -> None:
        """Back columns ``[0, upto)`` of ``slot`` with physical blocks
        (prefix-shared blocks already in the row count as backed)."""
        if upto > self.virtual_len:
            raise ValueError(
                f"slot {slot} needs column {upto - 1} but rows are "
                f"{self.virtual_len} columns"
            )
        self._bill_slot(slot)  # per-decode-step occupancy integration
        if self.windowed:
            self._cols[slot] = max(self._cols.get(slot, 0), upto)
        row = self.table.rows[slot]
        for i in range(-(-upto // self.block_size)):
            if row[i] < 0:
                self.table.set(slot, i, self._alloc_block())
        self._mirror_push()

    def ensure_decode_col(self, slot: int, col: int) -> None:
        """Back (and exclusively own) the single column the next decode
        step writes for ``slot``."""
        self.ensure_cols(slot, col + 1)
        self.ensure_writable(slot, col)

    def ensure_writable(self, slot: int, col: int) -> int:
        """Copy-on-write guard: make the block backing ``col``
        exclusively owned by ``slot`` before a write. Normal serving
        never triggers the copy (shared blocks are full and writes start
        at the block-aligned shared boundary); ``fork_slot`` aliases do.
        Returns the (possibly fresh) physical block id."""
        i = col // self.block_size
        block = int(self.table.rows[slot, i])  # host-ok: numpy table
        if block < 0:
            raise ValueError(f"slot {slot} column {col} is unallocated")
        if self._ref[block] == 1:
            return block
        # The copy is work the FORKING slot's tenant caused; bill the
        # elapsed window at the old count and count the COW event.
        self._bill_slot(slot, cow=True)
        fresh = self._alloc_block()
        self.swap(_copy_block(self.cache, jnp.int32(block),
                              jnp.int32(fresh)))
        self.table.set(slot, i, fresh)
        self._decref(block)
        self._mirror_push()
        return fresh

    def fork_slot(self, parent: int) -> Optional[int]:
        """Alias a fresh slot over ``parent``'s blocks (refcounts bumped,
        zero device copies) — both slots read the same physical K/V until
        one writes, at which point ``ensure_writable`` copies just the
        written block. Returns the child slot id, or None when the pool
        is out of slots."""
        self._refuse_state("fork_slot")
        if parent in self._free:
            raise ValueError(f"slot {parent} is free; nothing to fork")
        child = self.acquire()
        if child is None:
            return None
        for i, b in enumerate(self.table.rows[parent]):
            if b >= 0:
                self._incref(int(b))  # host-ok: numpy table
                self.table.set(child, i, int(b))  # host-ok: numpy table
        # A fork's occupancy is the forking tenant's doing: the child
        # inherits the parent's owner and starts its own billing window
        # at full block count (every aliased block bills twice — once
        # per holder — matching the refcounts it actually pins).
        if parent in self._owner:
            self.set_slot_owner(child, self._owner[parent])
        return child

    def release(self, slot: int,
                tokens: Optional[Sequence[int]] = None) -> None:
        """Refcount-aware release: ``slot`` returns to the free list and
        DROPS one reference on each of its blocks — shared blocks
        survive for their other holders. ``tokens`` — the slot's full
        token chain, prompt + generated — lets the prefix cache adopt
        the full-block prefixes before the references drop, so a
        follow-up turn of the same conversation admits without
        re-prefilling. Double-releasing the slot raises ``ValueError``;
        a corrupt row that decrefs a free block raises
        ``RuntimeError``."""
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        if not 0 <= slot < self.max_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.max_slots})")
        self._bill_slot(slot)  # closing bill: occupancy up to release
        self._owner.pop(slot, None)
        self._billed_at.pop(slot, None)
        row = self.table.rows[slot]
        if tokens is not None and self.prefix is not None:
            backed = int((row >= 0).sum())  # host-ok: numpy table
            nfull = min(len(tokens) // self.block_size, backed)
            if nfull > 0:
                self.prefix.insert(
                    tuple(tokens)[:nfull * self.block_size],
                    [int(row[i]) for i in range(nfull)],  # host-ok: numpy table
                    self._incref,
                )
        for b in row:
            if b >= 0:
                self._decref(int(b))  # host-ok: numpy table
        self.table.clear_row(slot)
        self._free.append(slot)
        self._cols.pop(slot, None)
        if self.stateful or self.windowed:
            self.swap(_clear_state_row(self.cache, jnp.int32(slot)))
            self.state_resets += 1
        self._mirror_push()

    # -- cross-tier KV handoff -----------------------------------------------
    #
    # The disaggregated-serving transfer unit: a prefill replica exports
    # one slot's filled blocks through contiguous host buffers
    # (``export_blocks``), the wire codec frames them
    # (``parameter.wire.encode_kv_blocks``), and the decode replica
    # rebinds them into its own pool (``import_blocks``) — refcounts are
    # TRANSFERRED, not copied: the exporter's references drop with its
    # normal ``release``, the importer derives fresh references locally
    # (slot row + prefix-chain entries), and the billing window moves
    # with the blocks (closed at export, reopened by the importer's
    # ``set_slot_owner``) so cross-tier block-seconds never double-bill.

    def _kv_leaf_names(self) -> Tuple[List[str], List]:
        """(names, leaves) of every K/V leaf in tree order —
        the deterministic leaf enumeration both handoff sides share
        (same model config → same tree → same order)."""
        found = leaves_of_kind(self.cache, KV)
        return ([jax.tree_util.keystr(path) for path, _ in found],
                [leaf for _, leaf in found])

    def export_blocks(self, slot: int) -> Dict:
        """Gather ``slot``'s resident blocks into contiguous host
        buffers for a cross-tier handoff.

        Returns ``{"block_size", "blocks", "leaves", "arrays"}`` —
        ``arrays[i]`` is the ``(blocks, heads, rows, lanes)``
        host copy of leaf ``leaves[i]`` at the slot's block ids, in row
        order. Also CLOSES the slot's block-seconds billing window (the
        satellite-6 fix): occupancy up to this instant bills the owning
        tenant here, and the subsequent local ``release`` bills nothing
        — the decode replica's ``set_slot_owner`` opens the fresh
        window, so summed cross-tier block-seconds equal a monolithic
        run's within one billing window instead of double-counting the
        in-flight span."""
        from elephas_tpu.serving import host_sync

        self._refuse_state("export_blocks (a cross-tier KV handoff)")
        if slot in self._free:
            raise ValueError(f"slot {slot} is free; nothing to export")
        row = self.table.rows[slot]
        n = int((row >= 0).sum())  # host-ok: numpy table
        if n == 0:
            raise ValueError(f"slot {slot} has no resident blocks")
        ids = [int(row[i]) for i in range(n)]  # host-ok: numpy table
        # Close the billing window: bill up to now, then drop the
        # window so release()'s closing bill is a no-op for this slot.
        self._bill_slot(slot)
        self._owner.pop(slot, None)
        self._billed_at.pop(slot, None)
        names, leaves = self._kv_leaf_names()
        ids_dev = jnp.asarray(np.array(ids, np.int32))  # host-ok: host list
        host = host_sync.fetch([leaf[ids_dev] for leaf in leaves])
        return {
            "block_size": self.block_size,
            "blocks": n,
            "leaves": names,
            "arrays": [np.ascontiguousarray(a) for a in host],
        }

    def import_blocks(self, slot: int, tokens: Sequence[int],
                      arrays: Sequence[np.ndarray],
                      leaf_names: Optional[Sequence[str]] = None) -> int:
        """Rebind an exported block set to ``slot`` of THIS pool.

        ``tokens`` is the chain the blocks hold (the prompt plus the
        prefill-sampled first token's columns are NOT included — exactly
        the columns with K/V written, as the exporter's scheduler knew
        them). The local prefix cache is consulted first: matched
        full-block prefixes admit by incref (the cross-tier prefix hit
        — a shared system prompt costs zero uploads past its first
        import), only the remaining blocks allocate and upload, and the
        full-block chain is inserted into this pool's ``PrefixCache``
        so later handoffs and local admissions share it. Returns the
        matched token count. The caller owns slot acquisition and
        ``set_slot_owner`` (which opens the billing window the exporter
        closed). Raises ``ValueError`` on any structural mismatch —
        callers map that to the handoff reject path."""
        self._refuse_state("import_blocks (a cross-tier KV handoff)")
        bs = self.block_size
        if slot in self._free:
            raise ValueError(f"slot {slot} is free; acquire it first")
        names, leaves = self._kv_leaf_names()
        if leaf_names is not None and list(leaf_names) != names:
            raise ValueError(
                f"handoff leaf structure mismatch: got {list(leaf_names)}, "
                f"this pool has {names}"
            )
        if len(arrays) != len(names):
            raise ValueError(
                f"handoff carries {len(arrays)} leaves, pool has {len(names)}"
            )
        n_blocks = int(arrays[0].shape[0]) if arrays else 0  # host-ok: host array
        for name, leaf, arr in zip(names, leaves, arrays):
            want = (n_blocks,) + tuple(leaf.shape[1:])
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"handoff leaf {name} shape {tuple(arr.shape)} != {want}"
                )
            if np.dtype(arr.dtype) != np.dtype(leaf.dtype):
                raise ValueError(
                    f"handoff leaf {name} dtype {arr.dtype} != {leaf.dtype}"
                )
        if not tokens or n_blocks != -(-len(tokens) // bs):
            raise ValueError(
                f"handoff block count {n_blocks} does not back "
                f"{len(tokens)} tokens at block size {bs}"
            )
        if n_blocks > self.blocks_per_slot:
            raise ValueError(
                f"handoff needs {n_blocks} blocks/slot, rows have "
                f"{self.blocks_per_slot}"
            )
        self._bill_slot(slot)  # close the zero-block window pre-bind
        matched, mblocks = (
            self.prefix.match(tokens) if self.prefix is not None else (0, [])
        )
        for i, b in enumerate(mblocks):
            self._incref(b)
            self.table.set(slot, i, b)
        start = matched // bs
        fresh = []
        try:
            for i in range(start, n_blocks):
                b = self._alloc_block()
                self.table.set(slot, i, b)
                fresh.append(b)
        except RuntimeError:
            # Out of blocks mid-import: unwind every reference this
            # import took so the slot releases clean (the caller's
            # reject path re-prefills locally; nothing may leak).
            for i in range(start + len(fresh)):
                self._decref(int(self.table.rows[slot][i]))  # host-ok: numpy table
            self.table.clear_row(slot)
            raise
        # matched < len(tokens) (match is strictly shorter), so at least
        # one block always uploads — the jit also sets the index vectors.
        ids_dev = jnp.asarray(np.array(fresh, np.int32))  # host-ok: host list
        payload = tuple(
            jnp.asarray(np.ascontiguousarray(a[start:])) for a in arrays
        )
        self.swap(_write_imported_blocks(
            self.cache, ids_dev, payload, jnp.int32(slot),
            jnp.int32(len(tokens)),
        ))
        self.commit_prefix(slot, tokens)
        self._mirror_push()
        return matched

    # -- compiled-program operands -------------------------------------------

    def device_table(self):
        """The (max_slots, blocks_per_slot) device block table the
        compiled gather/scatter programs consume (unallocated = the
        out-of-range id ``num_blocks``; cached until a row changes)."""
        return self.table.device()

    # -- saturation-plane signals --------------------------------------------

    def load_signals(self) -> dict:
        """Block-granular KV pressure for the load tracker: free blocks
        beat free slots as a saturation signal once blocks are shared
        (eight slots can be live on three slots' worth of storage)."""
        return {
            "kv_blocks_free": len(self._free_blocks),
            "kv_blocks_total": self.num_blocks,
            "prefix_hit_rate": (
                self.prefix.hit_rate if self.prefix is not None else None
            ),
        }

    def prefix_stats(self) -> dict:
        if self.prefix is None:
            return {"prefix_hits": 0, "prefix_lookups": 0,
                    "prefix_hit_rate": None, "prefix_tokens_saved": 0,
                    "prefix_evictions": 0, "prefix_resident": 0,
                    "prefix_cache": (
                        "off: per-slot state" if self.stateful
                        else "off: window layers keep a ring a slot"
                        if self.windowed else "off")}
        return {
            "prefix_cache": "on",
            "prefix_hits": self.prefix.hits_total,
            "prefix_lookups": self.prefix.lookups_total,
            "prefix_hit_rate": self.prefix.hit_rate,
            "prefix_tokens_saved": self.prefix.tokens_saved_total,
            "prefix_evictions": self.prefix.evictions_total,
            "prefix_resident": len(self.prefix),
        }

    def _mirror_push(self) -> None:
        mirror = self._mirror
        if mirror is None:
            try:
                from elephas_tpu import obs

                reg = obs.default_registry()
                mirror = (
                    reg.gauge("serving_kv_blocks_free",
                              help="unreferenced KV blocks in the paged "
                                   "pool"),
                    reg.counter("serving_prefix_cache_hit_total",
                                help="prompt admissions that reused a "
                                     "resident prefix"),
                    reg.counter("serving_prefix_cache_lookup_total",
                                help="prompt admissions that consulted "
                                     "the prefix cache"),
                    reg.gauge("serving_prefix_cache_hit_rate",
                              help="lifetime prefix-cache hit rate"),
                )
            except Exception:
                mirror = False
            self._mirror = mirror
        if not mirror:
            return
        gauge_free, hit_counter, lookup_counter, rate_gauge = mirror
        gauge_free.set(len(self._free_blocks))
        if self.prefix is not None:
            hit_counter.inc(self.prefix.hits_total - self._pushed_hits)
            lookup_counter.inc(
                self.prefix.lookups_total - self._pushed_lookups
            )
            self._pushed_hits = self.prefix.hits_total
            self._pushed_lookups = self.prefix.lookups_total
            rate = self.prefix.hit_rate
            if rate is not None:
                rate_gauge.set(rate)
