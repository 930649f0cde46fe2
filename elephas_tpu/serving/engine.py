"""InferenceEngine — the serving frontend over the continuous-batching
scheduler.

Binds a ``CompiledModel`` (or bare ``TransformerLM`` + params) to TWO
compiled programs that together serve arbitrary request traffic, both
over the paged ``PagedKVPool`` in place through its block table:

- ``prefill``: one prompt CHUNK of one slot per call, fixed
  ``(1, prefill_chunk)`` width (the last chunk right-padded); writes the
  chunk's K/V into the slot's blocks and samples the token after it;
- ``decode``: one token for every pool slot per call, fixed
  ``(max_slots,)`` shapes, per-slot cache positions. The previous
  step's device token vector chains straight back in as the next step's
  input (one-step-lookahead pipelining — see ``serving.scheduler``).
  Freshly admitted lanes are spliced in with a ``where`` override INSIDE
  the program; free lanes are masked so their cache index vectors
  freeze.

The KV-cache operand of both is DONATED (``donate_argnums``), so XLA
rewrites the pool in place instead of copying every layer's K/V each
call. What the served tokens are held to is ``models.transformer.
generate()``, one row at a time.

Admission, eviction, slot reuse and backpressure all happen HOST-side
between calls — neither program ever retraces once warm, which is the
entire point of the fixed-shape pool (``_prefill_traces`` /
``_decode_traces`` count compilations; tests pin them to 1). The only
blocking device→host reads go through ``serving.host_sync``
(``scripts/lint_blocking.py`` enforces this statically).

Tensor-parallel serving (``shard_serving``): before the first request,
annotate the parameters with the Megatron ``LM_RULES`` ``NamedSharding``s
and every KV-pool leaf with a head-axis sharding, then re-jit both
programs with ``in_shardings``/``out_shardings`` — GSPMD lowers the same
two programs across the mesh's ``'model'`` axis and inserts the
collectives itself. No ``shard_map``, so it runs on any backend that can
host a mesh (including ``--xla_force_host_platform_device_count``
virtual CPUs).

Usage::

    engine = InferenceEngine(compiled, max_slots=4, max_prompt_len=16,
                             max_len=64, stop_token=eos)
    engine.shard_serving(build_mesh(num_data=1, num_model=4))  # optional
    rid = engine.submit([5, 3, 9], max_new_tokens=20)
    result = engine.result(rid)          # drives steps inline, or waits
    ...                                  # on a serve_forever thread
    stop = threading.Event()
    t = threading.Thread(target=engine.serve_forever, args=(stop,))

``submit`` applies admission control (bounded queue) and raises
``QueueFull`` with a ``retry_after`` hint; ``submit_with_retry`` wraps
it in the same bounded-backoff loop the parameter-server client uses
for connect (``parameter.client._RETRY_DELAYS``).
"""

from __future__ import annotations

import dataclasses
import inspect
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu import obs
from elephas_tpu.models.decode_cache import (
    INDEX,
    KV,
    WINDOW,
    PagedDecode,
    first_index,
    has_latent,
    has_state,
    leaf_kind,
    leaf_name,
    leaves_of_kind,
)
from elephas_tpu.serving.kv_pool import PagedKVPool
from elephas_tpu.serving.metrics import ServingMetrics
from elephas_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    GenerationResult,
    QueueFull,
    Request,
    RequestQueue,
)

# Bounded backoff for submit_with_retry — same contract as the parameter
# server client's connect loop: a handful of increasing delays, then the
# error propagates.
_RETRY_DELAYS = (0.1, 0.2, 0.4, 0.8, 1.3)


class InferenceEngine:
    """Online inference over a ``TransformerLM`` decode path.

    Parameters
    ----------
    compiled: ``CompiledModel`` (module + params) or a flax
        ``TransformerLM``; in the latter case pass ``params=``.
    max_slots: concurrent sequences (decode batch width).
    max_prompt_len: the longest prompt ``submit`` takes.
    max_len: KV-cache columns per slot; a sequence may generate up to
        ``max_len - max_prompt_len`` tokens.
    stop_token: default EOS (per-request override via ``submit``).
    queue_depth: admission-control bound on queued (unadmitted) requests.
    temperature/top_k: 0/0 = greedy (default); otherwise sampled with an
        engine-owned PRNG stream.
    pipeline: one-step-lookahead decode (default). ``False`` selects the
        unpipelined oracle path — token-identical, device idles during
        host bookkeeping; exists for A/B tests and benchmarks.
    kv_block_size: columns per physical KV block (default
        ``max_prompt_len``). Smaller blocks share finer-grained
        prefixes at the cost of a wider block table.
    kv_blocks: physical block count (default
        ``max_slots * ceil(max_len / kv_block_size)`` — always enough
        for every slot, so prefix eviction can never dead-end).
    prefix_cache: keep released/committed prompt chains resident so
        later prompts sharing a full-block prefix admit by refcount
        instead of re-prefilling (default True).
    prefill_chunk: prefill chunk width (default ``max_prompt_len`` =
        one-shot). Smaller chunks split long prompts into several
        compiled-program calls so decode steps can interleave between
        them.
    prefill_chunks_per_step: max prefill chunks dispatched per scheduler
        step (default None = run every pending chunk at admission). Set
        to a small int to bound how long any one step's prefill work can
        stall in-flight decodes — the ITL-p99 protection the chunked
        program exists for.
    speculative: draft-and-verify decode (default False).
        Each pipelined dispatch drafts ``gamma`` tokens per slot with a
        cheap draft source and verifies the whole window in ONE batched
        target forward — between 1 and ``gamma + 1`` tokens emitted per
        step, byte-identical to plain decode by construction (see
        ``serving.spec``). Plain decode stays the oracle.
    gamma: draft window length per speculation step (default 4).
    draft_layers: shallow-stack SELF-draft — the target's first K layers
        draft with zero extra weights (default ``num_layers // 2`` when
        ``speculative`` and no ``draft_source`` given).
    draft_source: an explicit ``serving.spec.DraftSource`` (e.g.
        ``DraftModelSource`` pulling a small draft model version-gated
        from a parameter-server client). Mutually exclusive with
        ``draft_layers``; model sources require ``prefix_cache=False``
        (a refcount-admitted prefix would leave the draft cache cold).
    sink: optional ``metrics.JsonlSink`` for request/step records.
    tracer: optional ``obs.Tracer`` recording the per-request span tree
        (submit→queue→admit→prefill→decode→finish, one ``req:<id>``
        track each) plus per-iteration scheduler spans. Defaults to the
        process-global tracer (a no-op unless ``obs.enable_tracing()``
        ran). The tracer's ``clock`` must match the engine's — both
        default to ``time.monotonic``.
    """

    def __init__(
        self,
        compiled,
        params=None,
        *,
        max_slots: int = 8,
        max_prompt_len: int = 32,
        max_len: int = 128,
        stop_token: Optional[int] = None,
        queue_depth: int = 16,
        pad_token: int = 0,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        pipeline: bool = True,
        kv_block_size: Optional[int] = None,
        kv_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        prefill_chunk: Optional[int] = None,
        prefill_chunks_per_step: Optional[int] = None,
        speculative: bool = False,
        gamma: int = 4,
        draft_layers: Optional[int] = None,
        draft_source=None,
        sink=None,
        clock=time.monotonic,
        tracer=None,
    ):
        module = getattr(compiled, "module", compiled)
        if params is None:
            params = getattr(compiled, "params", None)
        if params is None:
            raise ValueError("need params (or a CompiledModel carrying them)")
        if max_prompt_len >= max_len:
            raise ValueError(
                f"max_prompt_len ({max_prompt_len}) must leave room to "
                f"generate within max_len ({max_len})"
            )
        if getattr(module, "max_seq_len", max_len) < max_len:
            raise ValueError(
                f"max_len ({max_len}) exceeds module.max_seq_len "
                f"({module.max_seq_len})"
            )
        # The cache path replaces the training-time attention kernel
        # wholesale, exactly as `models.transformer.generate` does.
        self.decode_module = dataclasses.replace(
            module, decode=True, attention="dense"
        )
        self.params = params
        self.max_prompt_len = max_prompt_len
        self.stop_token = stop_token
        self.temperature = temperature
        self.top_k = top_k
        self.clock = clock
        self._rng = jax.random.PRNGKey(seed)
        self._greedy = temperature == 0.0

        self.tracer = tracer if tracer is not None else obs.default_tracer()
        if (draft_layers is not None or draft_source is not None) \
                and not speculative:
            raise ValueError(
                "draft_layers/draft_source require speculative=True"
            )
        if draft_layers is not None and draft_source is not None:
            raise ValueError(
                "draft_layers and draft_source are mutually exclusive"
            )
        chunk = (prefill_chunk if prefill_chunk is not None
                 else max_prompt_len)
        if not 1 <= chunk <= max_prompt_len:
            raise ValueError(
                f"prefill_chunk ({chunk}) must be in "
                f"[1, max_prompt_len={max_prompt_len}]"
            )
        self.prefill_chunk = chunk
        # A chunk may start as late as the last prompt column; its
        # compiled slice/scatter window must fit the virtual row
        # without clamping. A speculative verify window writes up to
        # gamma columns past the last decode column the same way.
        virtual_len = max_prompt_len - 1 + chunk
        if speculative:
            virtual_len = max(virtual_len, max_len + gamma)
        self.pool = PagedKVPool(
            self.decode_module, max_slots, max_len,
            block_size=(kv_block_size if kv_block_size is not None
                        else max_prompt_len),
            num_blocks=kv_blocks,
            prefix_cache=prefix_cache,
            virtual_len=virtual_len,
            prefill_chunk=chunk,
        )
        # What the engine has to know of the model it reads from the cache
        # tree: a leaf that is neither K/V nor an index is per-slot state.
        self.stateful = has_state(self.pool.cache)
        # ... and a K/V leaf may be a latent: key and value in one, one head.
        self.latent = has_latent(self.pool.cache)
        # A module that takes ``valid`` is told a chunk's real tokens: it
        # keeps padding out of what padding is not invisible to (a
        # recurrence, a router) and returns the one sampled row of logits.
        self.takes_valid = "valid" in inspect.signature(
            type(module).__call__).parameters
        # Per-step counters a module's layers sow (``counters`` collection):
        # their names, fixed when the programs are traced.
        self._counter_names: Tuple[str, ...] = ()
        self.spec = None
        if speculative and self.latent:
            raise NotImplementedError(
                "speculative decoding is not built for a latent cache: a "
                "draft or verify window attends a gathered contiguous row of "
                "keys and values a head, and a latent pool holds neither"
                + ("; nor for a window layer's ring, which a rejected draft "
                   "token would have overwritten a live column of"
                   if self.pool.windowed else "")
            )
        if speculative:
            from elephas_tpu.serving.spec import (
                SelfDraftSource,
                SpeculativeDecoder,
            )

            if draft_source is None:
                layers = (draft_layers if draft_layers is not None
                          else max(1, self.decode_module.num_layers // 2))
                draft_source = SelfDraftSource(layers)
            if draft_source.kind == "model" and prefix_cache:
                raise ValueError(
                    "a model draft source requires prefix_cache=False: a "
                    "prefix-matched admission fills the target pool by "
                    "refcount and would leave the draft cache cold"
                )
            self.spec = SpeculativeDecoder(self, draft_source, gamma=gamma)
        self.queue = RequestQueue(max_depth=queue_depth)
        self.metrics = ServingMetrics(sink=sink, clock=clock)
        # Saturation + goodput plane, both on the engine's clock: the
        # scheduler feeds the load tracker every step; finished results
        # are evaluated into the goodput ledger as they publish (canary
        # probes excluded — see _publish).
        self.load = obs.LoadTracker(clock=clock)
        self.slo = obs.GoodputLedger(clock=clock)
        # Per-tenant cost attribution: the scheduler bills queue
        # seconds, prefill/decode tokens, spec windows and terminal
        # statuses per request tenant; the pool integrates KV
        # block-seconds per owning slot. Canary-blind goodput rides
        # _publish (mirroring self.slo), so per-tenant burn matches
        # the fleet ledger's exclusions.
        self.costs = obs.CostLedger(clock=clock)
        self.pool.attach_cost_ledger(self.costs, clock)
        self.scheduler = ContinuousBatchingScheduler(
            self.pool,
            self.queue,
            self._chunk_prefill,
            self._decode,
            max_prompt_len=max_prompt_len,
            pad_token=pad_token,
            metrics=self.metrics,
            clock=clock,
            pipeline=pipeline,
            tracer=self.tracer,
            load=self.load,
            costs=self.costs,
            prefill_chunk=self.prefill_chunk,
            prefill_chunks_per_step=prefill_chunks_per_step,
            spec_decode_fn=(self.spec.dispatch if self.spec is not None
                            else None),
            gamma=gamma if speculative else None,
        )

        self._prefill_traces = 0
        self._decode_traces = 0
        self.mesh = None  # set by shard_serving
        self._name_attention()
        self._make_jits()

        self._req_ids = itertools.count()
        self._results: Dict[int, GenerationResult] = {}
        self._cond = threading.Condition()
        self._step_lock = threading.Lock()
        self._halted = False  # see halt(): a dead engine never steps again
        self.ops = None  # OpsServer, mounted on demand
        self.store = None  # TelemetryStore, mounted with ops (store_dir=)
        # Canary exclusion: req_ids submitted with canary=True (guarded
        # by _cond). Their results still publish normally — the driver
        # retrieves them via result() — but never reach the goodput
        # ledger, so real-traffic SLO accounting is canary-blind.
        self._canary_ids: set = set()
        self.canary = None  # CanaryDriver, attached on demand
        # Live model delivery (rollout/): the PS version the serving
        # params carry (None = the construction-time tree, no delivery
        # yet) and the WeightSubscriber whose on_step hook runs at
        # every decode-step boundary under _step_lock.
        self.model_version: Optional[int] = None
        self.subscriber = None

    def _make_jits(self, in_shardings=None, out_shardings=None):
        """(Re)build the two compiled entry points. With shardings the
        same two programs lower via GSPMD over the mesh — still exactly
        one prefill and one decode compile."""
        pre_in = pre_out = dec_in = dec_out = None
        if in_shardings is not None:
            pre_in, dec_in = in_shardings
            pre_out, dec_out = out_shardings
        # BOTH programs rewrite the pool, so both donate it (argnum 1):
        # the stale reference dies at dispatch (the pool's guard turns
        # any later read into a loud error).
        self._jit_prefill = jax.jit(
            self._chunk_prefill_impl, donate_argnums=(1,),
            in_shardings=pre_in, out_shardings=pre_out,
        )
        self._jit_decode = jax.jit(
            self._paged_decode_impl, donate_argnums=(1,),
            in_shardings=dec_in, out_shardings=dec_out,
        )
        # ``program_report``'s, built when asked for and kept: new programs,
        # new reports
        self._program_reports: Dict[str, Any] = {}
        self._report_lowering = threading.local()

    # -- compiled bodies ---------------------------------------------------

    def _chunk_prefill_impl(self, params, cache, table, tokens, slot,
                            start, valid, rng):
        """One prompt CHUNK for one slot, on the paged pool in place: the
        K/V leaves the module sees ARE the pool's, beside the slot's table
        row and ``start`` as its cache index. Each attention layer writes
        the chunk's columns into the blocks they land in and attends the
        slot's live blocks through the row
        (``ops.attention.paged_chunk_attention``): positions and
        causality come from the cache index, as in ``generate()``'s
        prefill (token identity), and no contiguous row is built. The
        slot's index vectors then advance to ``start + valid``.

        ``tokens`` is (1, chunk) with the final chunk RIGHT-padded;
        padded columns compute garbage K/V that lands at-or-past the
        slot's cache index (or, past its allocation, nowhere), stays
        causally invisible, and is overwritten by subsequent decode
        steps. ``slot``/``start``/``valid`` are traced — one compile
        covers every slot, chunk position, and ragged tail.

        A state leaf (``models.decode_cache``) is this slot's row alone:
        handed to the module zeroed where the chunk is a prompt's first,
        and written back as the module left it. Padding run through a
        recurrence is not invisible, so a stateful module is told
        ``valid`` and leaves the state after ``valid`` tokens; it then
        also returns the one row of logits that is sampled."""
        # Traced once per compilation — the counter measures retraces,
        # and the obs hook makes a surprise retrace (a silent 10×
        # regression if it happened per request) a visible counter +
        # trace marker.
        if not self._lowering_a_report():
            self._prefill_traces += 1
            from elephas_tpu.utils.compiler import note_retrace

            note_retrace("serving_prefill", count=self._prefill_traces)
        from elephas_tpu.models.transformer import sample_tokens_at

        row = jax.lax.dynamic_index_in_dim(table, slot, axis=0)

        def to_row(path, leaf):
            kind = leaf_kind(path)
            if kind == KV:
                return leaf
            if kind == INDEX:
                return jnp.full((1,), start, jnp.int32)
            own = jax.lax.dynamic_index_in_dim(leaf, slot, axis=0)
            if kind == WINDOW:  # the slot's ring: what it holds past the
                return own      # window's reach is never read
            return jnp.where(start == 0, jnp.zeros_like(own), own)

        row_cache = jax.tree_util.tree_map_with_path(to_row, cache)
        told = {"valid": valid[None]} if self.takes_valid else {}
        logits, mutated = self.decode_module.apply(
            {"params": params, "cache": row_cache},
            tokens,
            # a prompt's first chunk starts at 0 or where the whole blocks
            # of an adopted prefix end, every later one a chunk further
            paged=PagedDecode(
                row, self.prefill_attention,
                aligned=self.prefill_chunk % self.pool.block_size == 0,
                valid=valid),
            mutable=["cache", "counters"],
            **told,
        )
        # The chunk's LAST VALID position predicts the first new token
        # (only the final chunk's sample is ever read).
        last = logits[:, 0] if self.takes_valid else jax.lax.dynamic_slice_in_dim(
            logits, valid - 1, 1, axis=1)[:, 0]
        # Position-keyed sampling: rows are never left-padded, so the
        # sampled token's stream position is the prefilled depth start +
        # valid, and every program (chunk prefill, decode, speculative
        # verify) derives the same key for the same position: what makes
        # temperature decode byte-identical across all of them.
        with jax.named_scope("sample"):
            first = sample_tokens_at(
                last, rng, (start + valid)[None], self._greedy, self.top_k,
                self.temperature,
            )

        def back(path, pool_leaf, mut_leaf):
            kind = leaf_kind(path)
            if kind == KV:
                return mut_leaf  # the pool, the chunk's columns in it
            if kind == INDEX:
                # This slot advances to its true prefilled depth (NOT
                # start + chunk — the right-pad tail is garbage); every
                # other slot's entry is untouched.
                return pool_leaf.at[slot].set(start + valid)
            # State, or a window layer's ring: this slot's row alone, as the
            # module left it.
            return jax.lax.dynamic_update_slice_in_dim(
                pool_leaf, mut_leaf.astype(pool_leaf.dtype), slot, 0)

        new_cache = jax.tree_util.tree_map_with_path(
            back, cache, mutated["cache"]
        )
        return (first[0], new_cache) + self._counted(mutated)

    def _paged_decode_impl(self, params, cache, table, prev_tokens,
                           override_vals, override_mask, active_mask,
                           pad, rng):
        """One decode step over every slot, on the paged pool in place:
        the cache the module sees IS the pool plus the block table. Each
        layer writes the column of every active lane straight into its
        physical block and attends through the table
        (``ops.attention.paged_decode_attention``); no per-slot
        contiguous cache is built and nothing else of the pool moves."""
        if not self._lowering_a_report():
            self._decode_traces += 1
            from elephas_tpu.utils.compiler import note_retrace

            note_retrace("serving_decode", count=self._decode_traces)
        from elephas_tpu.models.transformer import sample_tokens_at

        # Pre-advance write column per lane (every layer advances in
        # lockstep, so the first index leaf speaks for all). A state
        # leaf is stepped by its layer, for the active lanes only.
        idx = first_index(cache)
        # Freshly-admitted lanes get their prefill first token here,
        # INSIDE the one compiled program — the pipelined scheduler
        # never materializes the token vector host-side.
        tokens = jnp.where(override_mask, override_vals, prev_tokens)
        logits, mutated = self.decode_module.apply(
            {"params": params, "cache": cache},
            tokens[:, None],
            pad_offset=pad,
            active=active_mask,
            paged=PagedDecode(table, self.decode_attention),
            mutable=["cache", "counters"],
        )
        with jax.named_scope("sample"):
            nxt = sample_tokens_at(
                logits[:, -1], rng, idx - pad + 1, self._greedy, self.top_k,
                self.temperature,
            )
        return (nxt, mutated["cache"]) + self._counted(mutated)

    def _counted(self, mutated) -> tuple:
        """What the module's layers sowed into ``counters`` in this apply,
        each name summed over the layers, as one float32 vector in the
        names' order: ``(vector,)``, or ``()`` for a module that counts
        nothing (its programs return what they always did)."""
        sown = mutated.get("counters")
        if not sown:
            return ()
        totals: Dict[str, Any] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(sown)[0]:
            name = next(k.key for k in reversed(path) if hasattr(k, "key"))
            totals[name] = totals.get(name, 0.0) + leaf
        self._counter_names = tuple(sorted(totals))
        return (jnp.stack([totals[n] for n in self._counter_names]).astype(
            jnp.float32),)

    # -- what the two programs are made of ----------------------------------

    _PROGRAMS = {"prefill": "_jit_prefill", "decode": "_jit_decode"}

    def _lowering_a_report(self) -> bool:
        """True on the thread that is lowering a program again for
        ``program_report``: the bodies run, and it is no retrace."""
        return getattr(self._report_lowering, "on", False)

    def program_args(self, which: str, sharding=None) -> tuple:
        """The abstract arguments (shapes, types, placement) with which the
        live engine calls its ``"prefill"`` or ``"decode"`` program. With
        ``sharding``, every one placed there instead: a described chip's,
        to compile for it without the chip."""
        if which not in self._PROGRAMS:
            raise ValueError(f"program is one of {sorted(self._PROGRAMS)}, got {which!r}")

        from elephas_tpu.obs.programs import shapes_of

        def arg(shape, dtype, placed=None):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=placed if sharding is None else sharding)

        with self._step_lock:  # between two steps the pool is whole
            params, cache, rng, pad = shapes_of(
                (self.params, self.pool.cache, self._rng, self.pool.pad), sharding)
        table = arg(self.pool.table.rows.shape, jnp.int32, self.pool.table.sharding)
        S = self.pool.max_slots
        if which == "prefill":
            return (params, cache, table, arg((1, self.prefill_chunk), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32), arg((), jnp.int32), rng)
        # ``prev_tokens`` is the previous step's output, as the pool's leaves
        # are: committed where they are (``_decode`` places it on a mesh)
        placed = jax.tree_util.tree_leaves(cache)[0].sharding if self.mesh is None \
            else NamedSharding(self.mesh, P())
        return (params, cache, table, arg((S,), jnp.int32, placed),
                arg((S,), jnp.int32), arg((S,), jnp.bool_), arg((S,), jnp.bool_),
                pad, rng)

    def program_report(self, which: str):
        """``obs.programs.ProgramReport`` of the ``"prefill"`` or
        ``"decode"`` program: which model part issued each of its
        instructions, and what its copies move. Built on first use and
        kept. The SAME jitted function is lowered with
        ``program_args(which)`` and compiled once more — a load where the
        persistent compile cache is on, a compile of the program's own
        length where it is not. Lowering runs the program's Python body
        again; that is no retrace, and ``prefill_traces``,
        ``decode_traces`` and ``retrace_total`` stay what they were."""
        from elephas_tpu.obs.programs import ProgramReport

        if which not in self._program_reports:
            args = self.program_args(which)
            self._report_lowering.on = True
            try:
                lowered = getattr(self, self._PROGRAMS[which]).lower(*args)
            finally:
                self._report_lowering.on = False
            self._program_reports[which] = ProgramReport.from_compiled(lowered.compile())
        return self._program_reports[which]

    def _kv_layout(self):
        """The pool's own K/V leaf and the head width its rows pack: the
        leaf's heads are the model's K/V heads, which grouped attention
        has fewer of than query heads; a block is ``block_size`` columns
        a head. Of a latent pool, the latent's leaf (an indexer's key beside
        it is a narrower leaf of the same layout); of a pool with compressed
        keys beside its K/V, a key's leaf."""
        found = leaves_of_kind(self.pool.cache, KV)
        leaf = next((leaf for path, leaf in found
                     if leaf_name(path) in ("cached_latent", "cached_key")), found[0][1])
        _, _, rows, lanes = leaf.shape
        return leaf, rows * lanes // self.pool.block_size

    def _every_latent_fits(self) -> bool:
        """Whether the latent kernels' tiles lower for EVERY column-minor
        leaf the pool holds, a window layer's ring read as the small pool it
        is: one body name serves all of a program's layers."""
        from elephas_tpu.ops.attention import _latent_fits

        heads = self.decode_module.num_heads
        cache = self.pool.cache
        for path, leaf in leaves_of_kind(cache, KV) + leaves_of_kind(cache, WINDOW):
            # an index key is scored by kernels of its own (``ops.sparse_index``)
            if leaf_name(path) in ("cached_latent", "cached_window_latent"):
                shape = (-1,) + leaf.shape[-3:]  # a ring: (slots, blocks, ...)
                if not _latent_fits(shape, leaf.dtype, shape[2], heads):
                    return False
        return True

    @property
    def decode_attention(self) -> str:
        """Name of the attention body the decode program is traced with:
        ``"paged_pallas"`` or ``"paged_xla"``, chosen from the backend,
        the mesh and the pool's layout."""
        from elephas_tpu.ops.attention import paged_decode_body

        leaf, head_dim = self._kv_layout()
        if self.latent and not self._every_latent_fits():
            return "paged_xla"
        return paged_decode_body(leaf.shape, leaf.dtype, head_dim, self.mesh,
                                 q_heads=self.decode_module.num_heads,
                                 latent=self.latent)

    @property
    def decode_kernel_blocks(self) -> Optional[int]:
        """Blocks of a lane that a grid step of the decode kernel folds,
        derived from the pool's layout as the kernel derives it; ``None``
        where the decode program runs the XLA body."""
        from elephas_tpu.ops.attention import paged_decode_blocks

        if self.decode_attention != "paged_pallas":
            return None
        leaf, head_dim = self._kv_layout()
        return paged_decode_blocks(
            leaf.shape, leaf.dtype, head_dim, self.decode_module.num_heads,
            self.pool.blocks_per_slot, latent=self.latent)

    @property
    def prefill_attention(self) -> str:
        """Name of the attention body the chunk program is traced with,
        as ``decode_attention`` names the decode program's: chosen from
        the backend, the mesh, the pool's layout and the chunk's width."""
        from elephas_tpu.ops.attention import paged_chunk_body

        leaf, head_dim = self._kv_layout()
        if self.latent and not self._every_latent_fits():
            return "paged_xla"
        return paged_chunk_body(leaf.shape, leaf.dtype, head_dim,
                                self.prefill_chunk, self.mesh,
                                q_heads=self.decode_module.num_heads,
                                latent=self.latent)

    @property
    def prefill_query_tile(self) -> Optional[int]:
        """Queries a tile of the latent chunk kernel, derived from the
        shapes as the kernel derives it; ``None`` where the chunk program
        runs another body (a K/V pool's kernel, or XLA's)."""
        return self._latent_chunk_tiles()[2]

    def _latent_chunk_tiles(self):
        """``(heads, blocks, queries)`` of the latent chunk kernel's tiles,
        or Nones where the chunk program's body is not that kernel."""
        from elephas_tpu.ops.attention import _latent_chunk_tiles

        if not self.latent or self.prefill_attention != "paged_pallas":
            return None, None, None
        return _latent_chunk_tiles(self.decode_module.num_heads,
                                   self.pool.block_size, self.prefill_chunk)

    def _name_attention(self):
        """The bodies both programs will be traced with and their kernels'
        tiles (the decode kernel's blocks a grid step, the latent chunk
        kernel's queries a tile), onto every ``step`` event."""
        self.metrics.decode_attention = self.decode_attention
        self.metrics.prefill_attention = self.prefill_attention
        self.metrics.decode_kernel_blocks = self.decode_kernel_blocks
        self.metrics.kv_block_size = self.pool.block_size
        _, blocks, tile = self._latent_chunk_tiles()
        self.metrics.prefill_query_tile = tile
        self.metrics.prefill_step_columns = blocks and blocks * self.pool.block_size
        self.metrics.prefill_chunk = self.prefill_chunk

    def _next_rng(self):
        # Sampling keys derive from (base key, pad-free stream position)
        # via fold_in inside the programs (``sample_tokens_at``), so the
        # engine key is a CONSTANT: the n-th token of a stream draws the
        # same random number no matter which program (plain decode,
        # chunked prefill, speculative draft/verify) samples it, or how
        # many device calls preceded it. That positional determinism is
        # the whole temperature-identity story.
        return self._rng

    def _chunk_prefill(self, tokens, slot, start, valid):
        """Scheduler-facing chunk closure: runs one compiled chunk and
        swaps the donated pool; returns the device token sampled at the
        chunk's last valid position (read only for the final chunk)."""
        first, new_cache, *counted = self._jit_prefill(
            self.params, self.pool.cache, self.pool.device_table(),
            tokens, slot, start, valid, self._next_rng(),
        )
        self.pool.swap(new_cache)
        if self.spec is not None:
            # Model draft sources mirror every prompt chunk into their
            # own cache (no-op for self-draft, which reads the pool).
            self.spec.prefill_chunk(tokens, slot, start, valid)
        if counted:
            return first, (self._counter_names, counted[0])
        return first

    def _decode(self, cache, prev_tokens, override_vals, override_mask,
                active_mask, pad):
        if self.mesh is not None:
            # An engine's first ``prev_tokens`` is the host's; every later
            # one is the previous step's output, whose type carries the
            # mesh. Placed alike, both hit one trace of the program.
            prev_tokens = jax.device_put(
                prev_tokens, NamedSharding(self.mesh, P()))
        nxt, new_cache, *counted = self._jit_decode(
            self.params, cache, self.pool.device_table(), prev_tokens,
            override_vals, override_mask, active_mask, pad,
            self._next_rng(),
        )
        if counted:
            return nxt, new_cache, (self._counter_names, counted[0])
        return nxt, new_cache

    # -- tensor-parallel serving -------------------------------------------

    def shard_serving(self, mesh, rules=None):
        """Make both compiled programs tensor-parallel over ``mesh``'s
        ``'model'`` axis (GSPMD: annotate, don't rewrite).

        Parameters get the Megatron ``NamedSharding``s from
        ``tensor_parallel.param_specs`` (``rules`` defaults to
        ``LM_RULES``); every KV-pool K/V leaf is sharded over its heads
        axis (index vectors and pad replicated); prefill/decode are
        re-jit with explicit ``in_shardings``/``out_shardings`` so both
        programs lower sharded. Must be called BEFORE the first request
        — re-jitting warm programs would break the one-compile-each
        invariant, so a warm engine is refused.

        Returns ``self`` (builder style).
        """
        from elephas_tpu.parallel.mesh import MODEL_AXIS
        from elephas_tpu.parallel.tensor_parallel import (
            decode_cache_specs,
            param_specs,
        )

        if self.stateful:
            raise NotImplementedError(
                "shard_serving is not built for a model with per-slot "
                "state: the state rows have no sharding rule, and the "
                "scan's kernel is not partitioned by a mesh"
            )
        if self.pool.windowed:
            raise NotImplementedError(
                "shard_serving is not built for a model with window layers: "
                "a slot's ring of a window layer's latent has no sharding "
                "rule (one latent head: there is no head axis to divide), "
                "and the latent kernels are not partitioned by a mesh"
            )
        if getattr(self.decode_module, "experts_held", None) is not None:
            raise NotImplementedError(
                "shard_serving is not built for a routed expert layer: the "
                "sharding rules place no expert over a mesh, and the "
                "exchange of tokens between the devices that hold them is "
                "not built; the module is told which experts it holds "
                "(experts_held) and serves its share on one device"
            )
        if self._prefill_traces or self._decode_traces or \
                self.pool.admitted_total:
            raise RuntimeError(
                "shard_serving must run before the first request: the "
                "engine's programs are already compiled/warm, and "
                "re-jitting them would break the exactly-one-compile "
                "invariant"
            )
        tp = mesh.shape.get(MODEL_AXIS, 1)
        heads = self.decode_module.num_heads
        if heads % tp != 0:
            raise ValueError(
                f"num_heads ({heads}) must divide evenly over the "
                f"'{MODEL_AXIS}' mesh axis ({tp}) to shard the KV pool"
            )

        def named(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P),
            )

        repl = NamedSharding(mesh, P())
        p_sh = named(param_specs(self.params, rules))
        pool_sh = named(decode_cache_specs(self.pool.cache))

        # Place params and the (still-empty) pool on the mesh, then
        # re-jit so both programs lower via GSPMD with these layouts.
        self.params = jax.device_put(self.params, p_sh)
        self.pool.swap(
            jax.device_put(self.pool.cache, pool_sh),
            jax.device_put(self.pool.pad, repl),
        )
        # Both programs take (params, pool, table, ...): the block pool
        # shards over heads (decode_cache_specs keys on leaf NAME, and
        # block leaves keep heads at dim 1); the block table and every
        # scalar/lane operand replicate. Chunk prefill writes the
        # sharded pool directly, so there is no separate prefill cache
        # to lay out.
        self.pool.table.sharding = repl
        self.pool.table.invalidate()
        self._make_jits(
            in_shardings=(
                (p_sh, pool_sh) + (repl,) * 6,                 # prefill
                (p_sh, pool_sh) + (repl,) * 7,                 # decode
            ),
            out_shardings=(
                (repl, pool_sh),                               # prefill
                (repl, pool_sh),                               # decode
            ),
        )
        if self.spec is not None:
            if self.spec.source.kind != "self":
                raise NotImplementedError(
                    "tensor-parallel serving with a model draft "
                    "source is not supported yet (the draft model "
                    "has no sharding rules); use a self-draft"
                )
            self.spec.make_jits(p_sh, pool_sh, repl)
        self.mesh = mesh
        self._name_attention()
        return self

    # -- frontend ----------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 32,
        stop_token: Optional[int] = "default",
        timeout_s: Optional[float] = None,
        canary: bool = False,
        tenant: Optional[str] = None,
        prefill_only: bool = False,
    ) -> int:
        """Enqueue a request; returns its id. Raises ``QueueFull`` (with
        ``.retry_after``) when admission control rejects it.

        ``prefill_only=True`` runs this engine as a
        PREFILL TIER member for the request: the prompt prefills into
        paged blocks as usual, but instead of joining the decode batch
        the filled blocks export as a KV handoff — claim it with
        ``handoff()`` and ship it to a decode replica's
        ``submit_handoff``.

        ``canary=True`` tags the request as a blackbox probe: it rides
        the identical admission/prefill/decode path but its finished
        result is excluded from the goodput ledger (the tag must land
        before the queue submit — a serve thread can finish the probe
        before this method returns).

        ``tenant`` names the account billed for this request's tokens,
        queue seconds and KV block-seconds in the engine's
        ``CostLedger`` (untagged requests bill to ``"default"``). The
        tag rides the request object itself, so it survives fleet
        requeue-on-death replays unchanged. The request also roots (or
        adopts) a trace context here: the scheduler re-activates it at
        finish so histogram exemplars latch THIS request's trace id."""
        # what the calling thread spends here is a span on its own track
        t_in = self.clock() if self.tracer.enabled else None
        prompt = [int(t) for t in prompt]  # host-ok: caller-supplied ints
        if not 1 <= len(prompt) <= self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} outside [1, "
                f"{self.max_prompt_len}]"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        now = self.clock()
        req = Request(
            req_id=next(self._req_ids),
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            stop_token=self.stop_token if stop_token == "default" else stop_token,
            timeout_s=timeout_s,
            submitted_at=now,
            deadline=None if timeout_s is None else now + timeout_s,
            tenant=tenant,
            # Adopt the caller's distributed trace context (a router
            # hop) or root a fresh one — either way every span and
            # exemplar this request produces carries one trace id.
            ctx=obs.current_context() or obs.new_context(),
            prefill_only=prefill_only,
        )
        if canary:
            with self._cond:
                self._canary_ids.add(req.req_id)
        try:
            self.queue.submit(req)
        except QueueFull as err:
            if canary:
                with self._cond:
                    self._canary_ids.discard(req.req_id)
            self.metrics.record_reject()
            self.costs.record_reject(tenant)
            obs.default_flight_recorder().note(
                "backpressure_reject", "warn", req_id=req.req_id,
                queue_depth=len(self.queue), retry_after_s=err.retry_after,
            )
            raise
        self.metrics.record_submit()
        self.costs.record_submit(tenant)
        self.tracer.instant(
            "submit", at=now, track=f"req:{req.req_id}",
            req_id=req.req_id, prompt_tokens=len(prompt),
            tenant=tenant or obs.DEFAULT_TENANT,
        )
        if t_in is not None:
            self.tracer.record("submit", t_in, self.clock(), req_id=req.req_id)
        return req.req_id

    def submit_with_retry(self, prompt, **kwargs) -> int:
        """``submit`` with the parameter-client backoff idiom: retry a
        ``QueueFull`` rejection over bounded increasing delays (honoring
        the server's ``retry_after`` when it asks for longer), then give
        up and let the rejection propagate."""
        for delay in (*_RETRY_DELAYS, None):
            try:
                return self.submit(prompt, **kwargs)
            except QueueFull as err:
                if delay is None:
                    raise
                time.sleep(max(delay, err.retry_after))
        raise AssertionError("unreachable")

    # -- disaggregated serving (prefill tier ↔ decode tier) ------------------

    def submit_prefill(self, prompt: Sequence[int], **kwargs) -> int:
        """Prefill-tier submit: identical admission to ``submit``, but
        the request terminates at the prompt — claim its exported KV
        blocks with ``handoff()`` and ship them to a decode replica."""
        return self.submit(prompt, prefill_only=True, **kwargs)

    def handoff(self, req_id: int, timeout_s: Optional[float] = None):
        """Block until ``req_id``'s prefill finishes and claim its
        exported KV handoff (the dict ``serving.handoff.encode_handoff``
        frames). Drives the scheduler inline when no serve thread is
        mid-step, exactly like ``result()``. Returns the handoff dict —
        or the ``GenerationResult`` when the request terminated on this
        engine instead (deadline eviction mid-prefill); callers
        type-check."""
        deadline = None if timeout_s is None else self.clock() + timeout_s
        while True:
            data = self.scheduler.pop_handoff(req_id)
            if data is not None:
                return data
            with self._cond:
                if req_id in self._results:
                    return self._results.pop(req_id)
            if not self._halted and self._step_lock.acquire(blocking=False):
                try:
                    finished = [] if self._halted else self.scheduler.step()
                    if not self._halted:
                        self._on_step_boundary()
                finally:
                    self._step_lock.release()
                self._publish(finished)
                continue
            with self._cond:
                self._cond.wait(timeout=0.01)
            if deadline is not None and self.clock() >= deadline:
                raise TimeoutError(
                    f"handoff {req_id} not ready in {timeout_s}s")

    def submit_handoff(self, frame, canary: bool = False) -> int:
        """Decode-tier admission of a packed ``KVHandoff`` frame: decode
        it (``WireFormatError`` on any corruption — nothing binds until
        the frame validates), import the blocks into this engine's pool,
        and join the decode batch at the prompt frontier. Returns the
        LOCAL request id (``result()`` claims it). Raises ``QueueFull``
        when no slot is free — the router tries another decode replica
        or falls back to a local re-prefill.

        Cost accounting: no ``record_submit`` here — the prefill engine
        already billed the submit, the prompt, and the first token;
        this engine bills decode tokens from token two and block-seconds
        from the import instant (the window the exporter closed)."""
        from elephas_tpu.serving.handoff import decode_handoff

        data = decode_handoff(frame)
        prompt = [int(t) for t in data["prompt"]]  # host-ok: wire metadata
        if not 1 <= len(prompt) <= self.max_prompt_len:
            raise ValueError(
                f"handoff prompt length {len(prompt)} outside [1, "
                f"{self.max_prompt_len}]"
            )
        req = Request(
            req_id=next(self._req_ids),
            prompt=prompt,
            max_new_tokens=int(data["max_new_tokens"]),  # host-ok: wire metadata
            stop_token=data["stop_token"],
            timeout_s=None,
            submitted_at=float(data["submitted_at"]),  # host-ok: wire metadata
            deadline=data["deadline"],
            tenant=data["tenant"],
            ctx=obs.current_context() or obs.new_context(),
        )
        if canary:
            with self._cond:
                self._canary_ids.add(req.req_id)
        export = data["export"]
        try:
            with self._step_lock:
                _, finished = self.scheduler.admit_import(
                    req, int(data["first"]), prompt,  # host-ok: wire metadata
                    export["arrays"], leaf_names=export.get("leaves"),
                )
        except Exception:
            if canary:
                with self._cond:
                    self._canary_ids.discard(req.req_id)
            raise
        self._publish(finished)
        return req.req_id

    def cancel(self, req_id: int) -> bool:
        """QoS preemption: yank ``req_id`` from the queue if it has not
        been admitted yet, publishing a ``"preempted"`` terminal result
        (claimable via ``result()``; excluded from SLO/goodput — the
        router redispatches it). Returns False once the request holds a
        slot — admitted work is never clawed back."""
        with self._step_lock:
            result = self.scheduler.cancel_queued(req_id)
        if result is None:
            return False
        # Keeps submitted == completed + timed_out + rejected on this
        # engine: a preemption is a late reject, never a completion.
        self.metrics.record_reject()
        self._publish([result])
        return True

    def _publish(self, finished: List[GenerationResult]) -> None:
        """Make finished results claimable and account goodput — canary
        probes publish (the driver claims them via ``result()``) but are
        never evaluated into the real-traffic SLO ledger."""
        if not finished:
            return
        # ``step/publish``: what follows a scheduler step on the thread that
        # stepped, where it finished a request: the results made claimable,
        # the goodput ledger's windows read again for each of them
        t_in = self.clock() if self.tracer.enabled else None
        with self._cond:
            # Preempted results are deferrals, not failures: the router
            # redispatches them under fair-share, and only the eventual
            # terminal result may move SLO/goodput accounting.
            real = [r for r in finished
                    if r.req_id not in self._canary_ids
                    and r.status != "preempted"]
            for r in finished:
                self._results[r.req_id] = r
                self._canary_ids.discard(r.req_id)
            self._cond.notify_all()
        for r in real:
            self.slo.record(r)
            # Same canary-blindness as the fleet ledger: per-tenant
            # goodput/burn must agree with the aggregate SLO view.
            self.costs.record_goodput(r)
        if t_in is not None:
            self.tracer.record("step/publish", t_in, self.clock(),
                               parent_id=self.scheduler._step_id,
                               finished=len(finished))

    def halt(self) -> None:
        """Simulate process death for chaos harnesses: after any
        in-flight step completes, the scheduler never advances again —
        not from a serve thread, not from a ``result()`` caller
        stepping inline. Queued and mid-decode requests freeze exactly
        where the "process" died (the fleet router's requeue path is
        what recovers them); already-published results stay claimable,
        like reading a dead process's last output pipe."""
        self._halted = True
        with self._cond:
            self._cond.notify_all()

    @property
    def halted(self) -> bool:
        return self._halted

    def step(self) -> List[GenerationResult]:
        """One scheduler iteration; publishes finished results."""
        if self._halted:
            return []
        with self._step_lock:
            if self._halted:
                return []
            finished = self.scheduler.step()
            self._on_step_boundary()
        self._publish(finished)
        return finished

    def _on_step_boundary(self) -> None:
        """The subscription plane's atomic swap point. Runs under
        ``_step_lock`` after every scheduler step — no program is
        mid-dispatch and a speculative window (one scheduler step is
        one draft+verify window) can never span it — so a weight swap
        here is invisible to in-flight token streams except as "the
        next token came from the new model"."""
        sub = self.subscriber
        if sub is not None:
            sub.on_step(self)

    def install_weights(self, tree, version: Optional[int] = None) -> None:
        """Swap the serving params in place (the rollout plane's write
        seam — callers hold ``_step_lock`` via the subscriber hook, or
        own the engine exclusively). The pulled leaves are re-nested
        into the CURRENT params' container structure: jax tree ops and
        the wire codec rebuild dicts in sorted-key order, and pinning
        the treedef keeps the compiled programs' input structure stable
        — a swap must never retrace. ``model_version`` takes the PS
        version the tree was pulled at."""
        self.params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.params),
            jax.tree_util.tree_leaves(tree),
        )
        if version is not None:
            self.model_version = int(version)  # host-ok: PS version, plain int

    def result(
        self, req_id: int, timeout_s: Optional[float] = None
    ) -> GenerationResult:
        """Block until ``req_id`` finishes. Without a serving thread this
        drives the scheduler inline; alongside ``serve_forever`` it just
        waits."""
        deadline = None if timeout_s is None else self.clock() + timeout_s
        while True:
            with self._cond:
                if req_id in self._results:
                    return self._results.pop(req_id)
            if not self._halted and self._step_lock.acquire(blocking=False):
                # No server thread mid-step: advance the world ourselves.
                try:
                    finished = [] if self._halted else self.scheduler.step()
                    if not self._halted:
                        self._on_step_boundary()
                finally:
                    self._step_lock.release()
                self._publish(finished)
                continue
            with self._cond:
                if req_id in self._results:
                    return self._results.pop(req_id)
                self._cond.wait(timeout=0.01)
            if deadline is not None and self.clock() >= deadline:
                raise TimeoutError(f"request {req_id} not done in {timeout_s}s")

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        """Step until no queued or active work remains."""
        for _ in range(max_steps):
            if not self.scheduler.has_work:
                return
            self.step()
        raise RuntimeError(f"not drained after {max_steps} steps")

    def serve_forever(
        self,
        stop_event: Optional[threading.Event] = None,
        idle_sleep_s: float = 0.001,
    ) -> None:
        """Serve until ``stop_event`` is set (forever if None). Run in a
        thread; ``submit``/``result`` are safe from other threads."""
        while stop_event is None or not stop_event.is_set():
            if self.scheduler.has_work:
                self.step()
            else:
                time.sleep(idle_sleep_s)

    # -- observability -----------------------------------------------------

    def attach_canary(self, driver) -> None:
        """Register the blackbox probe driver serving ``/canary``."""
        self.canary = driver

    def _canary_doc(self) -> dict:
        if self.canary is not None:
            return self.canary.snapshot()
        return {"surface": None, "probes": 0, "failures": 0,
                "failure_ratio": None, "last": None}

    def stats(self) -> dict:
        out = {
            **self.metrics.summary(),
            "model_version": self.model_version,
            "prefill_traces": self._prefill_traces,
            "decode_traces": self._decode_traces,
            "decode_attention": self.decode_attention,
            "decode_kernel_blocks": self.decode_kernel_blocks,
            "prefill_attention": self.prefill_attention,
            "prefill_query_tile": self.prefill_query_tile,
            "pool_admitted_total": self.pool.admitted_total,
            "pool_active": self.pool.active_count,
            "pool_free": self.pool.free_count,
            "kv_blocks_free": self.pool.free_blocks,
            "kv_blocks_total": self.pool.num_blocks,
            "kv_bytes_per_token": self.pool.kv_bytes_per_token,
            **self.pool.prefix_stats(),
            **self.pool.state_signals(),
            "state_resets": self.pool.state_resets,
        }
        if self.spec is not None:
            out.update(self.spec.stats())
        if len(self.costs.tenants()) > 0:
            out["tenancy"] = self.costs.snapshot()
        return out

    def _tenants_doc(self) -> dict:
        """``/tenants``: evaluate the per-tenant alert rules (burn,
        noisy-neighbor KV share) against the ledger's synthetic metric
        view, then snapshot — rows, totals, kv_share, alerts."""
        self.costs.evaluate_alerts(self.clock())
        return self.costs.snapshot()

    def mount_ops(self, port: int = 0, host: Optional[str] = None,
                  store_dir: Optional[str] = None):
        """Mount a live introspection endpoint (``obs.opsd``) for this
        engine: ``/metrics``, ``/healthz`` (+ queue/pool summary),
        ``/trace``, ``/vars``, ``/flight``, ``/alerts`` (stock SLO rule
        pack — its serving ITL rule reads the registry mirror
        ``ServingMetrics`` feeds), plus the saturation/goodput plane:
        ``/load`` (EWMA load score), ``/slo`` (windowed goodput +
        burn), ``/canary`` (blackbox probe SLIs when a driver is
        attached), ``/tenants`` (per-tenant cost ledger + burn/KV-share
        alerts), and ``/profile``, whose ``?action=stop`` answers the
        capture reduced by model part (``by_part``) against this engine's
        two ``program_report``s. Loopback-bound by default; port 0 picks a free one
        (read ``engine.ops.port``). Idempotent.

        ``store_dir`` additionally mounts the durable telemetry journal
        (``obs.store``): flight notes, alert transitions, sampler ticks,
        and completed spans persist there for cross-process post-mortem
        reconstruction (``/incidents`` serves its meta).
        """
        if self.ops is not None:
            return self.ops
        from elephas_tpu import obs
        from elephas_tpu.obs.devprof import DeviceProfiler, record_device_memory
        from elephas_tpu.obs.opsd import OpsServer

        if getattr(self, "_alert_engine", None) is None:
            self._alert_engine = obs.AlertEngine()
        self._ops_history = obs.HistorySampler(
            extra_fn=record_device_memory).start()
        self.store = None
        if store_dir is not None:
            self.store = obs.TelemetryStore(
                store_dir, role="serving",
                flight=obs.default_flight_recorder())
            obs.default_flight_recorder().attach_store(self.store)
            self._alert_engine.attach_store(self.store)
            self._ops_history.attach_store(self.store)
            if getattr(self.tracer, "enabled", False):
                self.tracer.attach_store(self.store)
        self.ops = OpsServer(
            port=port, host=host, tracer=self.tracer,
            role="serving",
            alerts_fn=self._alert_engine.scrape,
            history=self._ops_history,
            vars_fn=lambda: {
                "role": "serving",
                "max_slots": self.pool.max_slots,
                "max_prompt_len": self.max_prompt_len,
            },
            health_fn=lambda: {
                "queue_depth": len(self.queue),
                "pool_active": self.pool.active_count,
                "pool_free": self.pool.free_count,
            },
            load_fn=self.load.snapshot,
            slo_fn=self.slo.snapshot,
            canary_fn=self._canary_doc,
            tenants_fn=self._tenants_doc,
            incidents_fn=(self.store.doc if self.store is not None
                          else None),
            # ``/profile?action=stop`` answers the capture by model part: the
            # two reports are built then, on first use, never before
            profiler=DeviceProfiler(reports=lambda: [
                self.program_report("prefill"), self.program_report("decode")]),
        ).start()
        return self.ops

    def unmount_ops(self, reason: str = "close") -> None:
        if self.ops is not None:
            self.ops.stop()
            self.ops = None
        sampler = getattr(self, "_ops_history", None)
        if sampler is not None:
            sampler.stop()
            self._ops_history = None
        store = getattr(self, "store", None)
        if store is not None:
            from elephas_tpu import obs
            obs.default_flight_recorder().detach_store(store)
            engine = getattr(self, "_alert_engine", None)
            if engine is not None:
                engine.detach_store(store)
            if hasattr(self.tracer, "detach_store"):
                self.tracer.detach_store(store)
            store.close(reason=reason)
            self.store = None


def shard_serving(engine: InferenceEngine, mesh, rules=None) -> InferenceEngine:
    """Module-level alias for ``InferenceEngine.shard_serving`` (the
    ROADMAP's tensor-parallel-decode entry point)."""
    return engine.shard_serving(mesh, rules=rules)
