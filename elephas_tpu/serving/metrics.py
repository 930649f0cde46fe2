"""Serving observability: TTFT, inter-token latency, queue depth,
tokens/sec — emitted through the existing ``metrics.logging.JsonlSink``
(one JSON object per line, the same artifact format every committed
benchmark in this repo uses) and aggregated in-memory for tests and the
engine's ``stats()``.

Two record streams share the sink, tagged by ``event``:

- ``event="request"`` — one line per FINISHED request: status, prompt /
  generated token counts, ``ttft_s`` (submit → first token) and its two
  parts ``queue_s`` (submit → pop) and ``prefill_s`` (pop → first
  token), ``itl_s_avg`` (mean gap between consecutive tokens), decode
  tokens/sec for that request.
- ``event="step"``   — one line per scheduler iteration (sampled every
  ``step_log_every``): queue depth, active slots, tokens emitted this
  step, step wall seconds and the host seconds of its phases
  (``harvest_wait_s`` — the one place the host blocks on the device, so
  ``step_seconds - harvest_wait_s`` is the host's own time —
  ``admit_s``, ``prefill_s``, ``dispatch_s``), the prompt work it
  dispatched (``prefill_tokens``, ``prefill_chunks``), the cache columns
  each decoding lane held before this step's decode (``lane_lengths``),
  the pool's ``kv_blocks_in_use`` / ``kv_blocks_total``,
  ``decode_attention`` and ``prefill_attention`` (the attention body the
  decode program and the prefill program were traced with:
  ``paged_pallas`` or ``paged_xla``), ``decode_kernel_blocks`` (the blocks
  of a lane that a grid step of the decode kernel folds; ``None`` under
  ``paged_xla``), ``prefill_query_tile`` (the queries a tile of the latent
  chunk kernel; ``None`` where the chunk program's body is another), and
  ``dispatch_to_fetch_s`` — the
  device-overlap gauge: wall seconds between a decode step's dispatch
  and the harvest of its tokens. On the pipelined path all host
  bookkeeping for the previous step happens inside this window, so the
  gauge reads ≈ one full step of hidden host work; on the unpipelined
  path it collapses to the bare device-compute+transfer time.

Metrics must degrade, not kill the serve loop — the sink already
stringifies anything JSON can't carry; here a missing sink simply means
in-memory aggregation only.
"""

from __future__ import annotations

import time
from typing import Optional

from elephas_tpu.obs import Histogram

# The three latency families summary() reports percentiles for. Raw
# sample lists are kept alongside (tests and notebooks read them); the
# histograms are what the percentile estimates come from, so the same
# numbers keep working if the lists are ever dropped for long runs.
_LATENCY_KEYS = ("ttft_s", "itl_s", "dispatch_to_fetch_s")


class ServingMetrics:
    """Aggregator + JSONL emitter for the serving engine."""

    def __init__(self, sink=None, step_log_every: int = 1,
                 clock=time.monotonic):
        self.sink = sink
        self.step_log_every = max(1, int(step_log_every))  # host-ok: arg
        self.clock = clock
        # Set by the engine (and again by shard_serving); kept by reset().
        self.decode_attention: Optional[str] = None
        self.prefill_attention: Optional[str] = None
        self.decode_kernel_blocks: Optional[int] = None
        self.kv_block_size: Optional[int] = None
        # the latent chunk kernel's geometry: queries a tile, columns a
        # grid step, queries a chunk (None where the body is another)
        self.prefill_query_tile: Optional[int] = None
        self.prefill_step_columns: Optional[int] = None
        self.prefill_chunk: Optional[int] = None
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_timed_out = 0
        self.requests_rejected = 0
        self.tokens_out = 0
        # Step-grained twins of the finish-grained ``tokens_out``: work
        # counted as the scheduler commits it, whether or not its request
        # has finished.
        self.tokens_emitted_total = 0
        self.tokens_prefilled_total = 0
        # Blocks the decode kernel's grid steps covered, step by step:
        # those that held a live column of their lane, and those that
        # only filled a lane's last grid step up to ``decode_kernel_blocks``
        # (scored dead, not moved). Zero under ``paged_xla``.
        self.decode_live_blocks = 0
        self.decode_dead_blocks = 0
        # (query tile, column step) pairs the latent chunk kernel's grid
        # steps walked, chunk by chunk from the host's ``start`` and
        # ``valid``, and what one tile of the whole chunk a step up to the
        # chunk's last column amounts to in the same units
        # (``ops.attention.latent_chunk_tiles_visited``).
        self.prefill_tiles_visited = 0
        self.prefill_tiles_dense = 0
        self.steps = 0
        self.max_concurrent = 0
        self.ttft_s: list = []
        self.itl_s: list = []
        self.dispatch_to_fetch_s: list = []
        self.histograms = {k: Histogram(k) for k in _LATENCY_KEYS}
        self._last_overlap: Optional[float] = None
        self._t0: Optional[float] = None
        # Speculative decode aggregates (zero unless the engine runs
        # with speculative=True): lane-windows harvested, draft tokens
        # proposed/accepted, tokens emitted by speculative harvests.
        self.spec_windows = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        # Lazy process-registry mirror of the ITL distribution: the SLO
        # alert pack's serving rule reads ``serving_itl_seconds_p99``
        # from registry snapshots, which the private per-engine
        # histograms above never reach. Bound on first finish; False
        # latches "registry unavailable" so a broken import can't tax
        # every request.
        self._registry_itl = None

    def reset(self) -> None:
        """Zero every in-memory aggregate (the sink, if any, keeps its
        already-written lines). Benchmarks warm the compile caches with
        a throwaway request, then reset so the timed run's numbers
        measure serving, not XLA compilation."""
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_timed_out = 0
        self.requests_rejected = 0
        self.tokens_out = 0
        self.tokens_emitted_total = 0
        self.tokens_prefilled_total = 0
        self.decode_live_blocks = 0
        self.decode_dead_blocks = 0
        self.prefill_tiles_visited = 0
        self.prefill_tiles_dense = 0
        self.steps = 0
        self.max_concurrent = 0
        self.ttft_s = []
        self.itl_s = []
        self.dispatch_to_fetch_s = []
        self.histograms = {k: Histogram(k) for k in _LATENCY_KEYS}
        self._last_overlap = None
        self._t0 = None
        self.spec_windows = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0

    # -- request lifecycle -------------------------------------------------

    def record_submit(self) -> None:
        if self._t0 is None:
            self._t0 = self.clock()
        self.requests_submitted += 1

    def record_reject(self) -> None:
        self.requests_rejected += 1

    def record_finish(self, result, queue_depth: int, active: int) -> None:
        if result.status == "timeout":
            self.requests_timed_out += 1
        else:
            self.requests_completed += 1
        self.tokens_out += len(result.tokens)
        if result.ttft_s is not None:
            self.ttft_s.append(result.ttft_s)
            self.histograms["ttft_s"].observe(result.ttft_s)
        if result.itl_s_avg is not None:
            self.itl_s.append(result.itl_s_avg)
            self.histograms["itl_s"].observe(result.itl_s_avg)
            hist = self._registry_itl
            if hist is None:
                try:
                    from elephas_tpu import obs
                    # exemplars=True: each observe latches the request's
                    # active trace id on its bucket, so a p99 spike in
                    # the exposition joins to the exact span tree in
                    # trace_report (the record runs inside the request
                    # span the scheduler opened).
                    hist = obs.default_registry().histogram(
                        "serving_itl_seconds",
                        help="per-request mean inter-token latency",
                        exemplars=True,
                    )
                except Exception:
                    hist = False
                self._registry_itl = hist
            if hist:
                hist.observe(result.itl_s_avg)
        if self.sink is not None:
            self.sink.log(
                self.steps,
                event="request",
                req_id=result.req_id,
                status=result.status,
                prompt_tokens=result.prompt_tokens,
                new_tokens=len(result.tokens),
                ttft_s=result.ttft_s,
                queue_s=result.queue_s,
                prefill_s=result.prefill_s,
                itl_s_avg=result.itl_s_avg,
                tokens_per_sec=result.tokens_per_sec,
                tokens_per_step=result.tokens_per_step,
                queue_depth=queue_depth,
                active_slots=active,
            )

    # -- speculative decode ------------------------------------------------

    def record_spec(self, *, windows: int, drafted: int, accepted: int,
                    emitted: int) -> None:
        """One speculative harvest: ``windows`` lane-windows read back,
        ``drafted`` draft tokens proposed (gamma per lane), ``accepted``
        of them matching the target, ``emitted`` tokens appended to
        streams (accepted + bonus, minus stop/budget truncation)."""
        self.spec_windows += windows
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_emitted += emitted

    @property
    def spec_accept_rate(self) -> Optional[float]:
        if self.spec_drafted == 0:
            return None
        return self.spec_accepted / self.spec_drafted

    @property
    def spec_tokens_per_step(self) -> Optional[float]:
        if self.spec_windows == 0:
            return None
        return self.spec_emitted / self.spec_windows

    # -- scheduler cadence -------------------------------------------------

    def record_overlap(self, seconds: float) -> None:
        """Dispatch→fetch wall time for one decode step (the window the
        pipelined scheduler hides host bookkeeping in)."""
        self.dispatch_to_fetch_s.append(seconds)
        self.histograms["dispatch_to_fetch_s"].observe(seconds)
        self._last_overlap = seconds

    def record_step(self, queue_depth: int, active: int, tokens: int,
                    step_seconds: float, *, harvest_wait_s: float = 0.0,
                    admit_s: float = 0.0, prefill_s: float = 0.0,
                    dispatch_s: float = 0.0, prefill_tokens: int = 0,
                    prefill_chunks: int = 0, prefill_spans=(),
                    lane_lengths=(),
                    kv_blocks_in_use: Optional[int] = None,
                    kv_blocks_total: Optional[int] = None,
                    state_slots_in_use: int = 0, state_slots_total: int = 0,
                    state_bytes: int = 0, counters=None, **pool_signals) -> None:
        self.steps += 1
        self.tokens_emitted_total += tokens
        self.tokens_prefilled_total += prefill_tokens
        if self.decode_kernel_blocks:
            # a lane that held ``n`` columns writes column ``n``
            live = [n // self.kv_block_size + 1 for n in lane_lengths]
            self.decode_live_blocks += sum(live)
            self.decode_dead_blocks += sum(
                -n % self.decode_kernel_blocks for n in live)
        if self.prefill_query_tile:
            from elephas_tpu.ops.attention import latent_chunk_tiles_visited

            for start, valid in prefill_spans:  # each chunk's, as launched
                visited, dense = latent_chunk_tiles_visited(
                    start, valid, self.prefill_chunk, self.prefill_query_tile,
                    self.prefill_step_columns)
                self.prefill_tiles_visited += visited
                self.prefill_tiles_dense += dense
        self.max_concurrent = max(self.max_concurrent, active)
        overlap, self._last_overlap = self._last_overlap, None
        if self.sink is not None and self.steps % self.step_log_every == 0:
            self.sink.log(
                self.steps,
                event="step",
                queue_depth=queue_depth,
                active_slots=active,
                step_tokens=tokens,
                step_seconds=step_seconds,
                dispatch_to_fetch_s=overlap,
                tokens_per_sec=tokens / max(step_seconds, 1e-9),
                harvest_wait_s=harvest_wait_s,
                admit_s=admit_s,
                prefill_s=prefill_s,
                dispatch_s=dispatch_s,
                prefill_tokens=prefill_tokens,
                prefill_chunks=prefill_chunks,
                lane_lengths=lane_lengths,
                kv_blocks_in_use=kv_blocks_in_use,
                kv_blocks_total=kv_blocks_total,
                # per-slot state rows (a recurrence's, a convolution's)
                # held beside the K/V blocks: none for a model without
                state_slots_in_use=state_slots_in_use,
                state_slots_total=state_slots_total,
                state_bytes=state_bytes,
                # what else the pool says of itself (``state_signals``): the
                # window layers' resident columns against their bound
                **pool_signals,
                decode_attention=self.decode_attention,
                prefill_attention=self.prefill_attention,
                decode_kernel_blocks=self.decode_kernel_blocks,
                prefill_query_tile=self.prefill_query_tile,
                # what the model's layers counted in the decode step that
                # this step harvested (a routed layer's assignments and
                # loads): nothing for a model that counts nothing
                **(counters or {}),
            )

    # -- aggregates --------------------------------------------------------

    def summary(self) -> dict:
        """The aggregates as one dict. ``tokens_out`` and with it
        ``tokens_per_sec`` are finish-grained (a request's tokens count
        when it finishes); ``tokens_emitted_total`` and
        ``tokens_prefilled_total`` count per scheduler step."""
        elapsed = None if self._t0 is None else self.clock() - self._t0
        mean = lambda xs: (sum(xs) / len(xs)) if xs else None  # noqa: E731
        out = {
            "submitted": self.requests_submitted,
            "completed": self.requests_completed,
            "timed_out": self.requests_timed_out,
            "rejected": self.requests_rejected,
            "tokens_out": self.tokens_out,
            "tokens_emitted_total": self.tokens_emitted_total,
            "tokens_prefilled_total": self.tokens_prefilled_total,
            "decode_live_blocks": self.decode_live_blocks,
            "decode_dead_blocks": self.decode_dead_blocks,
            "prefill_tiles_visited": self.prefill_tiles_visited,
            "prefill_tiles_dense": self.prefill_tiles_dense,
            "steps": self.steps,
            "max_concurrent": self.max_concurrent,
            "ttft_s_avg": mean(self.ttft_s),
            "itl_s_avg": mean(self.itl_s),
            "dispatch_to_fetch_s_avg": mean(self.dispatch_to_fetch_s),
            "elapsed_s": elapsed,
            "tokens_per_sec": (
                self.tokens_out / elapsed if elapsed else None
            ),
        }
        if self.spec_windows:
            out["spec_windows"] = self.spec_windows
            out["spec_accept_rate"] = self.spec_accept_rate
            out["spec_tokens_per_step"] = self.spec_tokens_per_step
        # Tail latencies (bucketed estimates, obs.Histogram): averages
        # hide exactly the stall spikes serving SLOs are written against.
        for key, hist in self.histograms.items():
            for pkey, v in hist.percentiles().items():
                out[f"{key}_{pkey}"] = v
        return out
