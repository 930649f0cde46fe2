"""Online inference over the KV-cache decode path (SURVEY.md §5.7 —
the reference has no generative models, let alone a serving story).

The training half of the repo has its coordination service (the
parameter server + engine drivers); this package is the inference
counterpart — the subsystem that turns ``TransformerLM``'s compiled
decode step into an engine that serves request traffic:

- ``PagedKVPool``      — the fixed-shape pool of per-layer KV caches
                         (admission/eviction never reshapes a compiled
                         program): reference-counted fixed-size KV
                         blocks behind a ``BlockTable``, a
                         ``PrefixCache`` that admits resident prompt
                         prefixes by refcount instead of re-prefilling,
                         LRU prefix eviction under pressure,
                         copy-on-write at shared boundaries
                         (``serving.kv_pool``),
- ``ContinuousBatchingScheduler`` — bounded request queue, prefill/decode
                         interleaving, deadline eviction, backpressure
                         (``serving.scheduler``),
- ``InferenceEngine``  — the frontend: ``submit()`` / ``result()`` /
                         ``serve_forever()``; ``shard_serving()`` makes
                         the two compiled programs tensor-parallel over
                         a mesh's ``'model'`` axis (``serving.engine``),
- ``ServingMetrics``   — TTFT / inter-token latency / queue depth /
                         tokens-per-sec / dispatch→fetch device overlap
                         through ``metrics.JsonlSink``
                         (``serving.metrics``),
- ``SpeculativeDecoder`` — draft-and-verify decode over the paged pool:
                         a ``DraftSource`` (shallow-stack self-draft or
                         a PS-delivered small draft model) proposes
                         gamma tokens per slot, ONE batched target
                         forward verifies them, emitted streams stay
                         byte-identical to plain decode
                         (``serving.spec``),
- ``host_sync``        — the ONE sanctioned device→host sync point;
                         ``scripts/lint_blocking.py`` statically bans
                         blocking reads anywhere else in this package,
- ``fleet``            — the replicated layer above the engine: a
                         ``ReplicaSet`` of N engine replicas with
                         spawn/drain/kill/restart lifecycles, a
                         signal-driven session-affinity ``Router`` that
                         actuates on burn alerts and canary failures,
                         and a ``FleetAutoscaler`` scaling replica
                         count from multi-window burn
                         (``serving.fleet``).

The decode hot path is PIPELINED (one-step lookahead: dispatch N+1
before reading N's tokens) and DONATION-CLEAN (the pool cache is donated
to every program that rewrites it; ``DonatedBufferError`` guards stale
reads). Both are engine-internal: token streams are identical to the
unpipelined path (``pipeline=False``), and both are held, one row at a
time, to ``models.transformer.generate()``.
"""

from elephas_tpu.serving import host_sync  # noqa: F401
from elephas_tpu.serving.kv_pool import (  # noqa: F401
    BlockTable,
    DonatedBufferError,
    PagedKVPool,
    PrefixCache,
)
from elephas_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatchingScheduler,
    GenerationResult,
    QueueFull,
    Request,
    RequestQueue,
)
from elephas_tpu.serving.engine import (  # noqa: F401
    InferenceEngine,
    shard_serving,
)
from elephas_tpu.serving.metrics import ServingMetrics  # noqa: F401
from elephas_tpu.serving.spec import (  # noqa: F401
    DraftModelSource,
    DraftSource,
    SelfDraftSource,
    SpeculativeDecoder,
)
from elephas_tpu.serving.fleet import (  # noqa: F401
    FleetAutoscaler,
    FleetUnavailable,
    Replica,
    ReplicaDead,
    ReplicaSet,
    Router,
)
