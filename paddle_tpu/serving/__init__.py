"""TPU serving runtime: dynamic micro-batching, compiled-executable
cache, admission control.

The reference ships a standalone inference engine (AnalysisPredictor +
zero-copy tensors) for single callers; this package is the multi-client
layer above it — the TPU-native analog of a serving stack in the
clipper/ORCA adaptive-batching tradition:

- ``RequestQueue`` + ``MicroBatcher`` coalesce single requests into
  padded power-of-two batches per feed-shape signature
  (``FLAGS_serving_max_batch_size`` / ``FLAGS_serving_batch_timeout_ms``)
- ``ExecutableCache`` holds AOT-compiled XLA executables — LRU, byte- and
  entry-capped, hit/miss/evict counters, warmup from a recorded
  signature file
- admission control: queue-depth backpressure
  (``ServerOverloadedError``), per-request deadlines
  (``DeadlineExceededError``), load shedding via
  ``resilience.CircuitBreaker``
- ``InferenceServer`` speaks the ``distributed/wire.py`` length-prefixed
  framing (HMAC-optional, same retry semantics as the PS transport);
  ``Client`` is the matching caller; both also work purely in-process
- ``server.stats()`` snapshots per-stage latency histograms
  (queue/pad/compile/execute), throughput and batch occupancy; the same
  spans land in ``paddle_tpu.profiler`` event tables while profiling

- generation: pass a ``models.generation.GPTGenerator`` as
  ``InferenceServer(generator=...)`` and the server also speaks
  ``op: "generate"`` — requests join a fixed bank of decode slots
  (``FLAGS_decode_slots``) stepped one token at a time by a single
  compiled paged decode executable (ORCA-style continuous
  batching: per-row position counters, token-level deadlines, slot
  reuse the moment a row finishes); ``stats()`` adds prefill/decode/
  sample histograms, ``tokens_per_s`` and ``decode_occupancy``

- paged KV cache: the decode bank's keys and values live in one
  shared block-paged ``kvpool.KVBlockPool`` (vLLM/PagedAttention),
  the only KV store behind serving — per-slot block tables,
  allocation on append, frees on EOS/deadline/cancel, typed
  ``KVPoolExhaustedError`` backpressure, optional bf16/int8 cache
  (``FLAGS_kv_cache_dtype``) read by the fused
  ``kernels.paged_attention`` decode kernel; ``stats()`` adds
  ``kvpool_*`` occupancy/fragmentation and the registry exports
  ``kvpool_*`` gauges

- telemetry: the ``metrics`` wire op (``Client.metrics()``) returns the
  Prometheus text exposition of the process metrics registry
  (``paddle_tpu.observability``); ``debug_dump`` returns the flight
  recorder's recent structured events; ``infer``/``generate`` frames
  may carry a ``trace`` context (sampled client-side at
  ``FLAGS_trace_sample_rate``) that the server threads through every
  stage into the profiler's unified span table for
  ``tools/timeline.py``

- fleet (``serving.fleet``): a ``Router`` tier fronts N replicas over
  the same wire protocol — telemetry-driven least-loaded dispatch
  (probed ``health`` snapshots: queue depths + kvpool occupancy),
  replica eviction/readmission, cross-replica failover + hedging with
  request-id dedup, drain-aware rolling weight reloads, and a
  DISAGGREGATED prefill/decode split that streams finished KV blocks
  from compute-bound prefill replicas into bandwidth-bound decode
  replicas' pools (``op: "prefill"`` + ``generate``'s ``kv=`` import)

- overload control: every request carries a priority class
  (``interactive``/``batch``/``best_effort``) — the queue serves
  higher classes first and sheds the lowest first under backpressure,
  deadline-expired queue entries are evicted typed, ``deadline_ms``
  propagates as the REMAINING budget across client -> router ->
  replica hops, one process-global ``resilience.RetryBudget`` bounds
  every retry/hedge/failover (``FLAGS_retry_budget_ratio``), a
  breached-SLO server walks the brownout ladder
  (``serving.brownout``, best_effort then batch degrade before
  interactive), and ``fleet.Autoscaler`` scales the replica pool on
  the probed telemetry with hysteresis + cooldown

- resilience: the server runs a lifecycle state machine (warming ->
  serving -> draining -> stopped, degraded while the loop supervisor's
  breaker is open), a ``health`` wire op, ``drain()`` graceful shutdown,
  ``reload_weights()`` hot checkpoint swap (manifest-verified; in-flight
  generations finish on the old weights), supervised batcher loops
  (heartbeats, watchdogged executes, capped-backoff restarts), and a
  hedging/reconnecting ``Client`` with server-side request-id dedup.
  ``resilience.chaos()`` arms seeded fault points through every serving
  stage for deterministic failure testing.

Quick start::

    import paddle_tpu.serving as serving
    server = serving.InferenceServer("/path/to/saved_model").start()
    with serving.Client(server.endpoint) as c:
        probs, = c.infer({"x": batch}, deadline_ms=50.0)
    print(server.stats()["mean_batch_size"])
    server.stop()

Generation quick start::

    gen = paddle_tpu.models.GPTGenerator(cfg, scope, max_len=512)
    server = serving.InferenceServer(generator=gen).start()
    with serving.Client(server.endpoint) as c:
        new_tokens = c.generate(prompt_ids, max_new_tokens=64,
                                temperature=0.8, top_k=40)
    server.stop()
"""
from .batching import (  # noqa: F401
    PRIORITIES, BadRequestError, DeadlineExceededError, DecodeBatcher,
    GenerationRequest, InternalServerError, MicroBatcher, Request,
    RequestCancelledError, RequestQueue, ServerOverloadedError,
    ServerShutdownError, ServingError, SwapHandle, next_bucket,
    priority_rank,
)
from .brownout import BrownoutController  # noqa: F401
from .cache import ExecutableCache, LRUCache, feed_signature  # noqa: F401
from .engine import (  # noqa: F401
    SIGNATURE_FILE, GenerationEngine, ServingEngine,
    load_param_snapshot,
)
from .kvpool import KVBlockPool, KVPoolExhaustedError  # noqa: F401
from .metrics import LatencyHistogram, ServingStats  # noqa: F401
from .server import Client, InferenceServer, ServingConfig  # noqa: F401
from .supervise import LoopSupervisor  # noqa: F401
from . import fleet  # noqa: F401  — Router/ReplicaRegistry (serving.fleet)
