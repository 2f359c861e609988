"""Disaggregated serving fleet: a ``Router`` tier over N
``InferenceServer`` replicas.

Everything a fleet needs shipped piecemeal in earlier layers — health
states + drain + hedged clients + request-id dedup (serving
resilience), Prometheus gauges incl. ``kvpool_occupancy_ratio`` and
wire-propagated trace contexts (observability), and a block-paged KV
pool whose block tables make in-flight KV state a well-defined,
migratable unit (serving/kvpool). This package composes them:

- :class:`~.registry.ReplicaRegistry` — replica table with health-probe
  loops, telemetry scraping, eviction after consecutive probe failures
  and automatic readmission;
- :class:`~.router.Router` — wire-compatible front-end with
  least-loaded telemetry-driven dispatch, cross-replica failover and
  hedging (request-id dedup: a failover never double-executes),
  drain-aware rolling weight reloads, and DISAGGREGATED
  prefill/decode pools: compute-bound prefill replicas serialize
  finished KV blocks (int8 scales included) out of their pool and the
  router streams them into bandwidth-bound decode replicas, so each
  pool scales on its own roofline.

Quick start::

    from paddle_tpu import serving
    from paddle_tpu.serving import fleet

    reps = [serving.InferenceServer(generator=mkgen(),
                                    kv_pool_name=f"rep{i}").start()
            for i in range(3)]
    router = fleet.Router([r.endpoint for r in reps]).start()
    with serving.Client(router.endpoint) as c:      # same protocol
        out = c.generate(prompt_ids, max_new_tokens=64)

Disaggregated split: register replicas with roles instead::

    router = fleet.Router([(pre.endpoint, "prefill"),
                           (dec.endpoint, "decode")]).start()

Autoscaling (:class:`~.autoscaler.Autoscaler`): hand the router a
replica factory and the pool scales itself between
``FLAGS_fleet_min_replicas`` and ``FLAGS_fleet_max_replicas`` on the
probed fleet telemetry (queue ratios, kvpool occupancy, SLO breach
state), with full-window hysteresis + cooldown so it never flaps and a
drain-aware scale-down path::

    scaler = fleet.Autoscaler(router, factory=spawn_replica).start()
"""
from .autoscaler import Autoscaler  # noqa: F401
from .registry import Replica, ReplicaRegistry  # noqa: F401
from .router import FLEET_EVENT_KINDS, Router  # noqa: F401
