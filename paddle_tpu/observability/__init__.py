"""Unified telemetry substrate (the observability PR's tentpole).

Four pieces, one package:

- :mod:`metrics` — ``MetricsRegistry`` of labeled counters / gauges /
  histograms with Prometheus text exposition; existing stat sinks
  (``ServingStats``, ``Executor.cache_stats()``, ``passes.stats()``,
  breaker states, the train supervisor) report into it via native
  instruments or scrape-time collectors without changing their Python
  payloads. Scraped by the ``"metrics"`` serving wire op and
  ``tools/export_metrics.py``.
- :mod:`tracing` — Dapper-style trace/span contexts minted at the
  client, wire-propagated next to ``rid``, threaded through queue /
  pad / compile / execute and the decode slot bank, recorded into the
  profiler's unified span table so ``tools/timeline.py`` renders one
  Chrome/Perfetto trace. ``FLAGS_trace_sample_rate`` keeps the
  off-path cost near zero.
- :mod:`utilization` — live MFU / HBM-bandwidth gauges: each cached
  AOT executable's ``cost_analysis()`` flops/bytes attached to its
  runtime step timings (``bench.py`` imports the same peak tables, so
  live gauges and the offline roofline agree by construction).
- :mod:`recorder` — the flight recorder: a bounded ring of recent
  structured events (admissions, evictions, restarts, chaos firings,
  non-finite hits, weight reloads, preemptions) dumped to JSON on a
  typed server-boundary error or the ``"debug_dump"`` wire op.
- :mod:`profiling` — performance attribution: the per-op cost profiler
  (estimated flops/bytes roofline ranking + ``FLAGS_profile_ops``
  measured op-granular replays with Perfetto spans) and the HBM
  live-set memory profiler (peak residency, op index at peak, top-k
  tensors live at peak).
- :mod:`slo` — the rule-driven SLO monitor: declarative rules over
  metric streams become ``slo_breach``/``slo_recovered`` flight events,
  ``slo_*`` metrics, and dispatch-penalty signals the fleet Router
  consumes.
- :mod:`goodput` — the training goodput ledger: every second of a
  supervised training run attributed to compute / compile / data_stall
  / h2d / checkpoint / recovery / preempt / other (MegaScale-style),
  exported as ``train_time_seconds_total{category}`` +
  ``train_goodput_ratio`` + a Perfetto counter track.
- :mod:`inputstall` — the input-pipeline stall profiler: queue
  occupancy gauges, producer/consumer wait histograms, and
  ``data_stall`` flight events on the dataio queues.
- :mod:`sharding` — the sharding audit: per-tensor ACTUAL shardings of
  a compiled mesh executable diffed against declared
  ``dist_attr``/PartitionSpecs, typed findings
  (replicated-large-param, unsharded-batch, sharding-mismatch,
  reshard-inserted) as flight events + metrics.
- :mod:`comms` — the collective-traffic ledger: every
  all-reduce/all-gather/reduce-scatter/all-to-all/collective-permute
  in a compiled executable's HLO attributed to a mesh axis via its
  replica_groups, bytes+counts per (collective, axis), rooflined
  against the ICI/DCN peak tables into ``device_comm_bound_ratio``.
"""
from .comms import CommLedger, parse_collectives  # noqa: F401
from .goodput import CATEGORIES, GoodputLedger  # noqa: F401
from .inputstall import StallTracker  # noqa: F401
from .metrics import (  # noqa: F401
    DEFAULT_BOUNDS_MS, Family, MetricsRegistry, UNIT_SUFFIXES,
    default_registry, render_metrics,
)
from .profiling import (  # noqa: F401
    format_table, last_op_profile, measure_op_times, memory_profile,
    profile_program,
)
from .recorder import FlightRecorder, flight_recorder  # noqa: F401
from .sharding import (  # noqa: F401
    ShardingAuditReport, ShardingFinding, audit_executable,
    lower_program, maybe_observe, observe_executable,
    recent_observations,
)
from .slo import SloMonitor, SloRule, default_server_rules  # noqa: F401
from .tracing import (  # noqa: F401
    SpanContext, ambient, current, current_loop, from_wire, loop_root,
    loop_span, loop_spans, maybe_trace, new_trace, record_child,
    record_span, span, to_wire,
)
from .utilization import (  # noqa: F401
    dcn_peak, executable_cost, hbm_peak, ici_peak, observe_execution,
    peak_flops, set_peaks,
)
