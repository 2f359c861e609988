"""Runtime flag facade.

Capability parity with the reference's gflags spine
(/root/reference/paddle/fluid/platform/flags.cc — ~26 DEFINE_* runtime
knobs; Python access via pybind/global_value_getter_setter.cc,
fluid.core.globals(), and FLAGS_* env passthrough whitelisted in
python/paddle/fluid/__init__.py).

One typed registry replaces gflags + pybind getters + env whitelist:
flags are declared here with defaults, `FLAGS_<name>` environment
variables override at import, and `set_flags`/`get_flags` mirror the
fluid API. Flags with a real XLA/JAX effect apply immediately
(check_nan_inf -> jax_debug_nans, deterministic -> matching XLA flag);
CUDA-allocator knobs are accepted no-ops so reference launch scripts run
unchanged.
"""
import os

_DEFS = {
    # name: (default, type, applies)
    # checked host-side by Executor.run so the error names the offending
    # variable (reference nan_inf_utils_detail.cc), not via jax_debug_nans
    # (which reports an anonymous FloatingPointError mid-jit)
    "check_nan_inf": (False, bool, None),
    # -- RPC hardening (reference FLAGS_rpc_deadline ms / rpc_retry_times;
    # here the deadline is SECONDS and must exceed the pserver's 120s
    # sync-barrier wait so a slow-but-live barrier isn't killed) --
    "rpc_deadline": (150.0, float, None),
    "rpc_retry_times": (3, int, None),
    "rpc_retry_base_backoff": (0.05, float, None),
    "rpc_circuit_break_failures": (3, int, None),
    "rpc_circuit_reset_secs": (5.0, float, None),
    # -- serving runtime (paddle_tpu/serving) --
    # batch former: flush a signature's batch at max_batch_size rows or
    # after the oldest member waited batch_timeout_ms
    "serving_max_batch_size": (32, int, None),
    "serving_batch_timeout_ms": (5.0, float, None),
    # admission: hard pending-request cap (backpressure) and the default
    # per-request deadline (0 = no deadline unless the request sets one)
    "serving_queue_depth": (256, int, None),
    "serving_default_deadline_ms": (0.0, float, None),
    # compiled-executable cache caps (0 = unbounded on that axis)
    "serving_cache_entries": (32, int, None),
    "serving_cache_bytes": (0, int, None),
    # load-shed breaker: consecutive queue-full refusals that open it,
    # and how long it sheds before re-probing
    "serving_shed_failures": (8, int, None),
    "serving_shed_reset_secs": (0.5, float, None),
    # -- serving resilience layer --
    # wall-clock budget per batcher execute / decode step (run under
    # resilience.run_with_watchdog so a hung chip call fails that
    # batch's clients instead of wedging the loop) and the supervisor's
    # stale-heartbeat threshold. Must exceed the worst-case first-shape
    # compile; 0 disables the watchdog and the hung-loop detector.
    "serving_loop_watchdog_s": (60.0, float, None),
    # client-side hedged requests: hedge `infer` after this many ms
    # without a reply (p99-derived once the client has observed enough
    # traffic; this flag is the cold-start delay). 0 = hedging off.
    "serving_hedge_ms": (0.0, float, None),
    # default seed for resilience.chaos() fault-point streams
    "chaos_seed": (0, int, None),
    # -- unified telemetry (paddle_tpu/observability) --
    # fraction of requests that carry a trace context (wire-propagated
    # request tracing): 0.0 = off, 1.0 = every request. Sampled at the
    # CLIENT (serving.Client / tracing.maybe_trace); untraced requests
    # pay one random() draw and nothing else
    "trace_sample_rate": (0.01, float, None),
    # flight recorder ring capacity (recent structured events kept for
    # postmortem dumps: admissions, evictions, restarts, chaos firings,
    # non-finite hits, weight reloads, preemptions)
    "flight_recorder_events": (512, int, None),
    # directory for AUTOMATIC flight-recorder dumps (written when a
    # typed Internal/Watchdog error crosses the serving wire boundary,
    # rate-limited). "" = automatic dumps off; the "debug_dump" wire op
    # and FlightRecorder.dump() always work
    "flight_recorder_dir": ("", str, None),
    # -- performance attribution & SLO guardrails --
    # sampled MEASURED per-op profiling: 0 = off (the default — the
    # executor hot path pays one flag read and is bitwise-unchanged);
    # N >= 1 = every N-th Executor.run dispatch of a program additionally
    # replays the optimized clone op-by-op (eager, synced) to record a
    # per-op wall-time table + Perfetto op spans + the hbm_live_bytes
    # counter track. The committed step result still comes from the
    # fused executable — profiling never changes numerics.
    "profile_ops": (0, int, None),
    # start the default SLO monitor (observability/slo.py) inside every
    # InferenceServer: p99 inter-token latency, queue-depth ratios,
    # kvpool occupancy, optional MFU floor
    "slo_monitor": (True, bool, None),
    # SLO rule evaluation cadence (the supervised monitor loop)
    "slo_poll_s": (0.25, float, None),
    # default-ruleset thresholds (0 disables the individual rule):
    # windowed p99 of the decode stage (inter-token latency proxy, ms)
    "slo_decode_p99_ms": (2000.0, float, None),
    # queue depth as a fraction of the admission cap
    "slo_queue_ratio": (0.9, float, None),
    # paged KV pool occupancy (blocks in use / allocatable)
    "slo_kvpool_ratio": (0.95, float, None),
    # MFU floor on the decode path (0 = rule off; set > 0 on real
    # accelerators where peak tables are known)
    "slo_mfu_floor": (0.0, float, None),
    # -- sharding audit & collective-traffic ledger (observability/
    # sharding, observability/comms) --
    # audit every newly compiled MESH executable's actual shardings
    # against the declared dist_attr/PartitionSpecs and emit typed
    # findings (replicated-large-param, unsharded-batch,
    # sharding-mismatch, reshard-inserted) as shard_audit_finding
    # flight events + shard_audit_findings_total. Off by default: the
    # compile-miss path pays one flag read and numerics are
    # bitwise-unchanged either way (the audit only READS the compiled
    # executable)
    "shard_audit": (False, bool, None),
    # replicated-large-param threshold: a persistable input replicated
    # across a >1 mesh axis only becomes a finding at or above this
    # many megabytes (small scales/biases legitimately replicate)
    "shard_audit_replicated_mb": (16.0, float, None),
    # parse every newly compiled mesh executable's HLO for collectives
    # (all-reduce / all-gather / reduce-scatter / all-to-all /
    # collective-permute), attribute each to a mesh axis via its
    # replica_groups, and export per-(collective, axis) bytes/op
    # counters plus the predicted device_comm_bound_ratio gauge
    "comms_ledger": (False, bool, None),
    # comma-separated mesh axes that ride DCN instead of ICI (multi-
    # slice deployments: an axis spanning slices prices its
    # collectives at the cross-slice fabric). A collective whose group
    # varies over ANY listed axis uses the DCN peak. "" = all-ICI
    "comms_dcn_axes": ("", str, None),
    # -- multi-slice training (train/slices, framework/passes
    # hier_grad_sync) --
    # run dcn_dp meshes through the hierarchical grad-sync path:
    # reduce-scatter in-slice (ICI), all-reduce across slices (DCN) on
    # the 1/dp shard each chip owns, all-gather in-slice. False =
    # plain GSPMD (the flat-all-reduce A/B baseline; numerics
    # unchanged — hier_allreduce is mathematically the same mean)
    "dcn_hierarchical": (True, bool, None),
    # before the first multi-slice slab is dispatched, parse the
    # compiled HLO and ASSERT the decomposition: DCN-priced traffic
    # only on FLAGS_comms_dcn_axes, and cross-slice wire bytes
    # strictly below the flat all-reduce estimate — raising
    # HierarchicalCommsError before a chip is burned
    "dcn_assert_hier": (True, bool, None),
    # SliceSupervisor liveness: a slice whose last heartbeat is older
    # than this many seconds counts one stale observation
    "slice_heartbeat_timeout_s": (5.0, float, None),
    # hysteresis window: membership only changes after this many
    # CONSECUTIVE stale (shrink) or fresh (regrow) observations
    "slice_window": (3, int, None),
    # cooldown between membership changes (shrink or regrow), so a
    # flapping slice can't thrash checkpoint-restore cycles
    "slice_cooldown_s": (10.0, float, None),
    # -- training observability (observability/goodput, train/health,
    # observability/inputstall) --
    # model-health monitoring cadence: every N-th supervised slab
    # additionally fetches per-slab loss / global grad-norm /
    # param-update-ratio IN-GRAPH through the run_steps fetch path and
    # evaluates the loss-spike / grad-norm-spike SLO rules. 0 (default)
    # = off: no ops are added to the program and the fused-step path is
    # bitwise-unchanged
    "train_health_every_n": (0, int, None),
    # health rule thresholds: breach when the fetched value exceeds
    # this multiple of its trailing EMA (loss spike / grad-norm spike)
    "train_loss_spike_ratio": (3.0, float, None),
    "train_grad_spike_ratio": (10.0, float, None),
    # input-pipeline stall profiler: flag a data_stall flight event
    # when, over a window of at least dataio_stall_window_s seconds,
    # the consumer spent more than dataio_stall_ratio of the wall time
    # blocked waiting on the producer queue
    "dataio_stall_window_s": (1.0, float, None),
    "dataio_stall_ratio": (0.5, float, None),
    # -- elastic training (paddle_tpu/train) --
    # periodic full-training-state checkpoint cadence for
    # TrainingSupervisor: one async (CheckFreq-staged) checkpoint every
    # N fused slabs
    "checkpoint_every_n_slabs": (16, int, None),
    # wall-clock budget for the preemption fast checkpoint (SIGTERM ->
    # save at next slab boundary -> exit); a save that misses it is
    # abandoned and the previous verified checkpoint stands. 0 = no
    # bound (save however long it takes before exiting)
    "preempt_deadline_s": (30.0, float, None),
    # how many supervised-restart attempts (crash/hang -> reload newest
    # checkpoint with capped backoff) before RestartBudgetExceeded
    "train_restart_budget": (3, int, None),
    # -- KV-cached autoregressive decoding (models/generation, serving
    # decode batching) --
    # the most positions a row's block table holds in the paged KV pool:
    # prompt length + max_new_tokens must fit (clamped to the model's
    # max_position)
    "decode_max_len": (2048, int, None),
    # minimum prefill sequence bucket: prompts pad up to the next
    # power-of-two >= this, bounding the universe of compiled prefill
    # shapes (buckets: decode_bucket_min, 2x, 4x, ... decode_max_len;
    # from 2048 up their midpoints too, models/generation.length_bucket)
    "decode_bucket_min": (16, int, None),
    # serving decode batch: fixed number of generation slots stepped by
    # one compiled decode executable; finished rows free their slot for
    # the next admitted request (continuous batching)
    "decode_slots": (8, int, None),
    # speculative decoding (Leviathan 2022 / Chen 2023): draft depth K —
    # a drafter proposes up to K tokens per row per step, one verify
    # pass scores all K+1 positions, rejection sampling keeps the
    # model-agreed prefix. 0 = off (the parity baseline); greedy output
    # is bitwise-identical either way
    "decode_spec_k": (0, int, None),
    # default drafter: "ngram" (free prompt-lookup self-drafting) or
    # "model" (1-layer draft GPT sharing the generator's parameter
    # snapshot)
    "decode_spec_mode": ("ngram", str, None),
    # -- paged KV cache (serving/kvpool, kernels/paged_attention) --
    # decode memory is block-paged: KV caches live in a shared block
    # pool with per-slot block tables (vLLM/PagedAttention); blocks
    # allocate on append and free on EOS/deadline/cancel, so
    # concurrency is bounded by actual tokens.
    # KV-cache element type: fp32 (bitwise baseline), bf16 (half the
    # cache bytes), int8 (quarter, with per-(block, head, slot) float32
    # scales) — at bandwidth-bound decode, cache bytes ARE tokens/s
    "kv_cache_dtype": ("fp32", str, None),
    # tokens per KV block: small = fine-grained allocation (less
    # last-block waste), large = smaller tables and fewer allocations
    "kv_block_size": (16, int, None),
    # total pool blocks (incl. the reserved trash block); 0 = size the
    # pool HBM-equivalent to the dense bank it replaces
    # (slots * ceil(max_len/block_size) + 1)
    "kv_pool_blocks": (0, int, None),
    # -- pod-scale serving (tp generation, chunked prefill, prefix
    # cache) --
    # tensor-parallel generation: compile prefill/decode/logits
    # executables under a tp=N mesh (Megatron column/row split via
    # gpt.apply_tp_sharding; pool block arrays sharded on the head
    # axis), gated at compile time by the sharding audit + a
    # comms-ledger wire-byte budget. 0/1 = single-chip (the parity
    # baseline)
    "serving_tp": (0, int, None),
    # chunked prefill (Orca/Sarathi continuous scheduling): admission
    # prefill proceeds in slices of at most this many tokens,
    # interleaved with decode steps so a long prompt never stalls the
    # decode bank's token cadence. 0 = monolithic prefill
    "prefill_chunk_tokens": (0, int, None),
    # block-granular prefix caching: completed prompts deposit their KV
    # blocks into a refcounted hash-keyed index; a new prompt sharing a
    # prefix adopts those blocks (copy-on-write on divergence) and only
    # prefills the tail. Cold entries evict LRU under pool pressure
    "kv_prefix_cache": (False, bool, None),
    # -- overload control (resilience.RetryBudget, serving brownout,
    # fleet autoscaler) --
    # process-global retry budget: every initial request deposits this
    # many retry tokens; every retry/hedge/failover withdraws one, so
    # tail-fighting machinery is bounded at ~ratio x offered load and a
    # saturated fleet sheds instead of amplifying itself (Tail at
    # Scale). A small time-based reserve keeps isolated failures
    # retryable. < 0 disables the budget (unbounded retries — the
    # bench.py --config overload A/B lever)
    "retry_budget_ratio": (0.1, float, None),
    # brownout degradation ladder: a breached-SLO server degrades
    # best-effort, then batch traffic (shed + capped max_new_tokens +
    # shrunken admission) BEFORE interactive traffic, recovering
    # symmetrically as breaches clear
    "serving_brownout": (True, bool, None),
    # fleet autoscaler bounds: the Autoscaler holds the replica pool
    # between these (inclusive), scaling on windowed fleet telemetry
    "fleet_min_replicas": (1, int, None),
    "fleet_max_replicas": (4, int, None),
    # minimum seconds between autoscaler scale events (with the
    # full-window hysteresis this is what keeps the pool from flapping)
    "fleet_scale_cooldown_s": (5.0, float, None),
    # -- disaggregated serving fleet (serving/fleet) --
    # router health-probe cadence against every registered replica, and
    # the per-probe wire timeout (a hung replica's accept loop must fail
    # the probe fast, not inherit the long socket default)
    "router_probe_interval_s": (0.5, float, None),
    "router_probe_timeout_s": (2.0, float, None),
    # consecutive failed probes before a replica is EVICTED from the
    # dispatch rotation (probing continues; a healthy probe readmits it)
    "router_evict_after": (3, int, None),
    # cross-replica hedging: fire a twin of a routed generate on a
    # SECOND replica after this many ms without a reply (the loser is
    # cancelled by request id). 0 = hedging off (failover-on-death only)
    "router_hedge_ms": (0.0, float, None),
    # extra replicas tried when a dispatch target dies mid-request
    # (transport failure -> the replica is marked dead and the request
    # fails over with the SAME request id)
    "router_dispatch_retries": (2, int, None),
    # Executor per-(program, feed-shape) compile cache entry cap — bounds
    # what was previously unbounded growth per input-shape signature
    "executor_cache_entries": (128, int, None),
    # -- pre-lowering program optimization pipeline (framework/passes) --
    # "1"/"default" = the default pipeline (dce,cse,fuse_optimizer) runs
    # on every executor compile-cache miss; "0" = off, reproducing the
    # unoptimized lowering bitwise; or an explicit comma-separated pass
    # list (e.g. "dce,cse") run in canonical registry order
    "program_passes": ("1", str, None),
    # per-pass translation validation (framework/analysis.py): verify
    # every pass's output program and the user program on compile-cache
    # misses, raising typed ProgramVerifyError with pass provenance.
    # Off by default (the hot path pays nothing); tests/CI turn it on
    # (tests/conftest.py), and `python tools/lint_program.py` runs the
    # same checkers standalone
    "verify_passes": (False, bool, None),
    # flattened-concat byte cap per fused-optimizer bucket (multi-tensor
    # apply): same-(op, dtype, hyperparam) update ops group into buckets
    # of at most this many megabytes of parameters
    "fuse_optimizer_bucket_mb": (64, int, None),
    # -- fused multi-step training loop (Executor.run_steps) --
    # default K for train_from_dataset: K steps compile into ONE jitted
    # lax.scan over a stacked feed slab (1 = unfused per-step dispatch)
    "steps_per_run": (1, int, None),
    # materialize fetches only on every N-th slab / print_period hit;
    # in-between slabs run a fetch-free executable (1 = every slab)
    "fetch_every_n": (1, int, None),
    # run_steps scan unroll factor. 1 (default) = loop form: bitwise
    # parity with sequential run() and K-independent compile time.
    # 0 = auto: full unroll on the CPU backend (XLA CPU runs while-loop
    # bodies without intra-op threading, so the loop form serializes
    # convs), loop form on accelerators. N>1 unrolls N steps per loop
    # iteration. Unrolled steps may fuse across step boundaries —
    # numerically equivalent but not bit-identical to sequential run().
    "scan_unroll": (1, int, None),
    "cudnn_deterministic": (False, bool, None),
    "cpu_deterministic": (False, bool, None),
    "benchmark": (False, bool, None),
    "eager_delete_tensor_gb": (0.0, float, None),
    "fraction_of_gpu_memory_to_use": (0.92, float, None),
    "allocator_strategy": ("auto_growth", str, None),
    "fast_eager_deletion_mode": (True, bool, None),
    "memory_fraction_of_eager_deletion": (1.0, float, None),
    "sync_nccl_allreduce": (True, bool, None),
    "communicator_independent_recv_thread": (True, bool, None),
    "communicator_send_queue_size": (20, int, None),
    "communicator_max_merge_var_num": (20, int, None),
    "paddle_num_threads": (1, int, None),
    "inner_op_parallelism": (0, int, None),
    "init_allocated_mem": (False, bool, None),
    "free_idle_chunk": (False, bool, None),
    "use_pinned_memory": (True, bool, None),
    "tracer_profile_fname": ("", str, None),
    "selected_tpus": ("", str, None),
}

# Accepted-but-inert compatibility knobs: declared so reference launch
# scripts (CUDA allocator tuning, communicator threading, eager GC) run
# unchanged, but nothing on the TPU path reads them — XLA owns what they
# governed. tools/lint_flags.py enforces that every OTHER declared flag
# is actually referenced somewhere in paddle_tpu/ (and that every
# FLAGS_* reference is declared); a new flag is either read by code or
# belongs in this set.
_COMPAT_ONLY = frozenset({
    "allocator_strategy", "benchmark",
    "communicator_independent_recv_thread",
    "communicator_max_merge_var_num", "communicator_send_queue_size",
    "cpu_deterministic", "cudnn_deterministic",
    "eager_delete_tensor_gb", "fast_eager_deletion_mode",
    "fraction_of_gpu_memory_to_use", "free_idle_chunk",
    "init_allocated_mem", "inner_op_parallelism",
    "memory_fraction_of_eager_deletion", "paddle_num_threads",
    "sync_nccl_allreduce", "tracer_profile_fname", "use_pinned_memory",
})

_values = {}


def _coerce(raw, typ):
    if typ is bool:
        return str(raw).lower() in ("1", "true", "yes", "on")
    return typ(raw)


def _apply(name, value):
    hook = _DEFS[name][2]
    if hook == "jax_debug_nans":
        import jax
        jax.config.update("jax_debug_nans", bool(value))


def _init():
    for name, (default, typ, _) in _DEFS.items():
        raw = os.environ.get(f"FLAGS_{name}")
        val = _coerce(raw, typ) if raw is not None else default
        _values[name] = val
        if raw is not None:
            _apply(name, val)


def get_flags(flags):
    """fluid.get_flags parity: names with or without the FLAGS_ prefix."""
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _values:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _values[key]
    return out


def set_flags(flags_dict):
    """fluid.set_flags parity."""
    for n, v in flags_dict.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _DEFS:
            raise ValueError(f"unknown flag {n!r}")
        _values[key] = _coerce(v, _DEFS[key][1])
        _apply(key, _values[key])


def flag(name):
    """Fast single-flag getter for hot paths (Executor.run, PSClient)."""
    return _values[name]


def globals_():
    """fluid.core.globals() analog: a live view of every flag."""
    return dict(_values)


_init()
