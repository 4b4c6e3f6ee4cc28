"""Unified host-side metrics + span tracing (the monitoring subsystem).

Disabled by default, and wired through the trainers
(`nn/multilayer.py`, `nn/graph.py`), the parallel stack
(`parallel/wrapper.py`, `parallel/sharded_trainer.py`,
`parallel/inference.py`), the executioner (`runtime/executioner.py`),
and the dashboard (`ui/server.py` serves `GET /metrics` in Prometheus
text format and a live metrics tab).

Quick start (one line at each end):

    net.setListeners(MetricsListener())          # optimize/listeners.py
    UIServer.getInstance().start()               # GET /metrics

or explicitly:

    from deeplearning4j_tpu import monitoring
    monitoring.enable()
    ... fit / serve ...
    monitoring.export_chrome_trace("/tmp/fit_trace.json")  # Perfetto
    print(monitoring.get_registry().prometheus_text())

ONE trace, host spans and device operations on one clock:
- `span()` (`tracing.py`) is the program's one instrumentation point.
  Every span — `fit.*` / `train.*` / `pipeline.*` in the trainers and
  the input pipeline, `serve.*` in `generation/server.py`, `exec.*` on
  the executable stores' miss path — is written into `jax.profiler`'s
  trace as `dl4j.<name>` with its counts as stats, whether monitoring
  is enabled or not; what the device runs carries stable names beside
  them (`jit_superstep`, `jit_graph_train_step`, the `flash_*` kernels,
  the decoder's `layer<i>/attn` scopes). So any profiler session —
  `profiler.ProfileSession` (`profile_next_steps(k)` /
  `POST /profile?steps=k`, decoded by `optimize/xplane.py` into a
  per-op table), or `benchmarks/run.py --trace 1` — yields a trace in
  which a gap on the device names what the host was doing in it.
- with monitoring ENABLED the same spans also land in the in-memory
  `Tracer` (Chrome trace JSON) and the step-time attribution flight
  recorder (`steps.py`, `GET /steps`), next to the registry's metrics,
  jit compile events, transfer bytes and device memory telemetry + OOM
  forensics (`memory.py`);
- `ui/stats.StatsListener` — LEARNING diagnostics: score curves, update
  ratios, activation histograms.
"""
from __future__ import annotations

from deeplearning4j_tpu.monitoring.state import STATE
from deeplearning4j_tpu.monitoring import cluster  # noqa: F401
from deeplearning4j_tpu.monitoring import memory  # noqa: F401
from deeplearning4j_tpu.monitoring import profiler  # noqa: F401
from deeplearning4j_tpu.monitoring import requests  # noqa: F401
from deeplearning4j_tpu.monitoring import events  # noqa: F401
from deeplearning4j_tpu.monitoring import slo  # noqa: F401
from deeplearning4j_tpu.monitoring import steps  # noqa: F401
from deeplearning4j_tpu.monitoring import stragglers  # noqa: F401
from deeplearning4j_tpu.monitoring.requests import (  # noqa: F401
    RequestLog, RequestTimeline, merged_chrome_trace, request_log)
from deeplearning4j_tpu.monitoring.slo import (  # noqa: F401
    LatencyObjective, RatioObjective, SloTracker, StepTimeObjective,
    StragglerObjective, ThroughputObjective, standard_objectives)
from deeplearning4j_tpu.monitoring.memory import (  # noqa: F401
    MemoryMonitor)
from deeplearning4j_tpu.monitoring.profiler import (  # noqa: F401
    ProfileSession, last_report, profile_next_steps)
from deeplearning4j_tpu.monitoring.steps import (  # noqa: F401
    StepRecorder, recorder as step_recorder)
from deeplearning4j_tpu.monitoring.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry,
    JIT_CACHE_MISSES, JIT_COMPILE_SECONDS, OP_DISPATCHES,
    JIT_PERSISTENT_HITS, JIT_PERSISTENT_MISSES,
    JIT_PERSISTENT_REQUESTS,
    EXEC_COMPILES, EXEC_COMPILE_SECONDS, EXEC_DISK_HITS,
    EXEC_DESERIALIZE_FAILURES, EXEC_SERIALIZE_FAILURES,
    EXEC_FLOPS, EXEC_BYTES_ACCESSED,
    SERVING_ROWS, SERVING_PADDED_ROWS, SERVING_BUCKET_OCCUPANCY,
    SERVING_SPLITS, SERVING_STAGED_BUFFERS, SERVING_STAGING_OCCUPANCY,
    SERVING_AOT_FALLBACKS,
    TRANSFER_H2D_BYTES, DEVICE_MEMORY_BYTES, DEVICE_MEMORY_SUPPORTED,
    HOST_RSS_BYTES,
    RESILIENCE_RETRIES, RESILIENCE_BACKOFF_SECONDS,
    RESILIENCE_BREAKER_TRIPS, RESILIENCE_FAULTS_INJECTED,
    RESILIENCE_BATCHES_SKIPPED, RESILIENCE_CHECKPOINT_SAVES,
    RESILIENCE_RESUMES, RESILIENCE_RESUME_STEP,
    RESILIENCE_INFERENCE_SHED, RESILIENCE_INFERENCE_TIMEOUTS,
    RESILIENCE_COLLECTOR_RESTARTS, RESILIENCE_CKPT_ORPHANS_REMOVED,
    RESILIENCE_CKPT_FALLBACKS,
    GUARDIAN_CHECKS, GUARDIAN_SKIPPED_UPDATES, GUARDIAN_LR_RETRIES,
    GUARDIAN_ROLLBACKS, GUARDIAN_SAVES_GATED, GUARDIAN_LAST_GOOD_STEP,
    WATCHDOG_STALLS, WATCHDOG_BEAT_AGE_SECONDS, WATCHDOG_DUMPS,
    DIST_PEERS, DIST_PEER_LOST, DIST_PREEMPTIONS,
    DIST_BARRIER_TIMEOUTS, DIST_ENCODED_BYTES, DIST_RESIDUAL_NORM,
    DIST_ACCUM_MICROBATCHES, DIST_EXCHANGE_BUCKETS, DIST_BUCKET_BYTES,
    DIST_EXPOSED_EXCHANGE_MS, DIST_ENCODER_MIGRATIONS,
    DIST_REFORMS_AGREED, DIST_REFORMS, DIST_REFORM_MS, DIST_WIRE_BYTES,
    DIST_STRAGGLER_RATIO, DIST_STRAGGLER_SKEW_MS,
    PIPELINE_SYNCS, PIPELINE_HOST_BLOCKED_MS, PIPELINE_PREFETCH_DEPTH,
    PIPELINE_STAGED_BATCHES,
    PROFILE_SESSIONS, PROFILE_CAPTURED_STEPS, PROFILE_DEVICE_MS,
    PROFILE_OP_MS, PROFILE_OP_COUNT,
    STEP_WALL_MS, STEP_PHASE_MS,
    MODEL_PARAMS_BYTES, MODEL_OPT_STATE_BYTES, MODEL_LAYER_STATE_BYTES,
    GEN_TOKENS, GEN_ACTIVE_SLOTS, GEN_ADMISSIONS, GEN_RETIREMENTS,
    GEN_PREFILL_MS, GEN_PER_TOKEN_MS, GEN_REPLAYS, GEN_RESTARTS,
    GEN_DEGRADATIONS, GEN_SUPERSTEPS, GEN_TOKENS_PER_DISPATCH,
    GEN_FETCH_OVERLAP_MS, GEN_DRAFT_ACCEPTS, GEN_DRAFT_REJECTS,
    GEN_PAGES_ACTIVE, GEN_PAGES_SHARED, GEN_PAGE_EVICTIONS,
    GEN_PREFIX_HITS,
    FLEET_ROUTED, FLEET_FAILOVERS, FLEET_REPLACEMENTS, FLEET_HEALTHY,
    FLEET_DESIRED_REPLICAS,
    QUANT_INT8_LAYERS, QUANT_CALIBRATIONS, QUANT_DEQUANT_FALLBACKS,
    QUANT_ACTIVATION_BYTES,
    INFERENCE_REQUEST_MS, SLO_BREACHES, SLO_BURN_RATE, SLO_BREACHED,
    EVENTS_EMITTED, EVENTS_DROPPED, INCIDENTS_OPEN, INCIDENTS_RESOLVED,
    CLUSTER_SNAPSHOT_AGE,
    bootstrap_core_metrics, collect_device_memory, get_registry,
    record_transfer)
from deeplearning4j_tpu.monitoring.tracing import (  # noqa: F401
    PROFILER_PREFIX, Span, Tracer, export_chrome_trace, get_tracer,
    span, traced_iter)

__all__ = [
    "enable", "disable", "enabled", "span", "traced_iter",
    "export_chrome_trace", "get_tracer", "get_registry",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Tracer",
    "bootstrap_core_metrics", "collect_device_memory", "record_transfer",
    "memory", "profiler", "steps",
    "MemoryMonitor", "ProfileSession", "StepRecorder",
    "last_report", "profile_next_steps", "step_recorder",
    "PROFILE_SESSIONS", "PROFILE_CAPTURED_STEPS", "PROFILE_DEVICE_MS",
    "PROFILE_OP_MS", "PROFILE_OP_COUNT",
    "STEP_WALL_MS", "STEP_PHASE_MS",
    "MODEL_PARAMS_BYTES", "MODEL_OPT_STATE_BYTES",
    "MODEL_LAYER_STATE_BYTES",
    "JIT_CACHE_MISSES", "JIT_COMPILE_SECONDS", "OP_DISPATCHES",
    "JIT_PERSISTENT_HITS", "JIT_PERSISTENT_MISSES",
    "JIT_PERSISTENT_REQUESTS",
    "EXEC_COMPILES", "EXEC_COMPILE_SECONDS", "EXEC_DISK_HITS",
    "EXEC_DESERIALIZE_FAILURES", "EXEC_SERIALIZE_FAILURES",
    "EXEC_FLOPS", "EXEC_BYTES_ACCESSED",
    "SERVING_ROWS", "SERVING_PADDED_ROWS", "SERVING_BUCKET_OCCUPANCY",
    "SERVING_SPLITS", "SERVING_STAGED_BUFFERS",
    "SERVING_STAGING_OCCUPANCY", "SERVING_AOT_FALLBACKS",
    "TRANSFER_H2D_BYTES", "DEVICE_MEMORY_BYTES",
    "DEVICE_MEMORY_SUPPORTED", "HOST_RSS_BYTES",
    "RESILIENCE_RETRIES", "RESILIENCE_BACKOFF_SECONDS",
    "RESILIENCE_BREAKER_TRIPS", "RESILIENCE_FAULTS_INJECTED",
    "RESILIENCE_BATCHES_SKIPPED", "RESILIENCE_CHECKPOINT_SAVES",
    "RESILIENCE_RESUMES", "RESILIENCE_RESUME_STEP",
    "RESILIENCE_INFERENCE_SHED", "RESILIENCE_INFERENCE_TIMEOUTS",
    "RESILIENCE_COLLECTOR_RESTARTS", "RESILIENCE_CKPT_ORPHANS_REMOVED",
    "RESILIENCE_CKPT_FALLBACKS",
    "GUARDIAN_CHECKS", "GUARDIAN_SKIPPED_UPDATES", "GUARDIAN_LR_RETRIES",
    "GUARDIAN_ROLLBACKS", "GUARDIAN_SAVES_GATED", "GUARDIAN_LAST_GOOD_STEP",
    "WATCHDOG_STALLS", "WATCHDOG_BEAT_AGE_SECONDS", "WATCHDOG_DUMPS",
    "DIST_PEERS", "DIST_PEER_LOST", "DIST_PREEMPTIONS",
    "DIST_BARRIER_TIMEOUTS", "DIST_ENCODED_BYTES", "DIST_RESIDUAL_NORM",
    "DIST_ACCUM_MICROBATCHES", "DIST_EXCHANGE_BUCKETS",
    "DIST_BUCKET_BYTES", "DIST_EXPOSED_EXCHANGE_MS",
    "DIST_ENCODER_MIGRATIONS",
    "DIST_REFORMS_AGREED", "DIST_REFORMS", "DIST_REFORM_MS",
    "DIST_WIRE_BYTES",
    "DIST_STRAGGLER_RATIO", "DIST_STRAGGLER_SKEW_MS",
    "PIPELINE_SYNCS", "PIPELINE_HOST_BLOCKED_MS", "PIPELINE_PREFETCH_DEPTH",
    "PIPELINE_STAGED_BATCHES",
    "GEN_TOKENS", "GEN_ACTIVE_SLOTS", "GEN_ADMISSIONS",
    "GEN_RETIREMENTS", "GEN_PREFILL_MS", "GEN_PER_TOKEN_MS",
    "GEN_REPLAYS", "GEN_RESTARTS", "GEN_DEGRADATIONS",
    "GEN_SUPERSTEPS", "GEN_TOKENS_PER_DISPATCH", "GEN_FETCH_OVERLAP_MS",
    "GEN_DRAFT_ACCEPTS", "GEN_DRAFT_REJECTS",
    "GEN_PAGES_ACTIVE", "GEN_PAGES_SHARED", "GEN_PAGE_EVICTIONS",
    "GEN_PREFIX_HITS",
    "FLEET_ROUTED", "FLEET_FAILOVERS", "FLEET_REPLACEMENTS",
    "FLEET_HEALTHY", "FLEET_DESIRED_REPLICAS",
    "QUANT_INT8_LAYERS", "QUANT_CALIBRATIONS",
    "QUANT_DEQUANT_FALLBACKS", "QUANT_ACTIVATION_BYTES",
    "INFERENCE_REQUEST_MS", "SLO_BREACHES", "SLO_BURN_RATE",
    "SLO_BREACHED", "CLUSTER_SNAPSHOT_AGE",
    "EVENTS_EMITTED", "EVENTS_DROPPED", "INCIDENTS_OPEN",
    "INCIDENTS_RESOLVED",
    "requests", "slo", "cluster", "stragglers", "events",
    "RequestLog", "RequestTimeline", "request_log",
    "merged_chrome_trace",
    "SloTracker", "LatencyObjective", "ThroughputObjective",
    "RatioObjective", "StepTimeObjective", "StragglerObjective",
    "standard_objectives",
]


def enable():
    """Turn on metrics collection and span recording globally."""
    STATE.enabled = True


def disable():
    """Back to the default: registry calls one branch per call site, a
    span one profiler annotation that records nothing."""
    STATE.enabled = False


def enabled():
    return STATE.enabled
