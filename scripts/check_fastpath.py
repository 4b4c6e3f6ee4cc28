#!/usr/bin/env python
"""Fast-path lint: instrumented hot-path modules must not call the
metrics registry outside an enabled-guard.

The monitoring contract on the disabled path: every
`registry.counter(...)` / `.gauge(...)` / `.histogram(...)` /
`get_registry()` reachable per-step must sit inside the
`if _mon.enabled():` / `if STATE.enabled:` guard pattern (or behind an
early `if not ...enabled...: return`) — ONE branch. A bare registry call
costs a lock + dict lookup + possible allocation per step even with
monitoring off — exactly the always-on overhead the disabled-by-default
design exists to prevent, and the kind of regression that creeps in
silently with new instrumentation. `_mon.span(...)` is the one thing
allowed outside the guard: disabled, it is ONE profiler annotation that
records nothing (`jax.profiler.TraceAnnotation`, a flag test in C++
unless a profiler session is on; measured cost in PERF.md, Findings
PR 26), which is what puts the program's phases into a profiler trace
beside the device's operations.

This script AST-walks the hot-path modules and reports violations;
`tests/test_fastpath_lint.py` runs it in tier-1 so a violating PR fails
CI. Run manually:  python scripts/check_fastpath.py  (exit 1 on
violations).

Intentionally NOT linted: `monitoring/` internals (they ARE the guard),
`_mon.span(...)` (above), `record_transfer(...)` / `step_recorder()`
(each internally one flag check), and cold-path modules (listeners, ui,
resilience policies) where a per-call registry lookup is irrelevant.
"""
from __future__ import annotations

import ast
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: per-step hot-path modules (relative to the repo root). The
#: resilience entries are the guardian/watchdog/fault hooks that sit
#: INSIDE every train step — their registry calls must be behind the
#: enabled-guard exactly like the trainers' own instrumentation
#: (resilience/policy.py stays unlinted: breaker trips and retry
#: backoffs are cold by definition).
HOT_MODULES = [
    "deeplearning4j_tpu/nn/multilayer.py",
    "deeplearning4j_tpu/nn/graph.py",
    "deeplearning4j_tpu/runtime/executioner.py",
    "deeplearning4j_tpu/runtime/pipeline.py",
    "deeplearning4j_tpu/runtime/executables.py",
    "deeplearning4j_tpu/parallel/wrapper.py",
    "deeplearning4j_tpu/parallel/sharded_trainer.py",
    "deeplearning4j_tpu/parallel/inference.py",
    # multi-host hot hooks: the per-step coordination/heartbeat/verdict
    # paths must stay one pointer compare when disabled, and their
    # sync-point registry calls guarded like everything else
    "deeplearning4j_tpu/parallel/coordination.py",
    "deeplearning4j_tpu/parallel/multihost.py",
    # elastic membership: `pending()` folds into EVERY heartbeat, and
    # the reform/commit/reap paths live next to the runner's counters —
    # registry traffic there obeys the same enabled-guard contract
    "deeplearning4j_tpu/parallel/membership.py",
    "deeplearning4j_tpu/resilience/guardian.py",
    "deeplearning4j_tpu/resilience/watchdog.py",
    "deeplearning4j_tpu/resilience/faults.py",
    "deeplearning4j_tpu/resilience/trainer.py",
    # generation decode loop: per-token metric calls must stay behind
    # the enabled-guard (one dict-get + dispatch per token otherwise)
    "deeplearning4j_tpu/generation/server.py",
    "deeplearning4j_tpu/generation/decode.py",
    "deeplearning4j_tpu/generation/sampling.py",
    "deeplearning4j_tpu/generation/paging.py",
    # fleet router: routed/failover counters ride every request's
    # relay path — guarded, or the disabled fleet pays per request
    "deeplearning4j_tpu/generation/fleet.py",
    # quantized inference: the rewritten layers' apply() and the chain
    # executor run inside every served forward — registry calls belong
    # to the rewrite/calibration cold path only
    "deeplearning4j_tpu/quantize/core.py",
    "deeplearning4j_tpu/quantize/infer.py",
    "deeplearning4j_tpu/quantize/kvcache.py",
    # request-timeline module: its appends ride the decode/dispatch
    # hot paths, so any registry/exemplar traffic it ever grows must
    # sit behind the enabled guard like the call sites that feed it.
    # monitoring/slo.py and monitoring/cluster.py stay UNLINTED on
    # purpose: both are pull-driven (endpoint / sync-point cadence,
    # never per step) — the same cold-path class as listeners and ui.
    "deeplearning4j_tpu/monitoring/requests.py",
]

# -- serving steady-state lint --------------------------------------------
#: modules forming the AOT serving hot path: everything REACHABLE from
#: the roots below (intra-repo call graph by function name) must never
#: trace or compile — `jax.jit` / `.lower()` / `.compile()` belong to
#: the declared miss-path boundary functions only
SERVING_MODULES = [
    "deeplearning4j_tpu/parallel/inference.py",
    "deeplearning4j_tpu/runtime/executables.py",
    # request timelines are appended from the dispatch path — the
    # walker descends into the append helpers to prove they stay pure
    # host bookkeeping (no trace, no compile)
    "deeplearning4j_tpu/monitoring/requests.py",
]
#: steady-state entry points: the collector's dispatch path and the
#: store/ring hot methods
SERVING_ROOTS = {"_dispatch", "_run", "lookup", "stage", "release"}
#: the documented miss-path boundary: steady state never crosses it
#: (`load_or_compile` runs only when `lookup` missed — i.e. a shape
#: outside the warmed ladder); the traversal does not descend into it
SERVING_MISS_BOUNDARY = {"load_or_compile", "warmup"}
#: calls that mean "a trace or an XLA compile happens here"
TRACE_CALL_NAMES = {"jit", "lower", "compile", "eval_shape", "trace"}

# -- generation decode-loop lint -------------------------------------------
#: modules forming the generation hot path: the decode loop's
#: step/admit/retire must resolve every dispatch from pre-compiled
#: executables (trace rule) and the ONLY per-token host sync is the
#: sampled-token fetch (sync rule)
GENERATION_MODULES = [
    "deeplearning4j_tpu/generation/server.py",
    "deeplearning4j_tpu/generation/decode.py",
    "deeplearning4j_tpu/generation/sampling.py",
    # paged-KV bookkeeping runs BETWEEN every pair of decode dispatches
    # (page allocation, prefix lookup, CoW planning, table build) — it
    # must stay pure host numpy/python: no trace, no device sync
    "deeplearning4j_tpu/generation/paging.py",
    "deeplearning4j_tpu/runtime/executables.py",
    # the int8 KV-cache codec runs INSIDE the decode step (quantize the
    # new K/V row, dequant-in-attention) — it must obey the same
    # no-trace / no-host-sync rules as the rest of the loop
    "deeplearning4j_tpu/quantize/kvcache.py",
    "deeplearning4j_tpu/quantize/core.py",
    # request-timeline appends ride the decode loop's delivery path —
    # they must stay INSIDE the declared _deliver_block/_fetch_tokens
    # sync boundary: pure host bookkeeping, no device materialization,
    # no trace. The walker descends into event()/finish() to prove it.
    "deeplearning4j_tpu/monitoring/requests.py",
]
#: decode-loop entry points (GenerationServer hot methods) PLUS the
#: crash-replay/supervised-restart path: re-admission and the key
#: advance must also resolve entirely from the warmed executable set
#: (the supervisor promises restarts with ZERO live compiles). The
#: superstep pipeline's dispatch/deliver pair and the drafting
#: proposal/verify path are decode-loop steady state too.
GENERATION_ROOTS = {"_dispatch_block", "_deliver_block",
                    "_superstep_args", "_propose_drafts",
                    "_admit_pending", "_admit_one",
                    "_admit_rec", "_retire_slot", "_deliver",
                    "_survive", "_recover", "_replay_one",
                    "_advance_key", "_supervised_restart",
                    # paged-KV hot path: per-block page prep and the
                    # allocator's admission/eviction/prefix machinery
                    # resolve from pre-compiled executables only
                    "_page_args", "admit_slot", "ensure_range",
                    "evict_cold", "release_slot", "build_table"}
#: the declared warmup boundary — steady state never crosses it
GENERATION_MISS_BOUNDARY = {"load_or_compile", "warmup",
                            "_warmup_locked"}
#: per-superstep sync rule: only the declared fetch boundary may touch
#: device values — `_fetch_tokens` (the blocking materialization) and
#: `_start_fetch` (the non-blocking copy_to_host_async initiation that
#: overlaps the next dispatch). `_deliver`/`_push` are roots too: the
#: crash-replay journal append (the delivered-token list) must stay on
#: the existing `_fetch_tokens` host boundary — no extra syncs; the
#: drafting proposal must stay pure host numpy.
GENERATION_SYNC_ROOTS = {"_dispatch_block", "_deliver_block",
                         "_superstep_args", "_propose_drafts",
                         "_deliver", "_push",
                         # retirement closes the request timeline
                         # (trace.event/finish) — walked so the close
                         # path stays host-pure too
                         "_retire_slot", "_finish", "_fail",
                         # paged-KV page prep rides the dispatch
                         # boundary: allocation, prefix lookup, CoW
                         # planning, table build, and the pool metrics
                         # emit must add ZERO host syncs per token
                         "_page_args", "_emit_page_metrics",
                         "admit_slot", "abort_admit", "ensure_range",
                         "evict_cold", "release_slot", "build_table"}
GENERATION_SYNC_BOUNDARY = {"_fetch_tokens", "_start_fetch"}
#: calls that mean "the host blocks on (or copies back) device data"
SYNC_CALL_NAMES = {"asarray", "device_get", "block_until_ready",
                   "item", "tolist", "copy_to_host_async"}

# -- fleet-router hot-path lint --------------------------------------------
#: the fleet router's route / dispatch / relay / failover walk runs on
#: EVERY request (and every mid-stream failover): it must stay pure
#: host bookkeeping — no trace, no device sync. Linted on fleet.py
#: alone: the replica servers it drives are covered by the generation
#: lint above, and `submit()` is deliberately NOT a root (prompt
#: normalization np.asarray lives there, exactly like the server's).
FLEET_MODULES = ["deeplearning4j_tpu/generation/fleet.py"]
#: per-request / per-failover entry points: replica selection, the
#: adopt-hook dispatch, the stream relay pump, the failover decision,
#: and the health/burn bookkeeping they lean on
FLEET_ROOTS = {"_route", "_dispatch", "_relay", "_failover",
               "_health", "_mark", "_retryable", "_finalize"}
#: the declared cold boundary — replica replacement (supervision) may
#: warm executables from the shared disk store; the routing walk never
#: crosses into it
FLEET_BOUNDARY = {"_supervise", "warmup"}

# -- training-exchange lint (accumulation scan + bucketed exchange) --------
#: modules forming the distributed train-step hot path: the in-step
#: accumulation scan, the bucket planner, and the bucketed
#: encode→pmean→decode exchange must perform NO host sync — one
#: dispatch per optimizer step, and the per-optimizer-step fetch
#: (encoder_stats / guardian _materialize / lazy score) stays the one
#: declared boundary
TRAIN_MODULES = [
    "deeplearning4j_tpu/parallel/sharded_trainer.py",
    "deeplearning4j_tpu/parallel/multihost.py",
    "deeplearning4j_tpu/parallel/buckets.py",
    "deeplearning4j_tpu/parallel/compression.py",
    "deeplearning4j_tpu/nn/accum.py",
]
#: per-optimizer-step entry points: the step builders (their traced
#: bodies), the accumulation core, the bucket planner (host-side but
#: must stay shape-metadata-only), and the dispatch hook
TRAIN_SYNC_ROOTS = {"make_step", "make_guarded_step", "_make_exchange",
                    "accumulate_grads", "accum_scan", "fit_batch",
                    "plan_buckets", "concat", "split",
                    # the sparse wire codec runs INSIDE the traced
                    # exchange — encode, size-prefixed decode rows and
                    # the chain-sum accumulate are explicit roots so a
                    # host sync in the wire path can never hide behind
                    # a renamed call site
                    "sparse_encode", "sparse_decode", "_decode_row",
                    "wire_caps"}
#: the declared host-fetch boundary — stats/score materialize at sync
#: cadence, never per optimizer step; the traversal stops there
TRAIN_SYNC_BOUNDARY = {"encoder_stats", "_materialize",
                       "materialize_score"}

# -- step-timeline publish lint (straggler plane) --------------------------
#: the per-host step-timeline publish hooks (monitoring/stragglers.py,
#: fed from the coordination sync point) must be pure host
#: serialization: walking the publish path from each group's roots must
#: reach NO device materialization. Groups are linted SEPARATELY
#: because the walker's call graph is by bare function name and
#: `publish` exists in coordination.py (the KV write), cluster.py, and
#: stragglers.py — one union graph would shadow two of the three.
TIMELINE_MODULE_GROUPS = [
    # membership.py rides group 1: `pending()` (the join/leave fold)
    # runs inside EVERY heartbeat build — the walker descends from
    # _sync_point into it and proves the fold stays KV reads + JSON,
    # never a device touch
    ["deeplearning4j_tpu/parallel/coordination.py",
     "deeplearning4j_tpu/parallel/membership.py"],
    ["deeplearning4j_tpu/monitoring/stragglers.py",
     "deeplearning4j_tpu/monitoring/steps.py"],
    ["deeplearning4j_tpu/monitoring/cluster.py"],
]
#: publish-path entry points present in the groups: the sync-point
#: cadence hook (coordination), the digest publishers
#: (stragglers/cluster), and the ring digest they serialize (steps)
TIMELINE_SYNC_ROOTS = {"_sync_point", "publish", "compact_summary"}
#: forensics reports materialize freely — they run on the failure
#: path, never at the publish cadence
TIMELINE_SYNC_BOUNDARY = {"_write_report"}

#: coordination-module aliases whose `.publish(self, ...)` is a
#: METRICS-plane publish (cluster metrics / step timelines) — each such
#: call must sit inside the enabled-guard. The coordinator's own
#: `self.publish(...)` (heartbeats, guardian verdicts) is control
#: plane: it runs whether or not monitoring is on, and is exempt.
METRICS_PUBLISH_ALIASES = {"_cluster", "_stragglers"}
METRICS_PUBLISH_MODULES = ["deeplearning4j_tpu/parallel/coordination.py"]

#: attribute calls that hit the registry
REGISTRY_ATTRS = {"counter", "gauge", "histogram"}
#: bare/attribute function names that resolve the registry
REGISTRY_FUNCS = {"get_registry"}

#: substrings that mark an `if` test (or early-return guard test) as the
#: enabled-guard: `_mon.enabled()`, `STATE.enabled`, a cached
#: `mon_on = _mon.enabled()`, or an armed-session check
GUARD_TOKENS = ("enabled", "STATE.", "mon_on", "ACTIVE")


def _is_registry_call(node):
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in REGISTRY_ATTRS:
        return f".{f.attr}(...)"
    name = None
    if isinstance(f, ast.Attribute):
        name = f.attr
    elif isinstance(f, ast.Name):
        name = f.id
    if name in REGISTRY_FUNCS:
        return f"{name}()"
    return None


def _test_is_guard(test):
    try:
        src = ast.unparse(test)
    except Exception:  # noqa: BLE001 — unparse of odd nodes
        return False
    return any(tok in src for tok in GUARD_TOKENS)


def _guarded(node, ancestors):
    """Inside an `if <enabled-ish>` block, or after an early-return
    `if not <enabled-ish>: return` in the enclosing function."""
    func = None
    for anc in reversed(ancestors):
        if isinstance(anc, ast.If) and _test_is_guard(anc.test):
            return True
        if func is None and isinstance(anc, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
            func = anc
    if func is not None:
        for stmt in ast.walk(func):
            if isinstance(stmt, ast.If) and _test_is_guard(stmt.test) \
                    and stmt.lineno < node.lineno \
                    and any(isinstance(s, (ast.Return, ast.Raise))
                            for s in stmt.body):
                return True
    return False


def check_source(source, path="<string>"):
    """[(path, lineno, description)] for unguarded registry calls."""
    tree = ast.parse(source, filename=path)
    violations = []

    def walk(node, ancestors):
        if isinstance(node, ast.Call):
            what = _is_registry_call(node)
            if what is not None and not _guarded(node, ancestors):
                violations.append(
                    (path, node.lineno,
                     f"{what} outside the enabled-guard fast path"))
        for child in ast.iter_child_nodes(node):
            walk(child, ancestors + [node])

    walk(tree, [])
    return violations


def check_file(path):
    with open(path) as f:
        return check_source(f.read(), path)


# -- serving steady-state lint (no trace/compile reachable from the
#    dispatch path) ---------------------------------------------------------
def _call_name(node):
    """Best-effort callee name of a Call: `f(...)` → f, `a.b.f(...)` →
    f. Good enough for an intra-repo method-name call graph."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _check_reachable(sources, roots, boundary, flag_names, describe):
    """Walk the union call graph (intra-repo, by function name) of
    every function/method defined in `sources`, starting from `roots`
    and NOT descending into `boundary`, and flag any call whose callee
    name is in `flag_names`. `describe(what, via)` renders the
    violation message. Matching is by bare callee name — a theoretical
    false positive (e.g. `"x".lower()`) is accepted over ever missing
    a real trace/sync on a hot path."""
    defs = {}        # name -> (path, FunctionDef)
    for path, source in sources.items():
        tree = ast.parse(source, filename=path)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, (path, node))
    violations = []
    seen = set()
    frontier = [r for r in roots if r in defs]
    while frontier:
        name = frontier.pop()
        if name in seen or name in boundary:
            continue
        seen.add(name)
        path, fn = defs[name]
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node)
            if callee in flag_names:
                f = node.func
                what = (f".{callee}(...)" if isinstance(f, ast.Attribute)
                        else f"{callee}(...)")
                violations.append(
                    (path, node.lineno, describe(what, name)))
            if callee in defs and callee not in seen \
                    and callee not in boundary:
                frontier.append(callee)
    return violations


def check_serving_steady_state(sources):
    """sources: {path: source}. Steady-state serving (post-`warmup()`)
    must resolve every dispatch from the in-memory executable tier — a
    `jax.jit`/`lower`/`compile` reachable from the dispatch path means
    a novel shape could trace ON the request path."""
    return _check_reachable(
        sources, SERVING_ROOTS, SERVING_MISS_BOUNDARY, TRACE_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the serving dispatch path (via "
            f"{via}) — steady state must stay inside the AOT "
            "executable cache"))


def check_generation_steady_state(sources):
    """The generation decode loop (step / admit / retire) must reach no
    jit/lower/trace call past the declared warmup boundary: admitting a
    new sequence into an in-flight batch, stepping it, and retiring a
    finished slot are all pre-compiled fixed-shape dispatches."""
    return _check_reachable(
        sources, GENERATION_ROOTS, GENERATION_MISS_BOUNDARY,
        TRACE_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the generation decode loop (via "
            f"{via}) — step/admit/retire must stay inside the warmed "
            "executable set"))


def check_training_host_sync(sources):
    """Zero host syncs on the distributed train-step path: the
    accumulation scan dispatches once per optimizer step, the bucket
    planner reads only leaf SHAPES, and the bucketed exchange stays
    device-resident end to end — the stats/score fetch
    (encoder_stats / guardian _materialize) is the only declared
    per-optimizer-step host boundary."""
    return _check_reachable(
        sources, TRAIN_SYNC_ROOTS, TRAIN_SYNC_BOUNDARY,
        SYNC_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the distributed train step (via "
            f"{via}) — the accumulation scan / bucketed exchange must "
            "not sync the host; encoder_stats is the declared "
            "boundary"))


def check_generation_host_sync(sources):
    """Zero per-token host syncs beyond the sampled-token fetch: the
    decode step's only device materialization is the declared
    `_fetch_tokens` boundary — everything else (caches, carries,
    positions, rng) stays device-resident and donated."""
    return _check_reachable(
        sources, GENERATION_SYNC_ROOTS, GENERATION_SYNC_BOUNDARY,
        SYNC_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the decode step (via {via}) — the "
            "sampled-token fetch (_fetch_tokens) is the only allowed "
            "per-token host sync"))


def check_fleet_trace_free(sources):
    """Zero traces/compiles on the fleet routing walk: routing reads
    health snapshots and hands a pre-built request to `adopt()` — a
    compile reachable from route/dispatch/relay/failover would hide an
    unbounded stall inside what must be a bounded re-route."""
    return _check_reachable(
        sources, FLEET_ROOTS, FLEET_BOUNDARY, TRACE_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the fleet routing walk (via {via})"
            " — replica replacement (_supervise) is the only place a "
            "warmup may happen, and it warms from the shared disk "
            "store"))


def check_fleet_host_sync(sources):
    """Zero device syncs on the fleet routing walk: the router is pure
    host plumbing between the client and the replica decode loops —
    token relaying moves already-fetched ints, never device values."""
    return _check_reachable(
        sources, FLEET_ROOTS, FLEET_BOUNDARY, SYNC_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the fleet routing walk (via {via})"
            " — the router must never touch device data; the replica's"
            " _fetch_tokens boundary already did"))


def check_timeline_host_sync(sources):
    """Zero host syncs on the step-timeline publish path: publishing a
    per-host digest is JSON over numbers the flight recorder already
    holds — a device materialization reachable from `publish` /
    `compact_summary` / `_sync_point` would turn the metrics plane
    into a hidden per-sync host sync."""
    return _check_reachable(
        sources, TIMELINE_SYNC_ROOTS, TIMELINE_SYNC_BOUNDARY,
        SYNC_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the step-timeline publish path "
            f"(via {via}) — publishing must stay pure host "
            "serialization, never a device touch"))


def check_metrics_publish_guarded(source, path="<string>"):
    """Every metrics-plane publish in the coordination module
    (`_cluster.publish(...)` / `_stragglers.publish(...)`) must sit
    inside the enabled-guard: with monitoring off the sync point pays
    one branch, not a KV write per sync."""
    tree = ast.parse(source, filename=path)
    violations = []

    def walk(node, ancestors):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "publish" \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in METRICS_PUBLISH_ALIASES \
                    and not _guarded(node, ancestors):
                violations.append(
                    (path, node.lineno,
                     f"{f.value.id}.publish(...) outside the "
                     "enabled-guard — the metrics/timeline planes must "
                     "cost one branch when monitoring is off"))
        for child in ast.iter_child_nodes(node):
            walk(child, ancestors + [node])

    walk(tree, [])
    return violations


# -- ops-event emission lint ------------------------------------------------
#: modules holding ops-event emission hooks (monitoring/events.py
#: `_events.emit(...)` call sites): every emit must sit inside the
#: enabled-guard — with monitoring off an event hook costs ONE branch,
#: never a lock + ring append. events.py itself stays out of
#: HOT_MODULES on purpose: it IS the guarded side, and its bundle()
#: crash path reads the registry unconditionally by design.
EVENT_HOOK_MODULES = [
    "deeplearning4j_tpu/resilience/guardian.py",
    "deeplearning4j_tpu/resilience/watchdog.py",
    "deeplearning4j_tpu/resilience/faults.py",
    "deeplearning4j_tpu/generation/server.py",
    "deeplearning4j_tpu/generation/fleet.py",
    "deeplearning4j_tpu/parallel/coordination.py",
    "deeplearning4j_tpu/parallel/membership.py",
    "deeplearning4j_tpu/parallel/multihost.py",
    "deeplearning4j_tpu/monitoring/slo.py",
]
#: the canonical import alias at every hook site
EVENT_EMIT_ALIASES = {"_events"}

#: the journal's own emit path (everything an `emit()` call can reach)
#: must stay pure host bookkeeping: no device touch, no trace. The
#: post-mortem side (`bundle`/`write_bundle`) is the declared boundary
#: — it runs on the failure path, never at emission cadence.
EVENT_JOURNAL_MODULES = ["deeplearning4j_tpu/monitoring/events.py"]
EVENT_EMIT_ROOTS = {"emit", "journal", "_correlate", "_sweep_quiet",
                    "_close", "_publish_locked", "snapshot",
                    "incidents", "absorb", "close"}
EVENT_EMIT_BOUNDARY = {"bundle", "write_bundle"}


def check_event_emit_guarded(source, path="<string>"):
    """Every ops-event emission hook (`_events.emit(...)`) must sit
    inside the enabled-guard: the event journal is monitoring-plane
    state, and a disabled run pays one branch per hook site, not a
    journal append per incident-adjacent code path."""
    tree = ast.parse(source, filename=path)
    violations = []

    def walk(node, ancestors):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "emit" \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in EVENT_EMIT_ALIASES \
                    and not _guarded(node, ancestors):
                violations.append(
                    (path, node.lineno,
                     f"{f.value.id}.emit(...) outside the "
                     "enabled-guard — ops-event hooks must cost one "
                     "branch when monitoring is off"))
        for child in ast.iter_child_nodes(node):
            walk(child, ancestors + [node])

    walk(tree, [])
    return violations


def check_event_emit_host_pure(sources):
    """The journal emit path (emit → correlate → sweep → publish) rides
    failure-adjacent hot paths (decode loop, sync point, train step) —
    walking it must reach NO device materialization and NO trace; the
    post-mortem bundle writer is the declared cold boundary."""
    return _check_reachable(
        sources, EVENT_EMIT_ROOTS, EVENT_EMIT_BOUNDARY,
        SYNC_CALL_NAMES | TRACE_CALL_NAMES,
        lambda what, via: (
            f"{what} reachable from the event-journal emit path (via "
            f"{via}) — emission must stay pure host bookkeeping; only "
            "bundle()/write_bundle() may do heavyweight work"))


def main(modules=None):
    violations = []
    for rel in modules or HOT_MODULES:
        path = os.path.join(REPO_ROOT, rel)
        if not os.path.exists(path):
            continue
        violations.extend(check_file(path))
    if modules is None:
        sources = {}
        for rel in SERVING_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    sources[path] = f.read()
        violations.extend(check_serving_steady_state(sources))
        gen_sources = {}
        for rel in GENERATION_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    gen_sources[path] = f.read()
        violations.extend(check_generation_steady_state(gen_sources))
        violations.extend(check_generation_host_sync(gen_sources))
        fleet_sources = {}
        for rel in FLEET_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    fleet_sources[path] = f.read()
        violations.extend(check_fleet_trace_free(fleet_sources))
        violations.extend(check_fleet_host_sync(fleet_sources))
        train_sources = {}
        for rel in TRAIN_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    train_sources[path] = f.read()
        violations.extend(check_training_host_sync(train_sources))
        for group in TIMELINE_MODULE_GROUPS:
            tl_sources = {}
            for rel in group:
                path = os.path.join(REPO_ROOT, rel)
                if os.path.exists(path):
                    with open(path) as f:
                        tl_sources[path] = f.read()
            violations.extend(check_timeline_host_sync(tl_sources))
        for rel in METRICS_PUBLISH_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    violations.extend(
                        check_metrics_publish_guarded(f.read(), path))
        for rel in EVENT_HOOK_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    violations.extend(
                        check_event_emit_guarded(f.read(), path))
        ev_sources = {}
        for rel in EVENT_JOURNAL_MODULES:
            path = os.path.join(REPO_ROOT, rel)
            if os.path.exists(path):
                with open(path) as f:
                    ev_sources[path] = f.read()
        violations.extend(check_event_emit_host_pure(ev_sources))
    for path, lineno, msg in violations:
        print(f"{os.path.relpath(path, REPO_ROOT)}:{lineno}: {msg}")
    if violations:
        print(f"\n{len(violations)} fast-path violation(s): wrap "
              "registry calls in `if _mon.enabled():` (or an early "
              "`if not STATE.enabled: return`) so the disabled path "
              "stays one branch, and keep traces/compiles behind the "
              "executable-store miss boundary (load_or_compile).")
    return violations


if __name__ == "__main__":
    sys.exit(1 if main(sys.argv[1:] or None) else 0)
