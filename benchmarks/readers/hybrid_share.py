"""Per-layer metrics of a Nemotron-H hybrid's decode step: the bytes a
step must move (harness/work_hybrid.py, from the configuration's sizes and
the program's `moe_*` counters over the window) against the chip's
published HBM rate (harness/peaks.py) and the device time the trace gives
the scope or the program. A program without the counters or the scopes
gives None, and the metric is left out of the line."""
from __future__ import annotations

from benchmarks.harness import work_hybrid
from benchmarks.harness.peaks import peaks
from benchmarks.readers import program_span, trace_reduce


def scope_ms(ctx, spec):
    """Device time of `spec["scope"]` an execution of `spec["program"]`,
    like `program_span`'s `scope_ms`, plus the operations outside every
    scope whose names start with one of `spec["unscoped_ops"]`: XLA
    rewrites `lax.ragged_dot` into custom calls (`ragged-dot-none.<n>`,
    `ragged-dot-metadata`) that carry no scope path (read on the chip, PR
    28: `layer*/moe/experts` held 0.6 ms of a step, the ten unscoped
    `ragged-dot-none` calls 25.5), and the expert layers' are the
    program's only ragged products. The name match is part of this
    metric's yardstick: a kernel that keeps its scope path (a Pallas
    grouped product under `moe/experts` does) is counted by its scope and
    needs no name here; one that XLA rewrites out of its scope under
    ANOTHER name would leave `serve.moe_ms`, which then falls short of
    `serve.decode_step_ms` less the other scopes (PERF.md, section 5,
    keeps that sum)."""
    tr = program_span.find(ctx)
    if tr is None:
        return None
    runs = program_span.executions(tr, ctx["trace"]["span"],
                                   spec["program"])
    if not runs:
        return None
    table = program_span.by_scope(tr, runs)
    heads = tuple(spec.get("unscoped_ops", ()))
    seconds = program_span.scope_seconds(table, spec["scope"]) + sum(
        t for (parts, own), t in table.items()
        if not parts and own and own.startswith(heads))
    return 1e3 * seconds / len(runs) if seconds else None


def _per_step(ctx, counter):
    """A `moe_*` counter's change over the window (the driver's
    `moe_delta`) a decode step of the window."""
    steps = (ctx["driver"].get("status_delta") or {}).get("steps")
    delta = ctx["driver"].get("moe_delta") or {}
    if not steps or delta.get(counter) is None:
        return None
    return delta[counter] / steps


def read(ctx, spec):
    key = spec["key"]
    config = ctx["config"]
    if key == "scope_ms":
        return scope_ms(ctx, spec)
    if key == "load_max_over_mean":
        # the fullest expert's pairs over the mean expert's, layers and
        # steps summed on both sides
        pairs, fullest = _per_step(ctx, "moe_pairs"), \
            _per_step(ctx, "moe_pairs_max")
        if not pairs or fullest is None:
            return None
        return fullest * config["n_routed_experts"] / pairs
    if key == "kernel_roofline":
        # what the decode kernel READS, used or not: every slot's whole
        # rung of K and V (one rung: which one ran is then no question)
        rungs = config["serving"]["cache_lengths"]
        ms = scope_ms(ctx, spec)
        if not ms or len(rungs) != 1:
            return None
        need = int(config["serving"]["slots"]) * rungs[0] \
            * work_hybrid.kv_bytes_per_position(config)
        return 100.0 * need / peaks(
            ctx["device_kind"])["hbm_bytes_per_s"] / (ms / 1e3)
    reads = _per_step(ctx, "moe_expert_reads")
    if reads is None or ctx["trace"] is None:
        return None
    slots = int(config["serving"]["slots"])
    if key == "step_hbm_share":
        ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
        rows = ctx["driver"].get("mean_rows_in_use")
        if rows is None:
            return None
        need = work_hybrid.decode_step_bytes(config, slots, rows, reads)
    elif key == "scope_hbm_share":
        ms = scope_ms(ctx, spec)
        if spec["scope"] == "moe":
            need = work_hybrid.moe_step_bytes(config, reads)
        else:
            need = work_hybrid.ssm_step_bytes(config, slots)
    else:
        raise ValueError(f"hybrid_share: unknown key {key!r}")
    if not ms:
        return None
    return 100.0 * need / peaks(ctx["device_kind"])["hbm_bytes_per_s"] \
        / (ms / 1e3)
