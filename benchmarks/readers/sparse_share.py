"""Per-layer metrics of a sparse-attention decoder's decode step
(`KeyeDecoder`): device time by scope PATH (`indexer/score`: a part of a
path, where `program_span`'s `scope_ms` takes one name), and the bytes a
step must move (harness/work_sparse.py, from the configuration's sizes and
the program's `dsa_*` and `moe_*` counters over the window) against the
chip's published HBM rate (harness/peaks.py) and that time. A program
without the counters or the scopes gives None, and the metric is left out
of the line."""
from __future__ import annotations

from benchmarks.harness import work_sparse
from benchmarks.harness.peaks import peaks
from benchmarks.readers import program_span, trace_reduce


def _holds(parts, path):
    """Whether the scope parts of an operation hold `path` ('a/b': a
    directly above b)."""
    want = tuple(path.split("/"))
    return any(tuple(parts[i:i + len(want)]) == want
               for i in range(len(parts) - len(want) + 1))


def scopes_ms(ctx, spec):
    """Device time an execution of `spec["program"]` spends under any of
    the scope paths `spec["scopes"]`."""
    tr = program_span.find(ctx)
    if tr is None:
        return None
    runs = program_span.executions(tr, ctx["trace"]["span"],
                                   spec["program"])
    if not runs:
        return None
    seconds = sum(t for (parts, _), t in program_span.by_scope(
        tr, runs).items() if any(_holds(parts, p) for p in spec["scopes"]))
    return 1e3 * seconds / len(runs) if seconds else None


def _per_step(ctx, counter):
    """A counter's change over the window (the driver's `sparse_delta`) a
    decode step of the window."""
    steps = (ctx["driver"].get("status_delta") or {}).get("steps")
    delta = ctx["driver"].get("sparse_delta") or {}
    if not steps or delta.get(counter) is None:
        return None
    return delta[counter] / steps


def read(ctx, spec):
    key = spec["key"]
    config = ctx["config"]
    if key == "scopes_ms":
        return scopes_ms(ctx, spec)
    scored = _per_step(ctx, "dsa_rows_scored")
    kept = _per_step(ctx, "dsa_rows_selected")
    reads = _per_step(ctx, "moe_expert_reads")
    if scored is None or kept is None or reads is None:
        return None
    if key == "selected_share":
        return 100.0 * kept / scored if scored else None
    if ctx["trace"] is None:
        return None
    if key == "step_hbm_share":
        ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
        need = work_sparse.decode_step_bytes(config, scored, kept, reads)
    elif key == "scopes_hbm_share":
        ms = scopes_ms(ctx, spec)
        need = {"index_scan": work_sparse.index_scan_bytes(config, scored),
                "selected_rows": work_sparse.selected_row_bytes(config,
                                                                kept),
                "moe": work_sparse.moe_step_bytes(config, reads),
                }[spec["bytes"]]
    else:
        raise ValueError(f"sparse_share: unknown key {key!r}")
    if not ms:
        return None
    return 100.0 * need / peaks(ctx["device_kind"])["hbm_bytes_per_s"] \
        / (ms / 1e3)
