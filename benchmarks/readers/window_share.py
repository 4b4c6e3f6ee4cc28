"""Per-layer metrics of a decoder whose layers attend a sliding window or
everything (`AfmoeDecoder`): device time by scope path, and the bytes a
decode step needs and the operations a prompt's band needs
(harness/work_window.py, from the configuration's sizes, the program's
`window_*`, `global_*` and `moe_*` counters over the window, and the traced
admits' prompt lengths) against the chip's published HBM rate and bfloat16
peak (harness/peaks.py) and that time. A program without the counters, the
scopes or the spans gives None, and the metric is left out of the line."""
from __future__ import annotations

from benchmarks.harness import work_window
from benchmarks.harness.peaks import peaks
from benchmarks.readers import program_span, sparse_share, trace_reduce


def _per_step(ctx, counter):
    """A counter's change over the window (the driver's `window_delta`) a
    decode step of the window."""
    steps = (ctx["driver"].get("status_delta") or {}).get("steps")
    delta = ctx["driver"].get("window_delta") or {}
    if not steps or delta.get(counter) is None:
        return None
    return delta[counter] / steps


def _admit_flops(ctx):
    """The band's operations of the mean traced admit: its `serve.admit`
    span's `prompt_len`."""
    tr = program_span.find(ctx)
    if tr is None:
        return None
    lens = [sp[4]["prompt_len"] for sp in program_span.spans_in(
        tr, ctx["trace"]["span"], {"serve.admit"}) if "prompt_len" in sp[4]]
    if not lens:
        return None
    return sum(work_window.prefill_window_flops(ctx["config"], n)
               for n in lens) / len(lens)


def read(ctx, spec):
    key = spec["key"]
    config = ctx["config"]
    if key == "scopes_ms":
        return sparse_share.scopes_ms(ctx, spec)
    if ctx["trace"] is None:
        return None
    if key == "prefill_mfu":
        need = _admit_flops(ctx)
        ms = sparse_share.scopes_ms(ctx, spec)
        if not need or not ms:
            return None
        return 100.0 * need / peaks(ctx["device_kind"])["bf16_flops_per_s"] \
            / (ms / 1e3)
    if key == "rows_hbm_share":
        rows = _per_step(ctx, spec["counter"])
        if rows is None:
            return None
        need = work_window.rows_bytes(config, rows)
        ms = sparse_share.scopes_ms(ctx, spec)
    elif key == "step_hbm_share":
        counts = [_per_step(ctx, c) for c in (
            "window_rows_attended", "global_rows_attended",
            "moe_expert_reads")]
        if None in counts:
            return None
        need = work_window.decode_step_bytes(config, *counts)
        ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
    else:
        raise ValueError(f"window_share: unknown key {key!r}")
    if not need or not ms:
        return None
    return 100.0 * need / peaks(ctx["device_kind"])["hbm_bytes_per_s"] \
        / (ms / 1e3)
