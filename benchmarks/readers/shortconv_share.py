"""Per-layer metrics of a decoder whose layers are gated short
convolutions or grouped-query attention, with routed experts
(`Lfm2MoeDecoder`): device time by scope path, and the bytes a decode step
needs and the operations a prompt's conv mixers and expert layers need
(harness/work_shortconv.py, from the configuration's sizes, the cell's
slots, the program's `global_rows_attended` and `moe_*` counters over the
window, and the traced admits' prompt lengths) against the chip's
published HBM rate and bfloat16 peak (harness/peaks.py) and that time. A
program without the counters, the scopes or the spans gives None, and the
metric is left out of the line."""
from __future__ import annotations

from benchmarks.harness import work_shortconv
from benchmarks.harness.peaks import peaks
from benchmarks.readers import program_span, sparse_share, trace_reduce


def _per_step(ctx, counter):
    """A counter's change over the window (the driver's `shortconv_delta`)
    a decode step of the window."""
    steps = (ctx["driver"].get("status_delta") or {}).get("steps")
    delta = ctx["driver"].get("shortconv_delta") or {}
    if not steps or delta.get(counter) is None:
        return None
    return delta[counter] / steps


def _admit_lens(ctx):
    """The traced admits' prompt lengths (their `serve.admit` spans)."""
    tr = program_span.find(ctx)
    if tr is None:
        return []
    return [sp[4]["prompt_len"] for sp in program_span.spans_in(
        tr, ctx["trace"]["span"], {"serve.admit"}) if "prompt_len" in sp[4]]


def read(ctx, spec):
    key = spec["key"]
    config = ctx["config"]
    if key == "scopes_ms":
        return sparse_share.scopes_ms(ctx, spec)
    if ctx["trace"] is None:
        return None
    slots = int(config["serving"]["slots"])
    if key in ("prefill_moe_mfu", "conv_prefill_roofline"):
        # the mean traced admit's least time at the chip's peaks, over the
        # scopes' device time an admit
        lens = _admit_lens(ctx)
        ms = sparse_share.scopes_ms(ctx, spec)
        if not lens or not ms:
            return None
        top = peaks(ctx["device_kind"])
        if key == "prefill_moe_mfu":
            least = [work_shortconv.prefill_moe_flops(config, n)
                     / top["bf16_flops_per_s"] for n in lens]
        else:
            least = [max(f / top["bf16_flops_per_s"],
                         b / top["hbm_bytes_per_s"]) for f, b in (
                work_shortconv.prefill_conv_work(config, n) for n in lens)]
        return 100.0 * sum(least) / len(least) / (ms / 1e3)
    if key == "moe_hbm_share":
        reads = _per_step(ctx, "moe_expert_reads")
        if reads is None:
            return None
        need = work_shortconv.moe_step_bytes(config, reads)
        ms = sparse_share.scopes_ms(ctx, spec)
    elif key == "step_hbm_share":
        rows, reads = (_per_step(ctx, c) for c in (
            "global_rows_attended", "moe_expert_reads"))
        if rows is None or reads is None:
            return None
        need = work_shortconv.decode_step_bytes(config, slots, rows, reads)
        ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
    else:
        raise ValueError(f"shortconv_share: unknown key {key!r}")
    if not need or not ms:
        return None
    return 100.0 * need / peaks(ctx["device_kind"])["hbm_bytes_per_s"] \
        / (ms / 1e3)
