"""Per-layer metrics that set the work an algorithm needs (harness/work.py)
against the chip's published peak (harness/peaks.py)."""
from __future__ import annotations

from benchmarks.harness import work
from benchmarks.harness.peaks import peaks
from benchmarks.readers import trace_reduce


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None:
        return None
    peak = peaks(ctx["device_kind"])
    key = spec["key"]
    if key == "mfu":
        # steps a second on the device, from the step program's starts in
        # the traced span, times the FLOPs the model needs per step
        p = trace_reduce.dominant(tr)
        if p is None or p["count"] < 2 or p["last"] <= p["first"]:
            return None
        samples_per_s = (p["count"] - 1) * ctx["driver"]["batch"] \
            / (p["last"] - p["first"])
        return 100.0 * samples_per_s * ctx["built"].train_flops_per_sample() \
            / (peak["bf16_flops_per_s"] * tr["chips"])
    if key == "decode_hbm_share":
        step_ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
        rows = ctx["driver"].get("mean_rows_in_use")
        if step_ms is None or rows is None:
            return None
        need = work.bert_decode_step_bytes(
            ctx["config"]["model"], rows, int(ctx["workload"]["clients"]))
        return 100.0 * need / peak["hbm_bytes_per_s"] / (step_ms / 1e3)
    raise ValueError(f"work_share: unknown key {key!r}")
