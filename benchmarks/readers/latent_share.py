"""Per-layer metrics of a latent-attention decoder's decode step
(`MLADecoder`): device time by scope path, and the bytes and operations a
step needs (harness/work_latent.py, from the configuration's sizes and the
program's `mla_*` and `moe_*` counters over the window) against the chip's
published HBM rate and bfloat16 peak (harness/peaks.py) and that time. A
program without the counters or the scopes gives None, and the metric is
left out of the line."""
from __future__ import annotations

from benchmarks.harness import work_latent
from benchmarks.harness.peaks import peaks
from benchmarks.readers import sparse_share, trace_reduce


def _per_step(ctx, counter):
    """A counter's change over the window (the driver's `latent_delta`) a
    decode step of the window."""
    steps = (ctx["driver"].get("status_delta") or {}).get("steps")
    delta = ctx["driver"].get("latent_delta") or {}
    if not steps or delta.get(counter) is None:
        return None
    return delta[counter] / steps


def read(ctx, spec):
    key = spec["key"]
    config = ctx["config"]
    if key == "scopes_ms":
        return sparse_share.scopes_ms(ctx, spec)
    if key == "load_max_over_mean":
        # the fullest held expert's pairs over the mean held expert's,
        # layers and steps summed on both sides (`hybrid_share`'s rule)
        pairs, fullest = _per_step(ctx, "moe_pairs"), \
            _per_step(ctx, "moe_pairs_max")
        if not pairs or fullest is None:
            return None
        return fullest * config["n_routed_experts"] / pairs
    if key == "rows_read_over_attended":
        # the rows the decode kernel fetched (whole tiles) over the rows
        # in use: what its tile costs in bytes
        attended, fetched = _per_step(ctx, "mla_rows_attended"), \
            _per_step(ctx, "mla_rows_read")
        if not attended or fetched is None:
            return None
        return fetched / attended
    rows = _per_step(ctx, "mla_rows_attended")
    reads = _per_step(ctx, "moe_expert_reads")
    pairs = _per_step(ctx, "moe_pairs")
    if rows is None or reads is None or pairs is None \
            or ctx["trace"] is None:
        return None
    if key in ("step_hbm_share", "step_mfu"):
        ms = trace_reduce.read(
            ctx, {"key": "program_ms", "heaviest_without": ["admit"]})
        need = work_latent.decode_step_bytes(config, rows, reads) \
            if key == "step_hbm_share" \
            else work_latent.decode_step_flops(config, rows, pairs)
    elif key == "moe_hbm_share":
        ms = sparse_share.scopes_ms(ctx, spec)
        need = work_latent.moe_step_bytes(config, reads)
    elif key in ("rows_hbm_share", "rows_mfu"):
        ms = sparse_share.scopes_ms(ctx, spec)
        need = work_latent.latent_rows_bytes(config, rows) \
            if key == "rows_hbm_share" \
            else work_latent.latent_rows_flops(config, rows)
    else:
        raise ValueError(f"latent_share: unknown key {key!r}")
    if not ms:
        return None
    peak = peaks(ctx["device_kind"])[
        "hbm_bytes_per_s" if key.endswith("hbm_share")
        else "bf16_flops_per_s"]
    return 100.0 * need / peak / (ms / 1e3)
