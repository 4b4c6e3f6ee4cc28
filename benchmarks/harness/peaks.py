"""Published peaks of the chips the benchmark knows, keyed by the
`device_kind` jax reports. A kind that is not here is an error, never a
default: a share of an unknown peak means nothing."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
#: HBM at 819 GB/s, per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmarks/harness/peaks.py with its source") from None
