"""Published peaks of each accelerator the benchmark may run on.

Keyed by `jax.Device.device_kind`.  Source: Google Cloud documentation,
"TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip.  Only published peaks are listed; no
f32 peak is derived from them, so a share of these can never read over
100% for a kernel whose work is counted right.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None
