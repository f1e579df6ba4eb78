"""Peaks of the devices the benchmark may run on, keyed by JAX's
`device_kind`. A device that is not here is an error, never a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip",
        # The scheduling kernels work in int32 and float32 on the vector unit,
        # not on the MXU. No published VPU peak exists; the roofline's compute
        # leg uses the bf16 MXU peak, which can only make the share smaller.
    },
}


def for_device(kind: str) -> Dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(
            f"benchmark/peaks.py has no peaks for device kind {kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
