"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. One table, with its source; a kind that is not
here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per chip
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDeviceError(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"with its source to benchmarks/lib/peaks.py") from None
