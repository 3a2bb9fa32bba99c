"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s).  A device that is not
in the table is an error, never a default: a roofline share against a
guessed peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peak table row of one chip kind; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}")
