"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default.

The backtest kernels are float32 vector (VPU) work; the table's
operation peak is the chip's bfloat16 matrix-unit peak, the one number
Google publishes. A roofline share against it reads low by design: it
is a fixed yardstick for comparing one PR with the next, not a grade.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "ops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float | None:
    """Percent of the roofline: the least time the chip could take for
    ``ops`` operations and ``nbytes`` bytes, over the time measured.
    None when nothing was measured."""
    if seconds <= 0 or ops <= 0:
        return None
    p = peaks(device_kind)
    least = max(ops / p["ops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
