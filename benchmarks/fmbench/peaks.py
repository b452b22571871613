"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong peak is worse than none.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16 MXU peak
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add it with its published source"
        ) from None


def least_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` of HBM traffic, and which of the two bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "bytes" if t_bytes >= t_flops else "flops",
    }
