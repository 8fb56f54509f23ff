"""Published peaks of the chips this benchmark has run on, by JAX's
``device_kind``.  A device that is not here is an error, not a default: a
share of a guessed peak is not a measurement."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]


def roofline_share(flops, bytes_moved, seconds, peaks):
    """(share in %, which bound) of the least time the chip could take —
    the larger of flops over peak FLOP/s and bytes over peak bytes/s —
    in the time it took."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    bound = "compute" if by_compute >= by_memory else "memory"
    return 100.0 * max(by_compute, by_memory) / seconds, bound
