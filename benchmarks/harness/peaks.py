"""Peaks of one chip, keyed by `jax.devices()[0].device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture (197
TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A kind that is not in the
table raises: a share of an unknown peak is not a number."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud TPU v5e"},
}


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which peak sets it."""
    compute = flops / peaks["bf16_flops"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}; "
                       f"add it to benchmarks/harness/peaks.py with its "
                       f"source") from None
