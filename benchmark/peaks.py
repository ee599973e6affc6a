"""Published peaks of the chips the benchmark may run on, by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB (Google Cloud
documentation, "TPU v5e").  There is no published f32 VPU peak; the
kernels measured here are memory-bound and are held to the bandwidth.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind, what):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmark/peaks.py "
                       "with its source")
    return PEAKS[device_kind][what]
