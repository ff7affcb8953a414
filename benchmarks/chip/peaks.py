"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error.

TPU v5e (JAX: "TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
at 819 GB/s, 1,600 Gbit/s interchip interconnect per chip; Google Cloud
documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e).

The distance kernels run their f32 matmuls at ``Precision.HIGHEST``, which
the MXU executes as about six bf16 passes, so their ceiling is about a
sixth of the bf16 peak: a roofline share above ~17% of the bf16 peak is not
reachable for an MXU-bound kernel at that precision.
"""
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_s": 1600e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
