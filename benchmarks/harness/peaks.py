"""Published peaks of the chips the benchmark may run on, by the
`device_kind` string JAX reports.  A device that is not here is an error,
never a default: a roofline share against the wrong peak is worse than
none."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            "row with its source to benchmarks/harness/peaks.py") from None


def roofline_s(flops: float, nbytes: float, device_kind: str
               ) -> tuple[float, str]:
    """Least time the chip could take for `flops` operations over
    `nbytes` of HBM traffic, and which of the two bounds it."""
    p = peaks_for(device_kind)
    t_c = flops / p["bf16_flops"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
