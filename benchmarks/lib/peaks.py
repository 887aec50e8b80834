"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
architecture page (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
at 819 GB/s).  A device that is not in the table is an error, not a
default: a roofline share against a guessed peak is no number at all.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to benchmarks/lib/peaks.py with its source"
                       ) from None
