"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``. A device that is not here is an error:
no environment variable and no default stands in for a data sheet.

Source for ``TPU v5 lite``: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s
per chip).
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"chipbench/peaks.py (have {sorted(PEAKS)}); add its data sheet, "
            "do not guess") from None
