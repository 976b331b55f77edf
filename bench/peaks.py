"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Copied from the program's ``launch/hlo_analysis.PEAKS`` so that no later
change to the program can move the yardstick.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM.
A device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,     # bf16 FLOP/s per chip
        "hbm_bw": 819e9,          # bytes/s
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
