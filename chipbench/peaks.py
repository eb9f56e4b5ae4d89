"""Published peaks of each accelerator kind the benchmark runs on, keyed
by ``jax.Device.device_kind``. A kind missing here is an error: no
default, no CPU row, no override."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: "
                  "16 GB HBM2 at 819 GB/s)",
    },
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"add a row with its source to chipbench/peaks.py"
                       ) from None
