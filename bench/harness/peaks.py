"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

"TPU v5 lite" is the TPU v5e: 16 GB of HBM at 819 GB/s per chip (Google
Cloud documentation, "TPU v5e"). A device missing here is an error, not a
default: a roofline share against a guessed peak would be meaningless.
"""
from __future__ import annotations

HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def hbm_bytes_per_s(device_kind: str) -> float:
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no HBM peak for device kind {device_kind!r}; add it, with its source, "
            f"to bench/harness/peaks.py (have {sorted(HBM_BYTES_PER_S)})"
        ) from None
