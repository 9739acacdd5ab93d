"""What the ALGORITHM needs to score one job, from shapes only — the
numerator of ``score_roofline_pct``.  Not what the implementation does: no
scratch zero-init, no padding, no re-extraction for ``store_results``.

bytes:  every resident peak slot read once (quantized m/z, pixel index and
        intensity as the configuration's ``cube_dtype`` stores it), and each
        ion image (ions x isotope peaks x pixels x 4 B) written once and read
        once by the metrics.
ops:    one add per peak slot (a slot lands in at most a window or two); per
        image pixel the moments (sum, square, product with the principal
        image: 5); per principal-image pixel one compare per chaos level and
        one label update; the correlations and the pattern match are O(K)
        per ion and vanish beside these.
"""

from __future__ import annotations

import json
from pathlib import Path

CUBE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def job_bytes(n_peaks: int, n_ions: int, k: int, pixels: int,
              cube_dtype: str) -> float:
    slot = 4 + 4 + CUBE_BYTES[cube_dtype]
    return float(n_peaks * slot + 2 * n_ions * k * pixels * 4)


def job_ops(n_peaks: int, n_ions: int, k: int, pixels: int,
            nlevels: int) -> float:
    return float(n_peaks + 5 * n_ions * k * pixels
                 + 2 * nlevels * n_ions * pixels)


def peaks_for(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table["devices"][device_kind]


def least_seconds(device_kind: str, n_bytes: float, n_ops: float
                  ) -> tuple[float, str]:
    """(least time one chip could take, which bound sets it)."""
    peak = peaks_for(device_kind)
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["f32_vector_ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
