"""Published peaks and the compulsory bytes of one search launch.

Peaks are keyed by JAX's ``device_kind``; a device not in the table is an
error, never a default.  TPU v5e: Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect.

The search compares int32 ids on the vector unit; no published peak covers
those compares, so its roofline is the memory bound alone.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_bw": 50e9},
}

INT32 = 4
MASK = 1  # one bool per result slot


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def search_bytes(rows: int, k: int, m0: int, mo: int) -> int:
    """Compulsory HBM bytes of one launch at its packed shape.

    Whatever kernel runs it, a launch of ``rows`` work items must read the
    first (shortest) list once, ids, parent ids and descendant counts
    (``m0`` each), read the other ``k - 1`` lists once, ids and descendant
    counts (``mo`` each), read each list's valid length, and write ``m0``
    result ids and a mask.
    """
    first = 3 * m0 * INT32
    others = (k - 1) * 2 * mo * INT32
    lengths = k * INT32
    out = m0 * (INT32 + MASK)
    return rows * (first + others + lengths + out)
