"""The table of peaks and the bytes and operations each codec program needs.

Peaks are the published ones of the chip, keyed by ``device_kind`` as JAX
reports it.  A kind that is not in the table is an error, never a default.
The costs count what the algorithm needs for the useful bytes (padding rows of
a coalesced batch are the program's own cost and count for nothing), so a
share of the roofline computed from them cannot pass 100 %.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
    # 16 GB HBM at 819 GB/s
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
    },
}
DIGEST = 32  # bytes of bitrot digest per shard block


def least_seconds(device_kind: str, nbytes: float, nops: float) -> float:
    """The larger of bytes over the memory peak and operations over the int8
    peak: the least time the chip could take."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to roofline.PEAKS")
    p = PEAKS[device_kind]
    return max(nbytes / p["hbm_bytes_per_s"], nops / p["int8_ops_per_s"])


def encode_cost(counted: float, k: int, m: int, lost: int) -> "tuple[float, float]":
    """PUT: ``counted`` payload bytes in k data rows are read, m parity rows
    are written; each parity byte is k multiply-adds in GF(2^8)."""
    return counted * (1 + m / k), 2.0 * counted * m


def digest_cost(counted: float, k: int, m: int, lost: int) -> "tuple[float, float]":
    """Healthy GET: the rows are read once and hashed (a multiply-add a byte)."""
    return counted, 2.0 * counted


def reconstruct_cost(counted: float, k: int, m: int, lost: int) -> "tuple[float, float]":
    """Degraded GET: the seam counts all n rows of a stripe; k are read and
    the lost data rows (lost * k / n of them on average) are written, each
    byte of them k multiply-adds."""
    n = k + m
    payload = counted * k / n
    rows_lost = lost * k / n
    return payload * (1 + rows_lost / k), 2.0 * payload * rows_lost
