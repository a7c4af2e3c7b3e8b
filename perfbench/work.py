"""Work and bytes of a sparse product, and the chip's peaks.

The work is the product's useful work, the same whichever kernel does it:
C = A @ B with A an (m, k) operand holding ``nnz`` non-zeros and B a dense
(k, cols) right-hand side takes 2 * nnz * cols FLOPs. The least traffic
reads A's values and indices once at their stored widths, reads B once
and writes C once.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float
    bytes_per_s: float
    source: str


# One table, keyed by ``jax.Device.device_kind``. A device that is not here
# is an error: a share of an assumed peak is no measurement.
PEAKS = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12, bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"add it to perfbench/work.py PEAKS with its source")\
            from None


@dataclasses.dataclass(frozen=True)
class Operand:
    """What the counts need to know of the sparse operand A."""
    m: int
    k: int
    nnz: int
    value_bytes: int
    index_bytes: int


def flops(op: Operand, cols: int) -> float:
    return 2.0 * op.nnz * cols


def bytes_moved(op: Operand, cols: int, rhs_bytes: int = 4,
                out_bytes: int = 4) -> float:
    return float(op.nnz * (op.value_bytes + op.index_bytes)
                 + op.k * cols * rhs_bytes + op.m * cols * out_bytes)


def roofline_s(op: Operand, cols: int, peak: Peak) -> float:
    """Least time one product of ``cols`` columns can take on the chip:
    the larger of its FLOPs over peak FLOP/s and its bytes over peak
    bandwidth."""
    return max(flops(op, cols) / peak.flops_per_s,
               bytes_moved(op, cols) / peak.bytes_per_s)
