"""The chip's side of a kernel's roofline: the least time a call can
take. The work a step needs belongs to the architecture and is counted
by the configuration's reference module (`bench/reference/<name>.py`).
"""
from __future__ import annotations

from typing import Tuple


def mm_least_seconds(m: int, k: int, n: int, weight_bits: int,
                     peak_ops: float, bytes_per_s: float,
                     scale_groups: int = 1) -> Tuple[float, str]:
    """Least time of one fused dequant matmul call, and which bound sets
    it ('compute' or 'memory'): the larger of 2*M*K*N over the peak and
    the bytes the call must move over the bandwidth. The bytes are the
    stored weight (K*N*bits/8), its f32 scales (G*N*4), the f32 static
    activation scale, the f32 input (M*K*4) and the f32 output (M*N*4),
    as the kernel takes and returns them."""
    ops = 2 * m * k * n
    moved = (k * n * weight_bits // 8 + scale_groups * n * 4 + 4
             + m * k * 4 + m * n * 4)
    t_ops, t_mem = ops / peak_ops, moved / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
