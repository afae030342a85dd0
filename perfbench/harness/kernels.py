"""Operations and bytes a kernel's work needs, from its shapes."""

from __future__ import annotations


def bitplane_cost(segments: float, k: int, n: int) -> tuple[float, float]:
    """(int8 operations, HBM bytes) of GF(256) recovery by the bitplane
    method for `segments` segments of k survivor rows of n bytes: per
    segment an (8k x 8k) 0/1 matrix times the (8k x n) bit-planes of the
    survivors, a multiply and an add per term; the bytes are the
    survivors read and the k recovered rows written, one byte each.
    Padding a kernel adds for its own layout is not work the recovery
    needs, so it is not counted."""
    ops = 2.0 * segments * (8 * k) * (8 * k) * n
    nbytes = 2.0 * segments * k * n
    return ops, nbytes


def roofline_floor(ops: float, nbytes: float, peaks: dict,
                   op_peak: str) -> tuple[float, str]:
    """(least seconds the chip could take, which bound sets it)."""
    t_ops = ops / peaks[op_peak]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
