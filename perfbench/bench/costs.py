"""Operations and bytes a kernel's work needs, from its real sizes.

These are the algorithm's counts, not the padded work a program happens to
do: a program that pads or recomputes gets no credit for it.
"""

from __future__ import annotations


def knn_scan(m: int, k: int) -> tuple[float, float]:
    """Exact kNN over ``m`` reduced rows of width ``k`` (float32): the
    cross-product term of every pair's squared distance (2k operations a
    pair), reading the rows once and writing one index and one distance a
    row."""
    return 2.0 * m * m * k, 4.0 * m * k + 8.0 * m


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline bound: the larger of compute time and memory time at the
    chip's published peaks."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
