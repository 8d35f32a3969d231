"""Core dataclasses for the DROP optimizer (paper Table 1 notation)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Literal, Sequence

import numpy as np

# Default progressive sampling schedule from §4.1 of the paper: ten steps,
# data processed no more than ~2.4x in total.
DEFAULT_SCHEDULE: tuple[float, ...] = (
    0.01, 0.02, 0.03, 0.04, 0.05, 0.10, 0.20, 0.30, 0.65, 1.00,
)


@dataclass(frozen=True)
class DropConfig:
    """Inputs of Problem 3.1 plus implementation knobs.

    Attributes:
        target_tlb: B — TLB preservation level (paper default 0.98).
        confidence: c — confidence for the sampled TLB estimate (default 0.95).
        schedule: progressive sampling schedule (fractions of m).
        reuse_fraction: q/100 — bottom-percentile of points carried into the
            next sample (importance sampling / work reuse; paper default 0.10).
        svd: "halko" (paper's randomized PCA) or "full" (exact SVD).
        halko_oversample: p in Algorithm 3 (default 5).
        halko_power_iters: q in Algorithm 3 (default 1).
        search: "binary" (paper Algorithm 4) or "prefix" (TPU-native all-prefix
            TLB search — one fused pass instead of O(log d) evaluations).
        initial_pairs: starting pair count for the TLB CI loop (paper: 100).
        max_pairs: cap on TLB evaluation pairs (paper observes <=300 typical).
        use_kernels: route hot matmuls through the Pallas kernel wrappers.
        min_iterations: run at least this many iterations before the progress
            estimator may terminate (needs 2 points for a slope).
        seed: determinism.
    """

    target_tlb: float = 0.98
    confidence: float = 0.95
    schedule: Sequence[float] = DEFAULT_SCHEDULE
    reuse_fraction: float = 0.10
    svd: Literal["halko", "full"] = "halko"
    halko_oversample: int = 5
    halko_power_iters: int = 1
    search: Literal["binary", "prefix"] = "binary"
    initial_pairs: int = 100
    # the paper observes <=300 pairs suffice; the cap only binds when the CI
    # straddles the target at the boundary k (where more pairs cannot change
    # the decision materially but cost O(pairs x d x k) each)
    max_pairs: int = 800
    use_kernels: bool = False
    min_iterations: int = 2
    seed: int = 0


@dataclass
class IterationRecord:
    """Per-iteration telemetry (i, m_i, k_i, r_i, obj_i)."""

    i: int
    sample_size: int
    k: int
    tlb_estimate: float
    runtime_s: float
    objective: float
    satisfied: bool
    pairs_used: int


@dataclass
class ReduceResult:
    """Output of any ``Reducer`` — the paper's T_k as an explicit linear map.

    Every operator in the comparison (PCA, FFT, PAA, DWT, JL) is a linear
    transformation, so one representation serves them all: ``v`` is the
    (d, k) operator matrix and ``mean`` the centering offset (all-zero for
    the baselines, which do not center). This is what makes the serving
    stack method-agnostic: the TLB revalidation, the basis-reuse cache, and
    ``transform`` never need to know which method fitted the map.

    ``DropResult`` is the deprecated alias (the PCA-only era name).
    """

    v: np.ndarray  # (d, k) linear operator (PCA: basis columns)
    mean: np.ndarray  # (d,) centering offset (zeros for uncentered methods)
    k: int
    tlb_estimate: float
    satisfied: bool
    runtime_s: float
    iterations: list[IterationRecord] = field(default_factory=list)
    method: str = "pca"

    def transform(self, y: np.ndarray) -> np.ndarray:
        """Apply the learned transformation (Algorithm 1 TRANSFORM).

        Inputs are cast through float32 first: the map was fit in float32,
        and a float64 caller must see bit-identical outputs to a float32
        caller (served transforms are cached and compared across tenants).

        Inputs over ``TRANSFORM_BLOCK_BYTES`` are centred and projected in
        row blocks (``transform_blocks``), the same arithmetic for each
        row; smaller ones in one ``(y - mean) @ v``. The map is row-wise:
        the rows of ``transform(y[i:])`` are those of ``transform(y)[i:]``
        wherever numpy's own matmul is (the subscription path's appends
        rely on it).
        """
        y32 = np.asarray(y, dtype=np.float32)
        mean = np.asarray(self.mean, dtype=np.float32)
        v = np.asarray(self.v, dtype=np.float32)
        blocks = transform_blocks(y32.shape)
        if blocks == 1:
            return (y32 - mean) @ v
        # every block has the same row count, the last one ending at m (it
        # overlaps the one before by fewer than `blocks` rows): each block
        # is the same BLAS call, never a short remainder that BLAS would
        # round with another kernel
        m = y32.shape[0]
        rows = -(-m // blocks)
        out = np.empty((m, v.shape[1]), dtype=np.float32)
        buf = np.empty((rows, y32.shape[1]), dtype=np.float32)
        for b in range(blocks):
            s = min(b * rows, m - rows)
            np.subtract(y32[s : s + rows], mean, out=buf)
            np.matmul(buf, v, out=out[s : s + rows])
        return out

    @property
    def total_rows_processed(self) -> int:
        return sum(rec.sample_size for rec in self.iterations)


DropResult = ReduceResult  # deprecated alias (pre-Reducer API)

# The most float32 bytes ``ReduceResult.transform`` centres in one piece.
# Larger inputs are projected in row blocks through one reused buffer. A
# centred copy past glibc's 32-MiB cap on its mmap threshold is a fresh
# mapping that every call page-faults, zeroes and unmaps: on a TPU v5e host,
# 9236 x 1024 (37.8 MB, k = 4) took 44.5 ms in one piece and 9-10 ms in
# blocks of 8 to 19 MB. Smaller inputs (16637 x 96, 1370 x 2709) keep the
# one call, which blocking did not speed up.
TRANSFORM_BLOCK_BYTES = 16 << 20


def transform_blocks(shape: tuple[int, ...]) -> int:
    """Row blocks ``ReduceResult.transform`` projects an input of ``shape``
    in: 1 (one ``(y - mean) @ v`` call) up to ``TRANSFORM_BLOCK_BYTES`` of
    float32 rows, else the fewest equal blocks of about that size (none
    more than a row over it)."""
    if len(shape) != 2:
        return 1
    m, d = shape
    return max(1, -(-4 * m * d // TRANSFORM_BLOCK_BYTES))


CostFn = Callable[[int], float]
