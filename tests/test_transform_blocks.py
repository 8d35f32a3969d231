"""``ReduceResult.transform`` on both sides of ``TRANSFORM_BLOCK_BYTES``:
one ``(y - mean) @ v`` call up to it, row blocks through a reused buffer
above it, with the same arithmetic for every row."""

import numpy as np
import pytest

from repro.core.types import TRANSFORM_BLOCK_BYTES, ReduceResult, transform_blocks

R256 = TRANSFORM_BLOCK_BYTES // (4 * 256)  # rows of d = 256 in one piece


def _map(m, d, k, seed=0):
    """Offset rows (the centring matters) and a float32 map fit to them."""
    rng = np.random.default_rng(seed)
    y = (rng.standard_normal((m, d)) * 2.0 + 5.0).astype(np.float32)
    res = ReduceResult(
        v=rng.standard_normal((d, k)).astype(np.float32),
        mean=y[::7].mean(axis=0).astype(np.float32),
        k=k, tlb_estimate=1.0, satisfied=True, runtime_s=0.0,
    )
    return y, res


@pytest.mark.parametrize("m,d,k", [
    (300, 32, 4),
    (16637, 96, 12),  # ElectricDevices
    (1370, 2709, 12),  # HandOutlines
    (R256, 256, 12),  # exactly the budget
    (TRANSFORM_BLOCK_BYTES // (4 * 1024), 1024, 4),
])
def test_under_the_budget_is_one_call_bit_for_bit(m, d, k):
    y, res = _map(m, d, k)
    assert transform_blocks(y.shape) == 1
    assert np.array_equal(res.transform(y), (y - res.mean) @ res.v)


@pytest.mark.parametrize("m,d,k,blocks", [
    (R256 + 1, 256, 12, 2),
    (9236, 1024, 4, 3),  # StarLightCurves
    (2 * 1370 + 1, 2709, 12, 2),
])
def test_over_the_budget_is_blocked_within_float32_rounding(m, d, k, blocks):
    y, res = _map(m, d, k)
    assert transform_blocks(y.shape) == blocks
    out = res.transform(y)
    assert out.dtype == np.float32 and out.shape == (m, k)
    c64 = y.astype(np.float64) - res.mean.astype(np.float64)
    ref = c64 @ res.v.astype(np.float64)
    # a float32 centring and a d-term float32 dot product: the standard
    # bound (d + 1) u |y - mean| |v|, u the unit roundoff
    bound = (d + 1) * 2.0**-24 * (np.abs(c64) @ np.abs(res.v.astype(np.float64)))
    assert np.all(np.abs(out - ref) <= bound)


@pytest.mark.parametrize("m,d,k", [(1370, 96, 12), (9236, 1024, 4)])
def test_float64_callers_get_the_float32_bits(m, d, k):
    y, res = _map(m, d, k)
    assert np.array_equal(res.transform(y.astype(np.float64)), res.transform(y))


# The subscription path appends rows and extends the client's transformed
# rows by transform(suffix): the map must be row-wise bit for bit. numpy's
# matmul itself is not for calls of a few rows (BLAS takes other kernels
# there), so every part below holds hundreds of rows or more. `blocks` is
# the whole input's; its blocks are ceil(m / blocks) rows, the last one
# ending at m.
@pytest.mark.parametrize("m,d,k,blocks,i", [
    (R256, 256, 12, 1, R256 // 2),  # one call, split in halves
    (R256 + 1, 256, 12, 2, R256 // 2),  # blocks of R256 / 2 + 1 rows
    (R256 + 1, 256, 12, 2, R256 // 2 + 1),  # at the edge
    (R256 + 1, 256, 12, 2, R256 // 2 + 2),
    (3 * R256 + 5, 256, 12, 4, 3 * R256 // 4 + 1),  # blocks of 3 R256 / 4 + 2
    (3 * R256 + 5, 256, 12, 4, 3 * R256 // 4 + 2),  # at the edge
    (3 * R256 + 5, 256, 12, 4, 3 * R256 // 4 + 3),
    (3 * R256 + 5, 256, 12, 4, 9 * R256 // 4 + 3),  # start of the last block
    (3 * R256 + 5, 256, 12, 4, 3 * R256 // 2),  # both parts blocked
    (9236, 1024, 4, 3, 3078),  # blocks of 3079 rows
    (9236, 1024, 4, 3, 3079),
    (9236, 1024, 4, 3, 4618),
    (9236, 1024, 4, 3, 6157),  # start of the last block
    (2 * 1370 + 1, 2709, 12, 2, 1370),  # blocks of 1371 rows
    (2 * 1370 + 1, 2709, 12, 2, 1371),
])
def test_transform_is_row_wise_across_the_budget_and_block_edges(m, d, k, blocks, i):
    y, res = _map(m, d, k, seed=i)
    assert transform_blocks(y.shape) == blocks
    whole = res.transform(y)
    assert np.array_equal(whole[i:], res.transform(y[i:]))
    assert np.array_equal(whole[:i], res.transform(y[:i]))
