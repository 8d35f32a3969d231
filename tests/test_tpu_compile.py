"""The chip compiler accepts every Pallas kernel on the served path.

Each kernel is lowered and compiled for one chip of a described (not
attached) TPU v5e 2x2 topology at the widths DROP serves:
SynElectricDevices' 16637 rows reduced to k=12 for the pairwise kernels,
and 6400 pairs x d=1024 x kmax=128 for the TLB table. Interpret-mode parity
lives in test_kernels.py; what only a real compile catches is tiling and
lowering (a packed-word block that is not (8, 128)-tiled, a primitive with
no Mosaic rule).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

M_TRUE, M_PAD, K = 16637, 16640, 12
SHARDS, SHARD_ROWS = 4, 4224
P, D, KMAX = 6400, 1024, 128
FIT_ROWS = 9236  # SynStarLightCurves' rows, d=1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernel_cases():
    from repro.kernels.center_gram.center_gram import center_gram_pallas
    from repro.kernels.matmul.matmul import matmul_pallas
    from repro.kernels.pairwise_reduce import pairwise_reduce as pr
    from repro.kernels.pairwise_tlb.pairwise_tlb import pairwise_tlb_pallas

    q, x, xs = (M_PAD, K), (M_PAD, K), (SHARDS * SHARD_ROWS, K)
    return {
        "knn": lambda s: pr.pairwise_knn_pallas.lower(s(q), s(x), m=M_TRUE),
        "dbscan": lambda s: pr.pairwise_dbscan_pallas.lower(
            s(q), s(x), m=M_TRUE, eps2=0.25
        ),
        "kde": lambda s: pr.pairwise_kde_pallas.lower(
            s(q), s(x), m=M_TRUE, inv_two_h2=0.5
        ),
        "knn_split": lambda s: pr.pairwise_knn_split_pallas.lower(
            s(q), s(xs), m=M_TRUE, shards=SHARDS
        ),
        "dbscan_split": lambda s: pr.pairwise_dbscan_split_pallas.lower(
            s(q), s(xs), m=M_TRUE, eps2=0.25, shards=SHARDS
        ),
        "kde_split": lambda s: pr.pairwise_kde_split_pallas.lower(
            s(q), s(xs), m=M_TRUE, inv_two_h2=0.5, shards=SHARDS
        ),
        "tlb": lambda s: pairwise_tlb_pallas.lower(
            s((P, D)), s((P, D)), s((D, KMAX))
        ),
        "center_gram": lambda s: center_gram_pallas.lower(s((FIT_ROWS, D))),
        "matmul": lambda s: matmul_pallas.lower(s((FIT_ROWS, D)), s((D, 17))),
    }


@pytest.mark.parametrize(
    "kernel",
    [
        "knn", "dbscan", "kde", "knn_split", "dbscan_split", "kde_split",
        "tlb", "center_gram", "matmul",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = _kernel_cases()[kernel](spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
