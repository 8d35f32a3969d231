"""Collections at the UCR archive's shapes, generated from the run's seed.

After the sinusoid-mixture generator the DROP paper uses for its
scalability experiment (§4.3: linear combinations of sinusoids with random
amplitude and phase, so the intrinsic dimensionality stays fixed), kept with
the benchmark so the yardstick's inputs cannot change under a later PR. The
rows are z-normalized per series, as the archive's are. It departs from
§4.3 in its class amplitudes, which are placed, not drawn (below): that
steadies the work from seed to seed, and it keeps every tenant off the TLB
edge, so DROP's adaptive path (more TLB rounds, a k chosen near the target,
refits) does not run on this data. A tenant at the edge needs a cell of its
own.

A ``SinusoidSource`` fixes one collection's population (frequencies, phases,
class means); ``rows`` draws i.i.d. series from it. The population of a
tenant is the same in every run; the run's seed draws its rows. How close
a tenant's TLB curve comes to the target decides how many pairs each
revalidation needs, and a curve within sampling error of it makes
revalidation pass or fail with the rows drawn (a failed one refits, and the
refit lands on the same edge): a population drawn from the seed, or a
spectrum that falls off gradually, moved the work from seed to seed. So
the components are near orthogonal and of equal energy, and the ``rank``
+ 1 classes sit at the vertices of a regular simplex, in equal numbers:
the class means' covariance is isotropic in all ``rank`` directions. Every
direction then carries the same variance, TLB(rank - 1) lies near
sqrt((rank - 1) / rank), far under a target such as 0.98, and TLB(rank)
above 0.99: each tenant is served at k = rank, and its map clears the
target on the fewest pairs, whatever rows the seed draws.
"""

from __future__ import annotations

import numpy as np

NOISE = 0.05  # white-noise amplitude relative to unit class amplitudes
POPULATION_SEED = 0  # the tenants' populations, the same in every run
MAX_CYCLES = 24  # sinusoids of 1 to 24 whole cycles over a series


class SinusoidSource:
    """Series of length ``d`` drawn from a rank-``rank`` sinusoid mixture."""

    def __init__(self, d: int, rank: int, rng: np.random.Generator) -> None:
        t = np.linspace(0.0, 1.0, d)
        # distinct whole numbers of cycles, so the components are near
        # orthogonal and each carries the same energy
        freqs = rng.choice(np.arange(1, MAX_CYCLES + 1), size=rank, replace=False)
        phases = rng.uniform(0.0, 2 * np.pi, size=rank)
        self.basis = np.sin(
            2 * np.pi * freqs[:, None] * t[None, :] + phases[:, None]
        ).astype(np.float32)
        # class means (nearest neighbours mean something): rank + 1 vertices
        # of a centred regular simplex at a random rotation, the rows of an
        # orthonormal basis of the complement of the all-ones vector, so
        # their covariance over the classes is isotropic; scaled to unit
        # amplitude per component
        a = rng.normal(size=(rank + 1, rank))
        q = np.linalg.qr(a - a.mean(0))[0]
        self.class_means = (q / np.sqrt((q**2).mean(0))).astype(np.float32)
        self.d, self.rank = d, rank

    def rows(self, m: int, rng: np.random.Generator) -> np.ndarray:
        # every class in equal numbers, as far as m allows
        labels = rng.permutation(np.arange(m) % len(self.class_means))
        amps = self.class_means[labels] + 0.3 * rng.standard_normal(
            (m, self.rank), dtype=np.float32
        )
        x = amps @ self.basis
        x += NOISE * rng.standard_normal((m, self.d), dtype=np.float32)
        x -= x.mean(axis=1, keepdims=True)
        x /= x.std(axis=1, keepdims=True) + np.float32(1e-8)
        return np.ascontiguousarray(x, dtype=np.float32)


def collection_rng(seed: int, *stream: int) -> np.random.Generator:
    """One independent stream per (run seed, purpose, index...): seeds of any
    size, and no stream shared between two purposes."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def make_collection(spec: dict, seed: int, index: int) -> tuple[SinusoidSource, np.ndarray]:
    """The ``index``-th tenant of collection ``spec`` for run ``seed``: its
    fixed population, and rows drawn from the seed."""
    src = SinusoidSource(spec["d"], spec["rank"], collection_rng(POPULATION_SEED, 0, spec["id"], index))
    return src, src.rows(spec["m"], collection_rng(seed, 1, spec["id"], index))
