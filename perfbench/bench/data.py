"""Collections at the UCR archive's shapes, generated from the run's seed.

A copy of the sinusoid-mixture generator the DROP paper uses for its
scalability experiment (§4.3: linear combinations of sinusoids with random
amplitude and phase, so the intrinsic dimensionality stays fixed), kept with
the benchmark so the yardstick's inputs cannot change under a later PR. The
rows are z-normalized per series, as the archive's are.

A ``SinusoidSource`` fixes one collection's population (frequencies, phases,
class means); ``rows`` draws i.i.d. series from it. The population of a
tenant is the same in every run; the run's seed draws its rows. How close
a tenant's TLB curve comes to the target decides how many pairs each
revalidation needs, and a curve within sampling error of it makes
revalidation pass or fail with the rows drawn (a failed one refits, and the
refit lands on the same edge): a population drawn from the seed, or one
whose spectrum falls off gradually, moved the work from seed to seed. So
the components are near orthogonal and of equal energy, and every seed
serves the same tenants with fresh rows.
"""

from __future__ import annotations

import numpy as np

NOISE = 0.05  # white-noise amplitude relative to unit class amplitudes
N_CLASSES = 4  # class-conditioned amplitudes, so nearest neighbours mean something
POPULATION_SEED = 0  # the tenants' populations, the same in every run
MAX_CYCLES = 24  # sinusoids of 1 to 24 whole cycles over a series


class SinusoidSource:
    """Series of length ``d`` drawn from a rank-``rank`` sinusoid mixture."""

    def __init__(self, d: int, rank: int, rng: np.random.Generator) -> None:
        t = np.linspace(0.0, 1.0, d)
        # distinct whole numbers of cycles, so the components are near
        # orthogonal, and class means scaled so each carries the same
        # energy: the spectrum then drops after ``rank`` components and no
        # TLB(k) sits within sampling error of a target such as 0.98
        freqs = rng.choice(np.arange(1, MAX_CYCLES + 1), size=rank, replace=False)
        phases = rng.uniform(0.0, 2 * np.pi, size=rank)
        self.basis = np.sin(
            2 * np.pi * freqs[:, None] * t[None, :] + phases[:, None]
        ).astype(np.float32)
        means = rng.normal(size=(N_CLASSES, rank))
        self.class_means = (means / np.sqrt((means**2).mean(0))).astype(np.float32)
        self.d, self.rank = d, rank

    def rows(self, m: int, rng: np.random.Generator) -> np.ndarray:
        labels = rng.integers(0, N_CLASSES, size=m)
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
