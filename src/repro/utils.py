"""Shared small utilities: timing, rng plumbing, tree helpers."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np


class Clock:
    """Wall-clock timer matching the paper's CLOCK.RESTART / CLOCK.ELAPSED."""

    def __init__(self) -> None:
        self._t0 = time.perf_counter()

    def restart(self) -> None:
        self._t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0


@dataclass
class RngStream:
    """Deterministic per-purpose numpy RNG fan-out from a single seed."""

    seed: int
    _streams: dict = field(default_factory=dict)

    def get(self, name: str) -> np.random.Generator:
        if name not in self._streams:
            # stable per-name child seed
            child = np.random.SeedSequence([self.seed, abs(hash(name)) % (2**31)])
            self._streams[name] = np.random.default_rng(child)
        return self._streams[name]


def tree_bytes(tree: Any) -> int:
    """Total bytes of all arrays (or ShapeDtypeStructs) in a pytree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_count(tree: Any) -> int:
    """Total parameter count of a pytree of arrays/structs."""
    return sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")
    )
