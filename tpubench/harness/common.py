"""What every traffic kind (``kinds/<kind>.py``) shares: the outcome of a
run that the runner reads, the seeded sample of answers to compare, and
the chunk clock."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np


@dataclass
class Outcome:
    setup_s: float
    window_s: float
    latencies_s: List[float]
    attempted: int
    failed: int
    docs: int = 0
    shapes: Dict = field(default_factory=dict)
    late_s: float = 0.0  # how long the source held up work
    inputs: Dict = field(default_factory=dict)
    got: Dict = field(default_factory=dict)


def block(eng):
    import jax
    jax.block_until_ready(eng.states())


def sample(g, n_all: int, n: int, prefer=None) -> np.ndarray:
    """``n`` distinct indices of ``n_all``, half of them from ``prefer``
    where it has them."""
    n = min(n, n_all)
    if prefer is None or not len(prefer):
        return np.sort(g.choice(n_all, n, replace=False))
    first = g.choice(prefer, min(len(prefer), n // 2), replace=False)
    rest = np.setdiff1d(np.arange(n_all), first)
    return np.sort(np.concatenate(
        [first, g.choice(rest, n - first.size, replace=False)]))


class ChunkClock:
    """Chunk-boundary hook (the engine's checkpointer slot): stamps the
    moment a chunk's device state is ready and its meter record written."""

    def __init__(self):
        self.done: List[float] = []

    def on_chunk(self, eng):
        block(eng)
        self.done.append(time.perf_counter())
