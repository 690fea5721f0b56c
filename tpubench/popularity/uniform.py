"""Every tenant equally likely to send each document."""
import numpy as np


def draw(g: np.random.Generator, m: int, size: int) -> np.ndarray:
    """(size,) tenant ids in [0, m)."""
    return g.integers(0, m, size, dtype=np.int32)
