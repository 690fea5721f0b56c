"""Seeded randomness and the backlogged source every traffic kind uses.

Each generated item is a pure function of the seed and its index (the
routed mix also of the items before it: a tenant's positions continue),
so the reference regenerates what the window was fed. Documents come
from a producer thread a few items ahead of the engine — a backlogged
source — and NumPy's generators release the interpreter lock while they
fill, so the producer runs beside the host path it feeds.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

SEED_MASK = (1 << 64) - 1


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator of one named stream of the seed (seeds may be any int)."""
    return np.random.default_rng([int(seed) & SEED_MASK, *stream])


# ---- the backlog -------------------------------------------------------------

class Backlog:
    """Producer thread keeping ``depth`` items ready: ``make(i)`` for
    i = 0, 1, ... Items are handed out in order; ``close`` stops and joins
    the thread."""

    def __init__(self, make, depth: int = 2):
        self._make = make
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self.waited_s = 0.0  # time the consumer waited for an item
        self._t = threading.Thread(target=self._run, name="bench-source",
                                   daemon=True)
        self._t.start()

    def _run(self):
        i = 0
        while not self._stop.is_set():
            try:
                item = (i, self._make(i), None)
            except Exception as e:  # handed to the consumer, raised there
                item = (i, None, e)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if item[2] is not None:
                return
            i += 1

    def get(self):
        t0 = time.perf_counter()
        i, item, err = self._q.get()
        self.waited_s += time.perf_counter() - t0
        if err is not None:
            raise err
        return item

    def close(self):
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._t.join(timeout=30)
        if self._t.is_alive():
            raise RuntimeError("traffic producer thread did not stop")
