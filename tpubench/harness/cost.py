"""Least work of a fleet step, from the cell's shapes alone: what any
implementation of the update has to move, whatever moves it."""
from __future__ import annotations

import json
import os

from .registry import BENCH_DIR

F32 = I32 = 4
MASK = 1  # one byte per slot of a boolean mask


def step_bytes(engine: str, m: int, k: int, w: int) -> int:
    """Bytes one step over ``m`` tenants with ``w`` slots each must move.

    exact: the (m, k) reservoir (f32 scores, i32 positions) and the (m,)
    counts read and written, the (m, w) chunk (f32 scores, i32 positions)
    read, the (m, w) write mask and the (m, k) evicted positions written.
    logmem: the chunk read and the write mask written (its O(log K) state
    per tenant is a rounding error beside them, and is left out so that a
    leaner state cannot push the share over 100%)."""
    chunk = m * w * (F32 + I32)
    if engine == "exact":
        reservoir = m * k * (F32 + I32) + m * I32
        return 2 * reservoir + chunk + m * w * MASK + m * k * I32
    if engine == "logmem":
        return chunk + m * w * MASK
    raise ValueError(f"unknown engine {engine!r}")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in the table is an error."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in peaks.json (have {sorted(table['devices'])})")
