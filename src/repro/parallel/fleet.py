"""Fleet-axis sharding: the M (stream) dimension laid out across devices.

The paper's tiering laws are per-stream, so every hot-path array in the
repo — reservoir state, drift-detector statistics, planner inputs — is
embarrassingly parallel along its leading M axis. This module owns the
one mesh axis that exploits that: a 1-D ``Mesh`` over the local devices
(``FLEET_AXIS``), ``NamedSharding`` helpers that split leading-axis rows
across it, a thread-local *active fleet mesh* (mirroring ``ctx``'s model
mesh so the planner entry points can pick the sharded dispatch up
ambiently), and the one genuinely cross-shard computation the stack
needs: fleet-shared capacity water-filling, whose water level λ couples
every stream and is found here by a ``psum`` bisection inside
``shard_map`` instead of a single-host sort.

Everything else stays collective-free: a ``shard_map`` of the engine
step / planner solve runs the exact single-device program on each
shard's rows, so sharded outputs are bit-identical to the single-device
run (tests assert this at every fleet size, divisible by the shard
count or not — padding rows are inert by construction).

On CPU-only boxes a multi-device mesh must be *forced* before jax
import: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import jaxcompat

FLEET_AXIS = "fleet"
_STATE = threading.local()


# ---------------------------------------------------------------------------
# Mesh construction + the thread-local active fleet mesh
# ---------------------------------------------------------------------------

def fleet_mesh(devices: Optional[int] = None) -> Optional[Mesh]:
    """A 1-D ``(FLEET_AXIS,)`` mesh over ``devices`` local devices (all of
    them when None). Returns ``None`` when fewer than 2 devices are
    available (or requested) — the callers then keep their single-device
    fallback paths (host thread fan-out, plain jit)."""
    avail = jax.local_device_count()
    d = avail if devices is None else int(devices)
    if d > avail:
        raise ValueError(
            f"fleet mesh needs {d} devices, only {avail} available — on "
            "CPU force them with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<d> before "
            "jax import")
    if d < 2:
        return None
    return jax.make_mesh((d,), (FLEET_AXIS,))


def n_shards(mesh: Optional[Mesh]) -> int:
    """Fleet-axis size of ``mesh`` (1 for None)."""
    if mesh is None:
        return 1
    return int(mesh.shape[FLEET_AXIS])


def set_fleet_mesh(mesh: Optional[Mesh]) -> None:
    _STATE.mesh = mesh


def get_fleet_mesh() -> Optional[Mesh]:
    """The thread-local active fleet mesh (None = single-device paths).
    ``core.shp_jax`` and ``online.replan_device`` consult this to pick
    the per-shard dispatch without any signature plumbing."""
    return getattr(_STATE, "mesh", None)


class use_fleet_mesh:
    """``with use_fleet_mesh(mesh): ...`` — scoped active fleet mesh."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        self.prev = get_fleet_mesh()
        set_fleet_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_fleet_mesh(self.prev)


# ---------------------------------------------------------------------------
# Row (leading-M-axis) sharding helpers
# ---------------------------------------------------------------------------

def row_spec() -> P:
    """Partition spec splitting the leading axis across the fleet (all
    trailing axes replicated) — valid for any rank."""
    return P(FLEET_AXIS)


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, row_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_rows(m: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= m (>= shards, so every shard
    owns at least one row)."""
    return max(-(-int(m) // shards), 1) * shards


def shard_rows(mesh: Optional[Mesh], tree):
    """``device_put`` every array leaf of ``tree`` with its leading axis
    split across the fleet (identity without a mesh). Leading dims must
    be multiples of the shard count — pad with inert rows first."""
    if mesh is None:
        return tree
    sh = row_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


# ---------------------------------------------------------------------------
# Cross-shard fleet-shared capacity water-filling
# ---------------------------------------------------------------------------

_WF_ITERS = 96  # f64 bisection: hi/2^96 is far below one ulp of λ


def _waterfill_local(d, budget):
    """Per-shard body: bisection on the scalar water level λ with the
    grant sum reduced across the fleet by ``psum`` each step. The loop
    keeps the invariant Σ min(d, lo) <= budget, so returning
    ``min(d, lo)`` can never oversubscribe the budget (up to the psum's
    own fp summation, ~1 ulp — the property test's tolerance)."""
    total = jax.lax.psum(d.sum(), FLEET_AXIS)
    # the TPU lowers only sum all-reduces in f64: gather the per-shard
    # maxima instead of a pmax
    hi0 = jnp.max(jax.lax.all_gather(
        jnp.max(d, initial=jnp.zeros((), d.dtype)), FLEET_AXIS))

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        s = jax.lax.psum(jnp.minimum(d, mid).sum(), FLEET_AXIS)
        ok = s <= budget
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, _WF_ITERS, body,
                              (jnp.zeros_like(hi0), hi0))
    grants = jnp.minimum(d, jnp.maximum(lo, 0.0))
    return jnp.where(total <= budget, d, grants)


_WF_CACHE: dict = {}


def _waterfill_fn(mesh: Mesh):
    fn = _WF_CACHE.get(mesh)
    if fn is None:
        fn = _WF_CACHE[mesh] = jax.jit(jaxcompat.shard_map(
            _waterfill_local, mesh=mesh,
            in_specs=(row_spec(), P()), out_specs=row_spec()))
    return fn


def waterfill_sharded(desired, budget: float, mesh: Mesh) -> np.ndarray:
    """Device-resident ``streams.planner.waterfill`` for a sharded fleet:
    each stream's desired occupancy stays on its own shard and the common
    water level λ (Σ min(desired, λ) = budget) is found by a 96-step f64
    bisection whose grant sums cross the mesh via ``psum`` — the
    single-host sort/prefix-scan view of the fleet never materializes.

    Returns the (M,) grants, matching the exact host λ to well below one
    ulp (bisecting from below guarantees the fleet never oversubscribes
    ``budget``; when the desires already fit they are granted verbatim).
    """
    d = np.asarray(desired, np.float64).reshape(-1)
    m = d.shape[0]
    shards = n_shards(mesh)
    mp = pad_rows(m, shards)
    dp = np.zeros(mp, np.float64)
    dp[:m] = d  # zero-desire pad rows draw no grant at any λ
    with jaxcompat.enable_x64():
        out = _waterfill_fn(mesh)(
            jax.device_put(dp, row_sharding(mesh)),
            jax.device_put(jnp.asarray(float(budget), jnp.float64),
                           replicated(mesh)))
        res = np.asarray(out, np.float64)
    return res[:m]
