"""The one place that adapts to the installed JAX.

* ``enable_x64`` — the scoped float64 context (``jax.enable_x64``).
* ``shard_map`` — ``jax.shard_map`` with the varying-manual-axes check
  off: every fleet program is row-independent, and the water-fill's
  ``psum`` results are replicated by construction.
* ``platform`` / ``pallas_interpret`` — the backend check every Pallas
  wrapper uses. Kernels run in interpret mode on ``cpu`` only; on
  ``tpu`` they compile or the call fails. Any other backend raises
  rather than silently picking a path.
* ``compile_cache`` — where entry points keep JAX's persistent compile
  cache.
"""
from __future__ import annotations

import os

import jax

PLATFORMS = ("cpu", "tpu")


def enable_x64(on: bool = True):
    """``with enable_x64(): ...`` — float64 inside the scope only."""
    return jax.enable_x64(bool(on))


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def platform() -> str:
    """The default backend's platform, ``"cpu"`` or ``"tpu"``."""
    p = jax.default_backend()
    if p not in PLATFORMS:
        raise RuntimeError(
            f"unsupported JAX backend {p!r}: Pallas kernels compile on tpu "
            "and run in interpret mode on cpu")
    return p


def pallas_interpret() -> bool:
    """True iff Pallas kernels must run in interpret mode (cpu)."""
    return platform() == "cpu"


def compile_cache(root: str) -> str:
    """Keep the persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says or, when it is unset, at the fixed ``<root>/.jax_cache`` (a
    directory that moves between runs never hits). Returns the path."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(os.path.abspath(root), ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
