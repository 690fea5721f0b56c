"""Fused masked-objective + joint-argmin reduction for the device-resident
constrained N-tier planner (``core.shp_jax``)."""
from .ops import enum_solve, monotone_combos  # noqa: F401
