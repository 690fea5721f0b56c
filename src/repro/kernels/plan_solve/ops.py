"""Dispatch wrapper for the fused plan-solve reduction.

``enum_solve``/``dp_solve`` are traceable (call them inside ``jax.jit``):
the combo tables and one-hot expansion matrices are static constants
baked into the program. The Pallas kernel path covers the heavy joint
enumeration (compiled on TPU, interpret mode on CPU — correctness only);
the default elsewhere is the pure-jnp reference, which XLA fuses into
the surrounding solver program. The cheap DP reduction always runs as
jnp.

Float policy: the reduction runs in whatever dtype the term tensors
carry — float64 under ``jaxcompat.enable_x64`` (the
oracle-matching CPU path), float32 on TPU where Pallas has no f64
(documented in the README; plans then match the NumPy oracle within
float32 tolerance, not ulps).
"""
from __future__ import annotations

import functools
import itertools

import jax.numpy as jnp
import numpy as np

from repro.core import jaxcompat

from . import ref
from .plan_solve import plan_solve_pallas


@functools.lru_cache(maxsize=None)
def monotone_combos(c: int, j: int) -> np.ndarray:
    """(G, J) int64 — monotone index tuples over a C-candidate grid, in
    ``itertools.combinations_with_replacement`` (lexicographic) order —
    the host enum solver's tuple order, so argmin precedence agrees."""
    return np.asarray(
        list(itertools.combinations_with_replacement(range(c), j)),
        np.int64).reshape(-1, j)


@functools.lru_cache(maxsize=None)
def _onehots(c: int, j: int, gp: int, dtype_name: str) -> np.ndarray:
    combos = monotone_combos(c, j)
    g = combos.shape[0]
    oh = np.zeros((j, c, gp), dtype_name)
    for jj in range(j):
        oh[jj, combos[:, jj], np.arange(g)] = 1.0
    return oh


def enum_solve(fs, consts, *, cand, kf=None, pair_caps=None, alpha=None,
               rhs=None, atol=None, masks=None, use_pallas: bool = False,
               block_m: int = 8):
    """Joint masked argmin over one subset run — see ``ref.enum_solve``
    for the contract. ``masks`` (length-J list of (M, S, C) bool or
    None) is only consumed by the Pallas path; the jnp reference
    expects per-candidate masks pre-folded into ``fs`` as +inf (the
    host solver's convention — the Pallas MXU path needs finite terms
    because masked values would turn the one-hot matmul into inf·0).
    Returns (val (M,), s_idx (M,), sel (M, J))."""
    m, s, j_steps, c = fs.shape
    if not use_pallas:
        return ref.enum_solve(fs, consts, monotone_combos(c, j_steps),
                              cand=cand, kf=kf, pair_caps=pair_caps,
                              alpha=alpha, rhs=rhs, atol=atol)
    combos = monotone_combos(c, j_steps)
    g = combos.shape[0]
    gp = -(-g // 128) * 128
    dtype = fs.dtype
    mp = -(-m // block_m) * block_m
    pad_m = mp - m

    def _pad(x):
        return jnp.pad(x, ((0, pad_m),) + ((0, 0),) * (x.ndim - 1))

    masked = (masks is not None or pair_caps is not None
              or alpha is not None)
    mask_grid = jnp.ones((m, s, j_steps, c), dtype)
    if masks is not None:
        mask_grid = jnp.stack(
            [jnp.ones((m, s, c), dtype) if mk is None else mk.astype(dtype)
             for mk in masks], axis=2)
    lb_grid = jnp.zeros((m, s, max(j_steps - 1, 1), c), dtype)
    if pair_caps is not None:
        lbs = []
        for j in range(1, j_steps):
            cap_m = pair_caps[j - 1]
            lbs.append(jnp.zeros((m, s, c), dtype) if cap_m is None
                       else ref.pair_lb_law(cand, cap_m[:, :, None],
                                            kf[:, None, None]))
        lb_grid = jnp.stack(lbs, axis=2)
    dl_grid = jnp.zeros((m, s, j_steps, c), dtype)
    if alpha is not None:
        dl_grid = cand[:, :, None, :] * alpha[:, :, :, None]
        rb = jnp.stack([rhs, atol], axis=2)
    else:
        rb = jnp.stack([jnp.full((m, s), jnp.inf, dtype),
                        jnp.zeros((m, s), dtype)], axis=2)
    const_arr = jnp.stack([jnp.asarray(cc, dtype) for cc in consts], axis=2)
    onehot = jnp.asarray(_onehots(c, j_steps, gp, np.dtype(dtype).name))
    val, idx = plan_solve_pallas(
        _pad(fs), _pad(const_arr), _pad(cand), _pad(mask_grid),
        _pad(lb_grid), _pad(dl_grid), _pad(rb), onehot, g_real=g,
        masked=masked, block_m=block_m,
        interpret=jaxcompat.pallas_interpret())
    val, idx = val[:m], idx[:m]
    s_idx = idx // g
    sel = jnp.asarray(combos, jnp.int32)[idx % g]
    return val, s_idx, sel
