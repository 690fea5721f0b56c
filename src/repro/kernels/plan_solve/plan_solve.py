"""Pallas TPU kernel: fused masked-objective evaluation + joint argmin for
the device-resident constrained boundary solve.

The batched planner reduces, per stream, a candidate grid of monotone
boundary tuples over S tier subsets to one winner: the feasible tuple of
minimum expected cost. Host-side this is the ``itertools`` enumeration in
``core.shp._solve_constrained_enum``; here the whole reduction is one
kernel pass.

Grid: (M/bm, S) — program (i, s) evaluates one stream block against one
subset. The wrapper lays every (M, S, ...) operand out subset-major —
(S, M, ·) and (S, J, M, C) — so each block's last two dimensions are
(bm, C)-style tiles: the stream rows and a full trailing axis. The
per-step term rows (bm, C) are expanded onto the G monotone tuples with
*static one-hot matmuls* (MXU-friendly: ``onehot[j]`` is the (C, Gp) 0/1
matrix with ``onehot[j][combos[g, j], g] = 1``), so the gather becomes a
dot product and the per-tuple sum accumulates in step order — the same
adds the jnp reference performs. Feasibility (per-step candidate masks,
pairwise lower bounds, the exact latency budget) is accumulated as an
infeasibility count and lifted to +inf after the sums. The s axis is
sequential and the (bm, 1) output blocks are revisited per subset (like
``tier_assign``'s per-tier counts): a running first-minimum-wins
min/argmin accumulates across subsets, emitting the joint (S·G) argmin
per stream in one pass, encoded ``s·G + g``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import ROWS_PARALLEL

_EXACT = jax.lax.Precision.HIGHEST  # one-hot "gathers" must not round


def _expand(x, onehot):
    """(bm, C) terms -> (bm, Gp) per-tuple values."""
    return jnp.dot(x, onehot, precision=_EXACT,
                   preferred_element_type=x.dtype)


def _kernel(fs_ref, const_ref, cand_ref, mask_ref, lb_ref, dl_ref, rb_ref,
            onehot_ref, val_ref, idx_ref, *, j_steps: int, g_real: int,
            masked: bool):
    s = pl.program_id(1)
    bm = fs_ref.shape[2]
    gp = onehot_ref.shape[2]
    dtype = fs_ref.dtype
    tot = jnp.zeros((bm, gp), dtype)
    for j in range(j_steps):
        tot = tot + _expand(fs_ref[0, j], onehot_ref[j])
    if masked:
        bad = jnp.zeros((bm, gp), dtype)
        for j in range(j_steps):
            bad = bad + _expand(1.0 - mask_ref[0, j], onehot_ref[j])
        for j in range(1, j_steps):
            prev = _expand(cand_ref[0], onehot_ref[j - 1])
            lbd = _expand(lb_ref[0, j - 1], onehot_ref[j])
            bad = bad + (prev < lbd * (1 - 1e-12) - 1e-12).astype(dtype)
        acc = jnp.zeros((bm, gp), dtype)
        for j in range(j_steps):
            acc = acc + _expand(dl_ref[0, j], onehot_ref[j])
        rb = rb_ref[0]  # (bm, 2): rhs, atol
        bad = bad + (acc > rb[:, 0:1] + rb[:, 1:2]).astype(dtype)
    for p in range(const_ref.shape[2]):
        tot = tot + const_ref[0, :, p:p + 1]
    gi = jax.lax.broadcasted_iota(jnp.int32, (bm, gp), 1)
    infeas = gi >= g_real
    if masked:
        infeas = infeas | (bad > 0)
    tot = jnp.where(infeas, jnp.inf, tot)
    # first-minimum-wins argmin as two lane reductions
    vmin = jnp.min(tot, axis=1, keepdims=True)
    amin = jnp.min(jnp.where(tot == vmin, gi, gp), axis=1, keepdims=True)
    enc = s * g_real + amin

    @pl.when(s == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    upd = vmin < val_ref[...]
    val_ref[...] = jnp.where(upd, vmin, val_ref[...])
    idx_ref[...] = jnp.where(upd, enc, idx_ref[...])


def plan_solve_pallas(fs, const, cand, mask, lb, deltas, rhs_atol, onehot,
                      *, g_real: int, masked: bool, block_m: int = 8,
                      interpret: bool = False):
    """fs (M, S, J, C); const (M, S, P); cand (M, S, C); mask (M, S, J, C)
    in {0, 1}; lb (M, S, max(J-1,1), C); deltas (M, S, J, C);
    rhs_atol (M, S, 2); onehot (J, C, Gp) with the last Gp − g_real
    columns zero (padding). M must be a multiple of ``block_m``.
    Returns (best (M,), idx (M,) int32 = s·G + g)."""
    m, s, j_steps, c = fs.shape
    assert m % block_m == 0, (m, block_m)

    def steps(x):  # (M, S, J, C) -> (S, J, M, C)
        return jnp.transpose(x, (1, 2, 0, 3))

    def rows(x):  # (M, S, P) -> (S, M, P)
        return jnp.transpose(x, (1, 0, 2))

    def step_spec(x):
        return pl.BlockSpec((1, x.shape[1], block_m, c),
                            lambda i, t: (t, 0, i, 0))

    def row_spec(x):
        return pl.BlockSpec((1, block_m, x.shape[2]),
                            lambda i, t: (t, i, 0))

    args = (steps(fs), rows(const), rows(cand), steps(mask), steps(lb),
            steps(deltas), rows(rhs_atol))
    val, idx = pl.pallas_call(
        functools.partial(_kernel, j_steps=j_steps, g_real=g_real,
                          masked=masked),
        grid=(m // block_m, s),
        in_specs=[step_spec(args[0]), row_spec(args[1]), row_spec(args[2]),
                  step_spec(args[3]), step_spec(args[4]),
                  step_spec(args[5]), row_spec(args[6]),
                  pl.BlockSpec(onehot.shape, lambda i, t: (0, 0, 0))],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((m, 1), fs.dtype),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ),
        compiler_params=ROWS_PARALLEL,
        interpret=interpret,
        name="plan_solve",
    )(*args, onehot)
    return val[:, 0], idx[:, 0]
