"""Jit'd public wrapper: pads sequences to tile multiples (padded keys are
masked via the in-kernel position check), interpret mode on CPU only."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat

from . import ref
from .flash_attention import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "use_pallas"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    use_pallas: bool = True):
    if not use_pallas:
        return ref.flash_attention(q, k, v, causal=causal, window=window)
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 8))
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # padded q rows sit at positions >= skv: they attend nothing real but
    # the kernel masks padded KEYS by absolute position, so their outputs
    # are garbage and sliced off here.
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_k=bk,
                                 interpret=jaxcompat.pallas_interpret())
    return out[:, :sq]
