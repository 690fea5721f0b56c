"""Jit'd public wrapper for the entropy+NLL kernel: pads to tile multiples,
runs the Pallas kernel (interpret mode on CPU only), slices back."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat

from . import ref
from .entropy_scores import NEG_BIG, entropy_nll_pallas


@partial(jax.jit, static_argnames=("block_b", "block_v", "use_pallas"))
def entropy_nll(logits, labels, *, block_b: int = 8, block_v: int = 2048,
                use_pallas: bool = True):
    """logits: (B, V); labels: (B,). Returns (entropy, nll) fp32 (B,)."""
    if not use_pallas:
        return ref.entropy_nll(logits, labels)
    b, v = logits.shape
    bb = min(block_b, max(b, 1))
    bv = min(block_v, max(v, 128))
    pad_b = (-b) % bb
    pad_v = (-v) % bv
    lp = jnp.pad(logits, ((0, pad_b), (0, pad_v)), constant_values=NEG_BIG)
    lab = jnp.pad(labels.astype(jnp.int32), ((0, pad_b),))
    ent, nll = entropy_nll_pallas(lp, lab, block_b=bb, block_v=bv,
                                  interpret=jaxcompat.pallas_interpret())
    return ent[:b], nll[:b]
