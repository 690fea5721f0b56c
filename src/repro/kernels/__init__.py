# Compute hot-spots of the paper's pipeline, as Pallas TPU kernels
# (pl.pallas_call + BlockSpec VMEM tiling). Each has a ref.py oracle; on
# TPU the kernels compile (tests/test_tpu_compile.py compiles the fleet
# path's kernels for a described v5e chip), on CPU they run in interpret
# mode (repro.core.jaxcompat.pallas_interpret):
#   entropy_scores — fused interestingness scoring (entropy+NLL over vocab tiles)
#   topk_filter    — streaming reservoir threshold scan (Fig. 2/3 inner loop)
#   batched_topk   — 2-D (stream, tile) threshold scan for the multi-tenant
#                     fleet engine in repro.streams
#   logmem_update  — fused ids-aware admission scan for the O(log K)
#                     logmem engine backend (streams.logmem)
#   tier_assign    — finalize-time (M, T) tier assignment of survivor
#                     payloads against per-stream boundary vectors
#   plan_solve     — fused masked-objective + joint-argmin reduction for
#                     the device-resident constrained planner (shp_jax)
#   flash_attention — fused attention (removes the S² HBM score traffic
#                     identified as the dominant train-cell roofline term)
from . import (batched_topk, entropy_scores, flash_attention, logmem_update,  # noqa: F401
               plan_solve, tier_assign, topk_filter)
