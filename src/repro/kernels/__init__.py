"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package contains:
  <name>.py — the pl.pallas_call kernel with explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper (tiling, the XLA top-k, the route)
  ref.py    — the pure-jnp oracle the kernel is validated against

`route.py` records which route each call took — compiled on a TPU,
interpret mode elsewhere, or the XLA ref where a shape gate says no.

Kernels:
  mips_topk       — streaming tiled inner-product scoring for the flat
                    scan (the Θ(m) baseline, HBM-bandwidth-bound); XLA
                    ranks the scores: plain / absolute / complement-
                    augmented
  ivf_probe       — scalar-prefetched IVF probe: streams only the probed
                    cells' rows HBM→VMEM (never materializing the gathered
                    candidate matrix) and amortizes the stream across a
                    serve wave of probes via a dedup + MXU-batched variant
  mwu_update      — fused multiplicative-weights update + online softmax stats
  mwem_step       — the iteration megakernel: measure → MWU → renormalize →
                    accumulate in one VMEM-resident pass over the wave's
                    lanes, each winner row DMA'd straight from the query
                    table, plus the gather-score kernels that fetch the
                    lazy-EM tail candidates once (DESIGN.md §7)
  flash_attention — GQA flash attention (full/causal/window/chunk masking)
  ssd_scan        — Mamba-2 SSD chunked state-passing scan
"""
