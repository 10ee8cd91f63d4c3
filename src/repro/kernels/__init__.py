"""Pallas TPU kernels for the substrate's compute hot spots.

The paper's contribution is in the communication layer (no custom compute
kernel of its own); these kernels cover the perf-critical compute the
assigned architectures need at the dry-run shapes (DESIGN.md §5):

  flash_attention/  fused streaming-softmax GQA attention (causal + local
                    window), BlockSpec-tiled for VMEM; plus the ragged
                    decode kernel (per-slot cache lengths via scalar
                    prefetch) — the TPU-target twin of the vector-index
                    ``attention_decode`` path continuous batching runs
  rglru/            RG-LRU gated linear recurrence, block-parallel scan

Each ships as kernel.py (pl.pallas_call + BlockSpec; TPU is the TARGET),
ops.py (jit'd wrapper; interpreted on the CPU, see mode.py), ref.py
(pure-jnp oracle for the allclose sweeps).
"""
