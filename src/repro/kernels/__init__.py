"""Pallas TPU kernels for the compute hot spots.

  flash_attention -- fused streaming-softmax causal/GQA attention (LM prefill)
  segment_sum     -- sorted-edge blocked one-hot SpMM aggregation (GNN/recsys)
  bfs_relax       -- program-generic frontier relaxation (the paper's local
                     BFS); the one kernel both traversal engines run

Each package ships kernel.py (pl.pallas_call + BlockSpec), ops.py (the
callers' entries, with padding/block selection) and ref.py (pure-jnp
oracle).  On the CPU kernels are validated with interpret=True; BlockSpecs
target TPU VMEM tiling (MXU-aligned 128-lane blocks).
"""
