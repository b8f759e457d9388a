"""Pallas TPU kernels for the framework's compute hot-spots.

  sketch           CountSketch detection symbol (O(k) BFT detection traffic)
  majority_vote    blockwise pairwise replica agreement (reactive 2f+1 vote)
  coded_encode     linear detection-code encode (generalized Fig-2 codes)
  gram             one-pass gram-plane precompute: G = R R^T and every
                   step's sketch table of the data rows
  flash_attention  fused blockwise attention forward (GQA, causal/window)

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling, a jit'd
wrapper in ops.py, and a pure-jnp oracle in ref.py; validated in
interpret=True mode on CPU, targeting TPU v5e.
"""
from repro.kernels import ops, ref  # noqa: F401
