"""Pallas TPU kernels for the LSH hot spots (validated via interpret=True).

hash_mm      -- fused p-stable hash: floor((X @ A)/r + b), optional proj out
simhash_pack -- fused matmul + sign + 32-bit pack
dct_mm       -- DCT-as-matmul Chebyshev embedding (MXU, no FFT)
rerank       -- masked L^p re-ranking of pre-gathered candidates
fused_query  -- gather + masked L^p + top-k: 128 candidates a grid step
                from rows held in VMEM, or one DMA'd row tile a step past
                VMEM (the (nq, C, N) candidate tensor never touches HBM)
dispatch     -- lazy backend selection + per-shape block sizes
ops          -- public wrappers (dispatch-routed); ref -- pure-jnp oracles
"""
from . import dispatch, ops, ref
