"""Pallas TPU kernels for the paper's compute hot-spots.

Modules:
  dense_mm          — conventional tiled MXU matmul (the paper's dense baseline)
  bsr_spmm          — block-sparse x dense steered by prefix counters (InCRS idea)
  index_match_spmm  — round-synchronized Alg. 2 port (comparators -> one-hot VPU)
  incrs_gather      — counter-vector-driven column gather / decompression
  incrs_spmm        — FUSED InCRS SpMM: section-stripe expansion in
                      VMEM straight into MXU accumulation; the dense (M, K)
                      intermediate of gather->dense_mm never touches HBM
  flash_attention   — GQA flash attention (online softmax in VMEM scratch,
                      causal/window block skipping — the framework's hottest
                      kernel, streaming KV in rounds like the paper's mesh)
  ops               — public wrappers + host-side format prep
  ref               — pure-jnp oracles (tests assert allclose against these)
"""
