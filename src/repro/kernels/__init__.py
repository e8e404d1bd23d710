# Pallas TPU kernels for the compute hot-spots PilotDB optimizes, plus the
# LM-stack hot paths.  Each subpackage: kernel.py (pl.pallas_call + BlockSpec
# VMEM tiling), ops.py (jit'd public wrapper), ref.py (pure-jnp oracle).
#
#   block_agg    — gather *sampled* blocks (scalar-prefetch ids) and emit
#                  per-block (count, sum, sumsq, min, max): the BSAP pilot /
#                  final scan hot path.  Non-sampled blocks never leave HBM.
#   filtered_agg — fused Q6-style predicate evaluation + block aggregation.
#   exact_agg    — the same shape over EVERY block (an exact answer):
#                  contiguous groups of blocks per grid step, columns in
#                  their own dtypes, no block-id table.
#   flash_attn   — blockwise-softmax attention for prefill.
#   gla_chunk    — chunked gated-linear-attention (RWKV6 / SSM hot path).
