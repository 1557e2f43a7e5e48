"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's contribution *is* a compute-kernel optimization (early-stopped
dot products and truncated factor updates), so this package carries the
scoring kernels (pruned matmul, pruned top-k) and the training step's row
write (``row_write.py``), plus their jit wrappers (``ops.py``) and pure-jnp
oracles (``ref.py``).
"""
from repro.kernels.ops import (  # noqa: F401
    pruned_matmul,
    pruned_topk,
    tile_block_stats,
)
