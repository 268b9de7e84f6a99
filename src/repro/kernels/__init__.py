"""Pallas TPU kernels: batch-reduce GEMM (the paper's building block),
direct convolution, flash attention and paged decode attention — each
with kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper +
custom VJP where it has one), and ref.py (pure-jnp oracle).

Importing this package registers every op's backends in the
``repro.core.dispatch`` registry (the ops modules self-register at import
time); ``dispatch`` imports it lazily on first resolution.
"""
from repro.kernels.brgemm.ops import (  # noqa: F401
    batched_matmul,
    brgemm,
    matmul,
)
from repro.kernels.conv2d.ops import conv2d  # noqa: F401
from repro.kernels.flash_attention.ops import (  # noqa: F401
    flash_attention,
    flash_attention_bwd,
)
from repro.kernels.paged_attention.ops import paged_attention  # noqa: F401
