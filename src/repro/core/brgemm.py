"""Core public API: the batch-reduce GEMM as the single building block.

Every matmul in this framework routes through this module — layers never
call ``jnp.dot`` directly for their compute hot-spots.  See
``repro.kernels.brgemm`` for the Pallas kernel and the XLA-path reference,
and ``repro.core.dispatch`` for the backend registry, the ``repro.use``
execution context, and the resolution precedence.
"""
from repro.kernels.brgemm import (  # noqa: F401
    LayerWeight,
    batched_matmul,
    batched_matmul_q,
    brgemm,
    brgemm_q,
    matmul,
    matmul_q,
    resolve_backend,      # deprecated shim
    set_default_backend,  # deprecated shim
)
from repro.core.blocking import Blocks, choose_blocks  # noqa: F401
