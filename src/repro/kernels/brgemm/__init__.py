from repro.kernels.brgemm.ops import (  # noqa: F401
    LayerWeight,
    batched_matmul,
    brgemm,
    matmul,
    resolve_backend,      # deprecated shim (see repro.core.dispatch)
    set_default_backend,  # deprecated shim (see repro.core.dispatch)
)
from repro.kernels.brgemm.quant import (  # noqa: F401
    batched_matmul_q,
    batched_matmul_q_ref,
    brgemm_q,
    brgemm_q_ref,
    matmul_q,
    matmul_q_ref,
)
