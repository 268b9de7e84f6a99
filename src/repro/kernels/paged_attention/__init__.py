from repro.kernels.paged_attention.ops import paged_attention  # noqa: F401
