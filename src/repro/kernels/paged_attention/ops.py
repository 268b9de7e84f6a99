"""Paged decode attention entry point with backend dispatch.

The Pallas backend resolves its block through the flash-attention
schema with a ``PagedAttnGeometry``: ``block_k`` (clamped to the page
size for pages of a lane tile or more) sets how many pages one
online-softmax step reads.  The XLA backend is the gathering oracle.
"""
from __future__ import annotations

from repro.core import dispatch
from repro.core.blocking import AttnBlocks, PagedAttnGeometry
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.sharding import local as _local


@dispatch.register("paged_attention", "pallas",
                   available=dispatch.pallas_available, priority=10)
def _paged_pallas_backend(q, k_pages, v_pages, page_tables, lengths, k_new,
                          v_new, layer, *, scale, blocks):
    if _local.kernel_mesh(dispatch.current_context().mesh) is not None:
        # the pool's sharding is the partitioner's; the oracle lets it
        # split the work, a Mosaic kernel could not be split
        return paged_attention_ref(q, k_pages, v_pages, page_tables,
                                   lengths, k_new, v_new, layer,
                                   scale=scale)
    n_table = page_tables.shape[1]
    page_size = k_pages.shape[3]
    blk = dispatch.resolve_blocks(
        "flash_attention", q.shape[1] // k_pages.shape[2],
        n_table * page_size, q.shape[2], q.dtype, backend="pallas",
        blocks=blocks,
        geometry=PagedAttnGeometry(page_size=page_size, pages=n_table))
    per_block = min(n_table, max(1, blk.block_k // page_size))
    return paged_attention_pallas(
        q, k_pages, v_pages, page_tables, lengths, k_new, v_new, layer,
        scale=scale, pages_per_block=per_block,
        interpret=dispatch.resolve_interpret())


@dispatch.register("paged_attention", "xla")
def _paged_xla_backend(q, k_pages, v_pages, page_tables, lengths, k_new,
                       v_new, layer, *, scale, blocks):
    del blocks  # no tiling on this path
    return paged_attention_ref(q, k_pages, v_pages, page_tables, lengths,
                               k_new, v_new, layer, scale=scale)


def paged_attention(q, k_pages, v_pages, page_tables, lengths, k_new,
                    v_new, *, layer=0, scale: float | None = None,
                    backend: str | None = None,
                    blocks: AttnBlocks | None = None):
    """Decode attention of one token per slot against a paged KV pool.

    ``q``: (S, Hq, d); ``k_pages``/``v_pages``: the layer-stacked pool
    leaves (L, n_pages, Hkv, page_size, d), read at ``layer`` (a traced
    int is fine); ``page_tables``: (S, P) int32 page ids in position
    order, padded with the sentinel ``n_pages``; ``lengths``: (S,) int32,
    the keys each slot holds in the pool before this token; ``k_new``,
    ``v_new``: (S, Hkv, d), the token's own key and value, attended
    besides the pool's (the pool is only read).  Returns (S, Hq, d).
    """
    impl = dispatch.get_impl("paged_attention", backend)
    return impl(q, k_pages, v_pages, page_tables, lengths, k_new, v_new,
                layer, scale=scale, blocks=blocks)
