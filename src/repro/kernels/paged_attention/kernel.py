"""Paged decode attention as a batch-reduce over a page list.

One query token per slot attends to the keys its slot holds in a paged
KV pool.  The page table *is* the paper's address list: each slot's row
names the pages to reduce over, in position order, and each page is one
step of the online-softmax reduction, with the flash kernel's rescaling
epilogue between steps.

  * grid = (slots,); every KV head of a slot in one grid step, the GQA
    group's queries as the rows of each head's block,
  * the pool stays in HBM (``pl.ANY``): the kernel DMAs only the slot's
    live pages, ``ceil(length / page_size)`` of them, ``pages_per_block``
    a round, double buffered; sentinel ids and pages past the length are
    never read,
  * the token's own key/value row enters as the reduction's first
    element, so the pool is read-only here and the caller writes the
    row afterwards,
  * bf16 (or f32) pages, fp32 scores, statistics and accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import round_up, sublane

NEG_INF = -1e30
STATS_LANES = 128


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_block", "interpret"))
def paged_attention_pallas(q, k_pages, v_pages, page_tables, lengths,
                           k_new, v_new, layer, *, scale=None,
                           pages_per_block: int = 1,
                           interpret: bool = False):
    """q: (S, Hq, d); k_pages, v_pages: (L, n_pages, Hkv, d, page_size),
    pages stored key-major (positions on the lanes), read at ``layer``;
    page_tables: (S, P) int32; lengths: (S,) int32, the keys each slot
    holds in the pool; k_new, v_new: (S, Hkv, d), the token's own row.
    Returns (S, Hq, d) in q's dtype."""
    n_slots, hq, d = q.shape
    _, _, hkv, _, page_size = k_pages.shape
    n_table = page_tables.shape[1]
    group = hq // hkv
    rows = round_up(group, sublane(q.dtype))
    scale = scale if scale is not None else d ** -0.5

    qg = q.reshape(n_slots, hkv, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    kn = k_new.reshape(n_slots, hkv, 1, d).astype(q.dtype)
    vn = v_new.reshape(n_slots, hkv, 1, d).astype(q.dtype)

    def body(layer_ref, pt_ref, len_ref, q_ref, kn_ref, vn_ref, k_hbm,
             v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref):
        s = pl.program_id(0)
        length = len_ref[s]
        per_block = pages_per_block * page_size
        n_blocks = (length + per_block - 1) // per_block

        def live(i, j):
            """Whether page ``j`` of block ``i`` holds live keys."""
            return (i * pages_per_block + j) * page_size < length

        def each_copy(i, buf, act):
            """``act`` on the K and V copies of block ``i``'s live pages
            into buffer ``buf``."""
            for j in range(pages_per_block):
                @pl.when(live(i, j))
                def _():
                    page = pt_ref[s * n_table + i * pages_per_block + j]
                    for c, (hbm, vmem) in enumerate(((k_hbm, kbuf),
                                                     (v_hbm, vbuf))):
                        act(pltpu.make_async_copy(
                            hbm.at[layer_ref[0], page], vmem.at[buf, j],
                            sems.at[c, buf]))

        def reduce_page(buf, j, first):
            """Fold one page into every head's softmax statistics."""
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            for h in range(hkv):
                sc = jax.lax.dot_general(
                    q_ref[h].astype(kbuf.dtype), kbuf[buf, j, h],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(k_pos < length, sc, NEG_INF)  # (R, page)
                m_prev = m_ref[h][:, :1]
                m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
                p = jnp.exp(sc - m_new)
                corr = jnp.exp(m_prev - m_new)
                pv = jax.lax.dot_general(
                    p.astype(vbuf.dtype), vbuf[buf, j, h],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (R, d)
                acc_ref[h] = acc_ref[h] * corr + pv
                m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[h] = jnp.broadcast_to(
                    corr * l_ref[h][:, :1] + p.sum(axis=-1, keepdims=True),
                    l_ref.shape[1:])

        # the token's own row opens the reduction with weight exp(0) = 1
        qf = q_ref[...].astype(jnp.float32)                # (Hkv, R, d)
        s_new = jnp.sum(qf * kn_ref[...].astype(jnp.float32), axis=-1,
                        keepdims=True) * scale             # (Hkv, R, 1)
        m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(vn_ref[...].astype(jnp.float32),
                                        acc_ref.shape)

        @pl.when(n_blocks > 0)
        def _():
            each_copy(0, 0, lambda c: c.start())

        def step(i, carry):
            buf = i % 2

            @pl.when(i + 1 < n_blocks)
            def _():
                each_copy(i + 1, 1 - buf, lambda c: c.start())

            each_copy(i, buf, lambda c: c.wait())
            for j in range(pages_per_block):
                @pl.when(live(i, j))
                def _():
                    reduce_page(buf, j, (i * pages_per_block + j)
                                * page_size)
            return carry

        jax.lax.fori_loop(0, n_blocks, step, 0)
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(
            o_ref.dtype)

    q_spec = pl.BlockSpec((None, hkv, rows, d), lambda s, *_: (s, 0, 0, 0))
    row_spec = pl.BlockSpec((None, hkv, 1, d), lambda s, *_: (s, 0, 0, 0))
    buf_shape = (2, pages_per_block, hkv, d, page_size)
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[q_spec, row_spec, row_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM(buf_shape, k_pages.dtype),
                pltpu.VMEM(buf_shape, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, rows, STATS_LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, STATS_LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_slots, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_tables.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), qg, kn, vn, k_pages, v_pages)
    return out[:, :, :group].reshape(n_slots, hq, d)
