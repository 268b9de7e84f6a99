"""FlashAttention as a batch-reduce GEMM — the beyond-paper unification.

Online-softmax attention is *exactly* the paper's kernel with a rescaling
epilogue: the output block O accumulates `sum_j P_j @ V_j` over KV blocks
(the reduce batch), with the running-max/denominator correction applied to
the VMEM-resident accumulator between steps.  Structure shared with
``kernels/brgemm``:

  * grid = (batch, q_heads, q_blocks, kv_blocks); last axis "arbitrary",
  * fp32 accumulator + (m, l) running statistics in VMEM scratch,
  * GQA is zero-copy: the K/V BlockSpec index_map maps q-head -> kv-head
    (h // group) — the paper's pointer-list trick again,
  * causal/sliding-window masks applied in-register on the scores block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dispatch
from repro.core.blocking import AttnBlocks, round_up

NEG_INF = -1e30
STATS_LANES = 128


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "blocks", "interpret",
                     "acc_dtype", "return_residuals"),
)
def flash_attention_pallas(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    blocks: AttnBlocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
    return_residuals: bool = False,
    q_offset=None,
):
    """q: (B, Hq, Tq, d); k, v: (B, Hkv, Tk, d) -> (B, Hq, Tq, d).

    Under ``causal``, key blocks past a query block's last position are
    neither fetched (their block index repeats the last live one) nor
    scored.  ``q_offset`` (a traced int32 scalar, causal only) places the
    queries at positions ``q_offset .. q_offset + Tq - 1`` of the keys: a
    prefill chunk against a cache, whose cost so follows the live extent,
    not ``Tk``.

    Tile geometry comes from ``blocks`` (an ``AttnBlocks``); when unset it
    resolves through ``dispatch.resolve_blocks`` under the active block
    policy — the kernel itself makes no geometry choices.  The running
    softmax statistics (m, l) always stay fp32; ``acc_dtype`` governs the
    output accumulator only.

    With ``return_residuals=True`` the kernel additionally emits the
    per-row log-sum-exp statistics ``lse = m + log(l)`` (fp32,
    (B, Hq, Tq)) — the VJP residual that lets the fused backward kernels
    rebuild the softmax blocks without re-running the online reduction.
    Fully-masked rows get ``lse = NEG_INF`` (log-sum-exp of an empty set).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    blk = blocks or dispatch.resolve_blocks(
        "flash_attention", tq, tk, d, q.dtype, backend="pallas")
    bq = min(round_up(tq, 8), blk.block_q)
    bk = min(round_up(tk, 128), blk.block_k)
    tqp, tkp = round_up(tq, bq), round_up(tk, bk)
    dp = round_up(d, 128)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tkp - tk), (0, dp - d)))

    grid = (b, hq, tqp // bq, tkp // bk)
    nkv = tkp // bk
    offset = q_offset is not None
    assert causal or not offset, "q_offset places causal chunk queries"

    def body(*refs):
        q_ref, k_ref, v_ref, o_ref, *rest = refs[1:] if offset else refs
        if return_residuals:
            lse_ref, acc_ref, m_ref, l_ref = rest
        else:
            lse_ref, (acc_ref, m_ref, l_ref) = None, rest
        j = pl.program_id(3)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        i = pl.program_id(2)
        q_start = refs[0][0] + i * bq if offset else i * bq
        k_start = j * bk

        def compute():
            qb = q_ref[0, 0]          # (bq, dp)
            kb = k_ref[0, 0]          # (bk, dp)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (bq, bk)

            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_pos < tk  # padded kv positions
            if causal:
                mask &= k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[:, :1]                       # (bq, 1)
            l_prev = l_ref[:, :1]
            m_cur = s.max(axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                      # (bq, bk)
            corr = jnp.exp(m_prev - m_new)              # (bq, 1)
            l_new = corr * l_prev + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dtype)
            acc_ref[...] = (acc_ref[...] * corr.astype(acc_dtype)
                            + pv).astype(acc_dtype)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        if causal:
            # Skip blocks strictly above the diagonal (no valid positions).
            @pl.when(k_start <= q_start + bq - 1)
            def _():
                compute()
        else:
            compute()

        @pl.when(j == nkv - 1)
        def _():
            l = l_ref[:, :1]
            lsafe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 out
            o_ref[...] = (acc_ref[...].astype(jnp.float32)
                          / lsafe).astype(o_ref.dtype)[None, None]
            if lse_ref is not None:
                lse = jnp.where(l > 0.0, m_ref[:, :1] + jnp.log(lsafe),
                                NEG_INF)
                lse_ref[...] = jnp.broadcast_to(
                    lse, lse_ref.shape[2:])[None, None]

    def kv_block(b_, h, i, j, *off):
        if causal:  # a skipped block repeats the last live one: no fetch
            j = jnp.minimum(
                j, ((off[0][0] if off else 0) + i * bq + bq - 1) // bk)
        return (b_, h // group, j, 0)

    q_spec = pl.BlockSpec((1, 1, bq, dp),
                          lambda b_, h, i, j, *_: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, dp), kv_block)
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((b, hq, tqp, dp), q.dtype)]
    if return_residuals:
        out_specs.append(pl.BlockSpec((1, 1, bq, STATS_LANES),
                                      lambda b_, h, i, j, *_: (b_, h, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, hq, tqp, STATS_LANES), jnp.float32))
    scratch = [
        pltpu.VMEM((bq, dp), acc_dtype),
        pltpu.VMEM((bq, STATS_LANES), jnp.float32),
        pltpu.VMEM((bq, STATS_LANES), jnp.float32),
    ]
    spec = dict(grid=grid, in_specs=[q_spec, kv_spec, kv_spec],
                out_specs=out_specs, scratch_shapes=scratch)
    operands = [qp, kp, vp]
    if offset:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec))
        operands = [jnp.reshape(q_offset, (1,)).astype(jnp.int32),
                    *operands]

    outs = pl.pallas_call(
        body,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        **spec,
    )(*operands)
    out = outs[0][:, :, :tq, :d]
    if return_residuals:
        return out, outs[1][:, :, :tq, 0]
    return out
