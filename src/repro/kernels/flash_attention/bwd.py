"""Fused Pallas flash-attention backward — the training-side building block.

The paper's Sec. 3 point is that backward passes are not special cases:
backward-by-data and weight-update are the *same* batch-reduce GEMM loop
with reindexed operands.  FlashAttention's recompute backward has exactly
that structure, so all three gradient kernels here are the forward kernel's
loop nest with the roles of the axes swapped:

  * dQ — outer loop over Q blocks, batch-reduce over K blocks
    (dQ += dS K).  Its first reduce step also computes
    ``delta = rowsum(dY ∘ Y)`` (the softmax-Jacobian correction term)
    into VMEM scratch — dY and Y are already resident for dS — and emits
    it as a second output, so delta costs no extra pass over HBM,
  * dK/dV — outer loop over K blocks, batch-reduce over Q blocks
    (dV += P^T dY, dK += dS^T Q accumulate in VMEM scratch across the
    whole Q axis and hit HBM once), consuming dQ's delta output.

The pre-fusion standalone delta kernel survives as
:func:`delta_rowsum_pallas`, the interpret-mode parity oracle for the
fused path.

No online-softmax recompute: the forward saved the per-row log-sum-exp, so
each score block rebuilds its softmax as ``P = exp(S - lse)`` in one shot.
GQA stays zero-copy through the K/V index_map (h -> h // group); the group
reduction of dK/dV over the q-heads sharing a kv-head happens host-side on
the fp32 kernel outputs.  Causal/window masking skips whole blocks exactly
like the forward, plus an explicit ``q_pos < tq`` guard: padded query rows
carry garbage lse, and only the mask keeps them out of the reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dispatch
from repro.core.blocking import AttnBwdBlocks, round_up
from repro.kernels.flash_attention.kernel import STATS_LANES


def _mask(q_start, k_start, bq, bk, tq, tk, causal, window):
    """Validity mask for one (bq, bk) score block, including padded rows."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (q_pos < tq) & (k_pos < tk)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def _block_live(q_start, k_start, bq, bk, causal, window):
    """Whether any (q, k) pair in the block can be unmasked — the same
    whole-block skip the forward kernel uses, extended to the window's
    lower bound.  Returns None when every block is live (dense case)."""
    cond = None
    if causal:
        cond = k_start <= q_start + bq - 1
    if window is not None:
        wcond = k_start + bk - 1 > q_start - window
        cond = wcond if cond is None else cond & wcond
    return cond


def _delta_body(y_ref, dy_ref, delta_ref):
    prod = (y_ref[0, 0].astype(jnp.float32)
            * dy_ref[0, 0].astype(jnp.float32))
    delta_ref[...] = jnp.broadcast_to(
        prod.sum(axis=-1, keepdims=True),
        delta_ref.shape[2:])[None, None]


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def delta_rowsum_pallas(y, dy, *, block_q: int = 128,
                        interpret: bool = False):
    """Standalone ``delta = rowsum(dY ∘ Y)`` pass over Q blocks.

    Superseded in the fused backward — the dQ kernel's first reduce step
    now computes delta in-kernel from its resident dY/Y panels, dropping
    this kernel's full HBM pass — but kept as the interpret-mode parity
    oracle for that fusion.  Returns (B, Hq, Tq) fp32.
    """
    b, hq, tq, d = y.shape
    bq = min(round_up(tq, 8), block_q)
    tqp, dp = round_up(tq, bq), round_up(d, 128)
    yp = jnp.pad(y, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    dyp = jnp.pad(dy, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    dspec = pl.BlockSpec((1, 1, bq, dp), lambda b_, h, i: (b_, h, i, 0))
    delta = pl.pallas_call(
        _delta_body,
        grid=(b, hq, tqp // bq),
        in_specs=[dspec, dspec],
        out_specs=pl.BlockSpec((1, 1, bq, STATS_LANES),
                               lambda b_, h, i: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tqp, STATS_LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(yp, dyp)
    return delta[:, :, :tq, 0]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "blocks", "interpret",
                     "acc_dtype", "return_delta"),
)
def flash_attention_bwd_pallas(
    q,
    k,
    v,
    y,
    lse,
    dy,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    blocks: AttnBwdBlocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
    return_delta: bool = False,
):
    """Fused backward: (dq, dk, dv) from the forward's (y, lse) residuals.

    q, dy, y: (B, Hq, Tq, d); k, v: (B, Hkv, Tk, d); lse: (B, Hq, Tq)
    fp32.  Tile geometry comes from ``blocks`` (an ``AttnBwdBlocks``);
    when unset it resolves through ``dispatch.resolve_blocks`` under the
    active block policy — tuned independently of the forward tile.  Score
    and dS blocks are fp32; ``acc_dtype`` governs the dq/dk/dv
    accumulators (``repro.use(accum_dtype=...)`` reaches here through the
    dispatch layer).

    ``delta = rowsum(dY ∘ Y)`` is fused into dQ's first reduce step (no
    standalone pass over dY/Y); ``return_delta=True`` appends the fused
    (B, Hq, Tq) delta to the outputs for parity testing against
    :func:`delta_rowsum_pallas`.
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    blk = blocks or dispatch.resolve_blocks(
        "flash_attention_bwd", tq, tk, d, q.dtype, backend="pallas")
    bq = min(round_up(tq, 8), blk.block_q)
    bk = min(round_up(tk, 128), blk.block_k)
    tqp, tkp = round_up(tq, bq), round_up(tk, bk)
    dp = round_up(d, 128)
    nq, nk = tqp // bq, tkp // bk

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tkp - tk), (0, dp - d)))
    yp = jnp.pad(y, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    dyp = jnp.pad(dy, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    # lse rides in the forward's stats layout: broadcast across lanes so
    # the (1, 1, bq, STATS_LANES) block is TPU-legal; padded rows are
    # masked in-kernel so their value never matters.
    lsep = jnp.pad(lse.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, tqp - tq)))
    lsep = jnp.broadcast_to(lsep[..., None], (b, hq, tqp, STATS_LANES))

    def _specs(qi, kj, tail):
        """in_specs for (q, k, v, dy, lse, *tail) given which of the two
        inner grid axes indexes Q blocks (qi) and K blocks (kj); ``tail``
        names extra row-shaped ("row") or stats-shaped ("stats") inputs."""
        row = pl.BlockSpec((1, 1, bq, dp),
                           lambda b_, h, g0, g1: (b_, h, qi(g0, g1), 0))
        stats = pl.BlockSpec((1, 1, bq, STATS_LANES),
                             lambda b_, h, g0, g1: (b_, h, qi(g0, g1), 0))
        kv = pl.BlockSpec(
            (1, 1, bk, dp),
            lambda b_, h, g0, g1: (b_, h // group, kj(g0, g1), 0))
        named = {"row": row, "stats": stats}
        return [row, kv, kv, row, stats] + [named[t] for t in tail]

    # Under ``causal`` a skipped block's index repeats the nearest live
    # one, so the pipeline fetches nothing for it: the dQ kernel's key
    # blocks stop at the last one its query block sees, the dK/dV
    # kernel's query blocks start at the first one that sees its keys.
    def last_live_k(i, j):
        return jnp.minimum(j, (i * bq + bq - 1) // bk) if causal else j

    def first_live_q(j, i):
        return jnp.clip(i, (j * bk) // bq, nq - 1) if causal else i

    # ---- shared score-block recompute -----------------------------------

    def _p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_col,
              q_start, k_start):
        """Rebuild P = exp(S - lse) and dS for one (bq, bk) block;
        ``delta_col`` is the (bq, 1) softmax-Jacobian correction."""
        qb = q_ref[0, 0]
        kb = k_ref[0, 0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _mask(q_start, k_start, bq, bk, tq, tk, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, :1]), 0.0)
        dp_ = jax.lax.dot_general(
            dy_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp_ - delta_col) * scale
        return qb, kb, p, ds

    # ---- dQ (+ fused delta): outer over Q, batch-reduce over K ----------

    def dq_body(q_ref, k_ref, v_ref, dy_ref, lse_ref, y_ref,
                dq_ref, delta_ref, dq_acc, delta_acc):
        i, j = pl.program_id(2), pl.program_id(3)
        q_start, k_start = i * bq, j * bk

        @pl.when(j == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            # delta = rowsum(dY ∘ Y) rides with the first reduce step:
            # the dY panel is already resident for dS, Y replaces the
            # delta input this kernel used to read.  Unconditional (not
            # under _block_live) — dK/dV needs delta for every Q row,
            # including rows whose (i, j) score block is masked here.
            prod = (y_ref[0, 0].astype(jnp.float32)
                    * dy_ref[0, 0].astype(jnp.float32))
            delta_acc[...] = jnp.broadcast_to(
                prod.sum(axis=-1, keepdims=True), delta_acc.shape)

        def compute():
            _, kb, _, ds = _p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref,
                                 delta_acc[:, :1], q_start, k_start)
            dq_acc[...] += jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dtype).astype(acc_dtype)

        live = _block_live(q_start, k_start, bq, bk, causal, window)
        if live is None:
            compute()
        else:
            pl.when(live)(compute)

        @pl.when(j == nk - 1)
        def _():
            dq_ref[...] = dq_acc[...].astype(jnp.float32)[None, None]
            delta_ref[...] = delta_acc[...][None, None]

    dq, delta = pl.pallas_call(
        dq_body,
        grid=(b, hq, nq, nk),
        in_specs=_specs(qi=lambda i, j: i, kj=last_live_k,
                        tail=("row",)),
        out_specs=[
            pl.BlockSpec((1, 1, bq, dp),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, STATS_LANES),
                         lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, tqp, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, tqp, STATS_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dp), acc_dtype),
            pltpu.VMEM((bq, STATS_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, dyp, lsep, yp)

    # ---- dK/dV: outer over K blocks, batch-reduce over Q blocks ---------

    def dkdv_body(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref,
                  dk_ref, dv_ref, dk_acc, dv_acc):
        j, i = pl.program_id(2), pl.program_id(3)
        q_start, k_start = i * bq, j * bk

        @pl.when(i == 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def compute():
            qb, _, p, ds = _p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref,
                                 delta_ref[0, 0][:, :1], q_start, k_start)
            dv_acc[...] += jax.lax.dot_general(
                p.astype(v_ref.dtype), dy_ref[0, 0],
                (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dtype).astype(acc_dtype)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dtype).astype(acc_dtype)

        live = _block_live(q_start, k_start, bq, bk, causal, window)
        if live is None:
            compute()
        else:
            pl.when(live)(compute)

        @pl.when(i == nq - 1)
        def _():
            dk_ref[...] = dk_acc[...].astype(jnp.float32)[None, None]
            dv_ref[...] = dv_acc[...].astype(jnp.float32)[None, None]

    dk, dv = pl.pallas_call(
        dkdv_body,
        grid=(b, hq, nk, nq),
        in_specs=_specs(qi=first_live_q, kj=lambda j, i: j,
                        tail=("stats",)),
        out_specs=[
            pl.BlockSpec((1, 1, bk, dp),
                         lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dp),
                         lambda b_, h, j, i: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, tkp, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, tkp, dp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dp), acc_dtype),
            pltpu.VMEM((bk, dp), acc_dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, dyp, lsep, delta)

    dq = dq[:, :, :tq, :d]
    dk = dk[:, :, :tk, :d]
    dv = dv[:, :, :tk, :d]
    if group > 1:
        # GQA: kv-head gradients sum over the q-heads sharing the head.
        dk = dk.reshape(b, hkv, group, tk, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, tk, d).sum(axis=2)
    out = (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))
    if return_delta:
        return out + (delta[:, :, :tq, 0],)
    return out
