"""Quantized batch-reduce GEMM Pallas kernels with fused dequant epilogue.

The same loop nest as ``kernel.py`` — grid walks the reduce axis while the
accumulator block stays resident in VMEM — but the operands are int8 (or
fp8) storage and the accumulator is the dtype-implied one (int32 for int8
via the MXU's integer path, fp32 for fp8).  Dequantization is *never* a
separate pass: the per-row activation scales and per-channel weight scales
multiply the accumulator in the epilogue, fused with alpha/bias/activation
before the single HBM write-back, so the quantized kernel touches HBM
exactly as often as the full-precision one while streaming operand panels
at 1/2 (vs bf16) or 1/4 (vs fp32) the bytes.

Scales ride in TPU-legal layouts borrowed from the library's existing
idioms: row scales broadcast across ``SCALE_LANES`` lanes (the
flash-attention stats layout) so a ``(bm, SCALE_LANES)`` block is legal,
and channel scales use bias-style ``(1, bn)`` blocks.

For the stacked brgemm the scales are *batch-shared* (one absmax over the
whole (B, k) reduction panel per output row/channel): the accumulator sums
int32 products across the entire reduction before the one dequant, so
per-batch scales would be mathematically wrong, not just slower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fusion
from repro.core.blocking import Blocks, choose_blocks, round_up
from repro.kernels.brgemm.kernel import _pad2, _pad3

SCALE_LANES = 128  # lane-broadcast width for row-scale blocks


def _acc_dtype(storage_dtype) -> object:
    """int32 accumulation for int8 storage, fp32 for fp8."""
    return jnp.int32 if jnp.dtype(storage_dtype) == jnp.int8 else jnp.float32


def _row_scales(s, pm: int):
    """(rows,) fp32 -> (pm, SCALE_LANES) lane-broadcast, row-padded."""
    s = s.astype(jnp.float32)
    if s.shape[0] != pm:
        s = jnp.pad(s, (0, pm - s.shape[0]))
    return jnp.broadcast_to(s[:, None], (pm, SCALE_LANES))


def _col_scales(s, pn: int):
    """(cols,) fp32 -> (1, pn) bias-style block row."""
    return _pad2(s.astype(jnp.float32).reshape(1, -1), 1, pn)


def _dequant_finish(acc, sx_block, sw_block, bias_ref, alpha, activation,
                    out_dtype):
    """The fused epilogue: dequant x epilogue on the VMEM accumulator."""
    acc = acc.astype(jnp.float32)
    acc = acc * (sx_block[:, :1] * sw_block.astype(jnp.float32))
    acc = acc * jnp.float32(alpha)
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    acc = fusion.apply(activation, acc)
    return acc.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "alpha", "out_dtype", "blocks",
                     "interpret"),
)
def matmul_q_pallas(
    xq,
    wq,
    sx,
    sw,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    out_dtype=jnp.float32,
    blocks: Blocks | None = None,
    interpret: bool = False,
):
    """C = act(alpha * (Xq @ Wq) * (sx x sw) + bias).

    xq: (m, k) quantized activations with per-row scales sx: (m,) fp32;
    wq: (k, n) quantized weights with per-channel scales sw: (n,) fp32
    (per-tensor configs pass broadcast scales).  The K grid axis is the
    batch-reduce; dequant happens once, in the epilogue.
    """
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2, (xq.shape, wq.shape)
    acc_dtype = _acc_dtype(xq.dtype)
    blk = blocks or choose_blocks(m, n, k, xq.dtype)
    bm, bn, bk = blk.astuple()

    xp = _pad2(xq, bm, bk)
    wp = _pad2(wq, bk, bn)
    mp, kp = xp.shape
    _, np_ = wp.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, r: (i, r)),
        pl.BlockSpec((bk, bn), lambda i, j, r: (r, j)),
        pl.BlockSpec((bm, SCALE_LANES), lambda i, j, r: (i, 0)),
        pl.BlockSpec((1, bn), lambda i, j, r: (0, j)),
    ]
    operands = [xp, wp, _row_scales(sx, mp), _col_scales(sw, np_)]
    has_bias = bias is not None
    if has_bias:
        operands.append(_pad2(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, r: (0, j)))

    def body(x_ref, w_ref, sx_ref, sw_ref, *rest):
        bias_ref = rest[0] if has_bias else None
        out_ref = rest[1] if has_bias else rest[0]
        acc_ref = rest[-1]
        r = pl.program_id(2)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=acc_dtype)

        @pl.when(r == pl.num_programs(2) - 1)
        def _finish():
            out_ref[...] = _dequant_finish(
                acc_ref[...], sx_ref[...], sw_ref[...], bias_ref, alpha,
                activation, out_dtype)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("activation", "alpha", "out_dtype", "blocks",
                     "interpret"),
)
def brgemm_q_pallas(
    aq,
    bq,
    sa,
    sb,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    out_dtype=jnp.float32,
    blocks: Blocks | None = None,
    interpret: bool = False,
):
    """C = act(alpha * (sum_i Aq_i @ Bq_i) * (sa x sb) + bias).

    aq: (B, m, k), bq: (B, k, n); sa: (m,), sb: (n,) fp32 — batch-shared
    scales (absmax over the full (B, k) reduction panel), so the single
    end-of-reduction dequant is exact for the summed accumulator.
    """
    nb, m, k = aq.shape
    nb2, k2, n = bq.shape
    assert nb == nb2 and k == k2, (aq.shape, bq.shape)
    acc_dtype = _acc_dtype(aq.dtype)
    blk = blocks or choose_blocks(m, n, k, aq.dtype)
    bm, bn, bk = blk.astuple()

    ap = _pad3(aq, 1, bm, bk)
    bp = _pad3(bq, 1, bk, bn)
    mp, kp = ap.shape[1], ap.shape[2]
    np_ = bp.shape[2]
    kb = kp // bk
    grid = (mp // bm, np_ // bn, nb * kb)

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, j, r: (r // kb, i, r % kb)),
        pl.BlockSpec((1, bk, bn), lambda i, j, r: (r // kb, r % kb, j)),
        pl.BlockSpec((bm, SCALE_LANES), lambda i, j, r: (i, 0)),
        pl.BlockSpec((1, bn), lambda i, j, r: (0, j)),
    ]
    operands = [ap, bp, _row_scales(sa, mp), _col_scales(sb, np_)]
    has_bias = bias is not None
    if has_bias:
        operands.append(_pad2(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, r: (0, j)))

    def body(a_ref, b_ref, sa_ref, sb_ref, *rest):
        bias_ref = rest[0] if has_bias else None
        out_ref = rest[1] if has_bias else rest[0]
        acc_ref = rest[-1]
        r = pl.program_id(2)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=acc_dtype)

        @pl.when(r == pl.num_programs(2) - 1)
        def _finish():
            out_ref[...] = _dequant_finish(
                acc_ref[...], sa_ref[...], sb_ref[...], bias_ref, alpha,
                activation, out_dtype)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("activation", "alpha", "out_dtype", "blocks",
                     "interpret"),
)
def batched_matmul_q_pallas(
    aq,
    bq,
    sa,
    sb,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    out_dtype=jnp.float32,
    blocks: Blocks | None = None,
    interpret: bool = False,
):
    """C_i = act(alpha * (Aq_i @ Bq_i) * (sa_i x sb_i) + bias).

    aq: (B, m, k) with per-batch-per-row scales sa: (B, m); bq: (B, k, n)
    with per-batch-per-channel scales sb: (B, n).  No cross-batch
    reduction, so scales are free to vary per batch entry.
    """
    nb, m, k = aq.shape
    nb2, k2, n = bq.shape
    assert nb == nb2 and k == k2, (aq.shape, bq.shape)
    acc_dtype = _acc_dtype(aq.dtype)
    blk = blocks or choose_blocks(m, n, k, aq.dtype)
    bm, bn, bk = blk.astuple()

    ap = _pad3(aq, 1, bm, bk)
    bp = _pad3(bq, 1, bk, bn)
    mp, kp = ap.shape[1], ap.shape[2]
    np_ = bp.shape[2]
    grid = (nb, mp // bm, np_ // bn, kp // bk)

    sa3 = sa.astype(jnp.float32)
    if sa3.shape[1] != mp:
        sa3 = jnp.pad(sa3, ((0, 0), (0, mp - sa3.shape[1])))
    sa3 = jnp.broadcast_to(sa3[..., None], (nb, mp, SCALE_LANES))
    sb3 = sb.astype(jnp.float32)[:, None, :]
    if sb3.shape[2] != np_:
        sb3 = jnp.pad(sb3, ((0, 0), (0, 0), (0, np_ - sb3.shape[2])))

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda bi, i, j, r: (bi, i, r)),
        pl.BlockSpec((1, bk, bn), lambda bi, i, j, r: (bi, r, j)),
        pl.BlockSpec((1, bm, SCALE_LANES), lambda bi, i, j, r: (bi, i, 0)),
        pl.BlockSpec((1, 1, bn), lambda bi, i, j, r: (bi, 0, j)),
    ]
    operands = [ap, bp, sa3, sb3]
    has_bias = bias is not None
    if has_bias:
        operands.append(_pad2(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda bi, i, j, r: (0, j)))

    def body(a_ref, b_ref, sa_ref, sb_ref, *rest):
        bias_ref = rest[0] if has_bias else None
        out_ref = rest[1] if has_bias else rest[0]
        acc_ref = rest[-1]
        r = pl.program_id(3)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=acc_dtype)

        @pl.when(r == pl.num_programs(3) - 1)
        def _finish():
            out_ref[...] = _dequant_finish(
                acc_ref[...], sa_ref[0], sb_ref[0], bias_ref, alpha,
                activation, out_dtype)[None]

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, r: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:, :m, :n]
