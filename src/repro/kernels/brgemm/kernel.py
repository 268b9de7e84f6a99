"""The batch-reduce GEMM Pallas TPU kernel.

Paper Algorithm 1, adapted for TPU (see DESIGN.md Sec. 2):

  * the fp32 accumulator block lives in VMEM scratch and is carried across
    the innermost ("arbitrary") grid axis — the TPU analogue of keeping the
    accumulation chain in registers,
  * the paper's pointer lists A_ptrs/B_ptrs become ``BlockSpec.index_map``
    functions: arbitrary sub-blocks of the input tensors are streamed into
    VMEM with no copies/reformatting,
  * the epilogue (alpha/beta scaling, bias, activation) is fused on the
    VMEM-resident accumulator before the single HBM write-back,
  * Mosaic double-buffers the A/B panel DMAs across grid steps (the
    software-prefetch analogue).

Three entry points share one kernel body:
  - ``matmul_pallas``:          C = act(alpha * X @ W + bias)            (K-block reduce)
  - ``brgemm_stacked_pallas``:  C = act(alpha * sum_i A_i @ B_i + ...)   (paper's literal interface)
  - ``batched_matmul_pallas``:  C_i = act(alpha * A_i @ B_i + bias)      (the baseline "batched GEMM";
                                 supports broadcast of either operand with zero copies via index_map)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fusion
from repro.core.blocking import Blocks, choose_blocks, round_up


def _pad2(x, bm, bn):
    m, n = x.shape
    pm, pn = round_up(m, bm), round_up(n, bn)
    if (pm, pn) != (m, n):
        x = jnp.pad(x, ((0, pm - m), (0, pn - n)))
    return x


def _pad3(x, bb, bm, bn):
    b, m, n = x.shape
    pm, pn = round_up(m, bm), round_up(n, bn)
    if (pm, pn) != (m, n):
        x = jnp.pad(x, ((0, 0), (0, pm - m), (0, pn - n)))
    return x


def _make_body(
    *,
    reduce_axis: int,
    has_c0: bool,
    has_bias: bool,
    alpha: float,
    beta: float,
    activation: str,
    out_dtype,
    block_rank3: bool,
    acc_dtype=jnp.float32,
):
    """Build the kernel body. Ref order: a, b, [c0], [bias], out, acc."""

    def body(*refs):
        idx = 0
        a_ref = refs[idx]; idx += 1
        b_ref = refs[idx]; idx += 1
        c0_ref = None
        bias_ref = None
        if has_c0:
            c0_ref = refs[idx]; idx += 1
        if has_bias:
            bias_ref = refs[idx]; idx += 1
        out_ref = refs[idx]; idx += 1
        acc_ref = refs[idx]

        r = pl.program_id(reduce_axis)
        nr = pl.num_programs(reduce_axis)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = a_ref[...]
        b = b_ref[...]
        if block_rank3:  # leading singleton batch-block dim
            a = a[0]
            b = b[0]
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

        @pl.when(r == nr - 1)
        def _finish():
            acc = acc_ref[...] * jnp.float32(alpha)
            if c0_ref is not None:
                # c0 blocks are always 2-D (bm, bn), independent of the
                # rank of the A/B blocks.
                acc += jnp.float32(beta) * c0_ref[...].astype(jnp.float32)
            if bias_ref is not None:
                acc += bias_ref[...].astype(jnp.float32)
            acc = fusion.apply(activation, acc)
            out = acc.astype(out_dtype)
            if out_ref.ndim == 3:
                out = out[None]
            out_ref[...] = out

    return body


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "alpha", "beta", "out_dtype", "blocks", "interpret",
        "acc_dtype",
    ),
)
def matmul_pallas(
    x,
    w,
    bias=None,
    c0=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    blocks: Blocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
):
    """C = act(alpha * X @ W + beta * C0 + bias); X: (m,k), W: (k,n).

    The K dimension is the batch-reduce axis: the grid walks K blocks while
    the fp32 accumulator stays resident in VMEM.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    blk = blocks or choose_blocks(m, n, k, x.dtype)
    bm, bn, bk = blk.astuple()

    xp = _pad2(x, bm, bk)
    wp = _pad2(w, bk, bn)
    mp, kp = xp.shape
    _, np_ = wp.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, r: (i, r)),
        pl.BlockSpec((bk, bn), lambda i, j, r: (r, j)),
    ]
    operands = [xp, wp]
    has_c0 = c0 is not None and beta != 0.0
    if has_c0:
        operands.append(_pad2(c0, bm, bn))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)))
    has_bias = bias is not None
    if has_bias:
        bp = _pad2(bias.reshape(1, -1), 1, bn)
        operands.append(bp)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, r: (0, j)))

    body = _make_body(
        reduce_axis=2, has_c0=has_c0, has_bias=has_bias, alpha=alpha,
        beta=beta, activation=activation, out_dtype=out_dtype,
        block_rank3=False, acc_dtype=acc_dtype,
    )
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
    static_argnames=(
        "activation", "alpha", "beta", "out_dtype", "blocks", "interpret",
        "acc_dtype",
    ),
)
def brgemm_stacked_pallas(
    a,
    b,
    c0=None,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    blocks: Blocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
):
    """Paper's literal interface: C = act(alpha * sum_i A_i@B_i + beta*C0 + bias).

    a: (B, m, k), b: (B, k, n) -> (m, n).  The reduction grid axis walks
    (batch x K-blocks); the accumulator is written to HBM exactly once.
    """
    nb, m, k = a.shape
    nb2, k2, n = b.shape
    assert nb == nb2 and k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    blk = blocks or choose_blocks(m, n, k, a.dtype)
    bm, bn, bk = blk.astuple()

    ap = _pad3(a, 1, bm, bk)
    bp = _pad3(b, 1, bk, bn)
    kp = ap.shape[2]
    kb = kp // bk  # K blocks per batch entry
    grid = (ap.shape[1] // bm, bp.shape[2] // bn, nb * kb)

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, j, r: (r // kb, i, r % kb)),
        pl.BlockSpec((1, bk, bn), lambda i, j, r: (r // kb, r % kb, j)),
    ]
    operands = [ap, bp]
    has_c0 = c0 is not None and beta != 0.0
    if has_c0:
        operands.append(_pad2(c0, bm, bn))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)))
    has_bias = bias is not None
    if has_bias:
        operands.append(_pad2(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, r: (0, j)))

    body = _make_body(
        reduce_axis=2, has_c0=has_c0, has_bias=has_bias, alpha=alpha,
        beta=beta, activation=activation, out_dtype=out_dtype,
        block_rank3=True, acc_dtype=acc_dtype,
    )
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[1], bp.shape[2]), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("activation", "alpha", "out_dtype", "blocks", "interpret",
                     "acc_dtype"),
)
def batched_matmul_pallas(
    a,
    b,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    out_dtype=None,
    blocks: Blocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
):
    """Strided-batched GEMM baseline; broadcast either operand zero-copy.

    a: (B, m, k) or (m, k); b: (B, k, n) or (k, n) -> (B, m, n).
    Broadcasting is expressed through the index_map (the paper's pointer-list
    trick): a 2-D operand is re-read for every batch entry without ever being
    materialized B times.
    """
    a_bcast = a.ndim == 2
    b_bcast = b.ndim == 2
    assert not (a_bcast and b_bcast)
    nb = b.shape[0] if a_bcast else a.shape[0]
    m, k = a.shape[-2:]
    k2, n = b.shape[-2:]
    assert k == k2
    out_dtype = out_dtype or a.dtype
    blk = blocks or choose_blocks(m, n, k, a.dtype)
    bm, bn, bk = blk.astuple()

    ap = _pad2(a, bm, bk) if a_bcast else _pad3(a, 1, bm, bk)
    bp = _pad2(b, bk, bn) if b_bcast else _pad3(b, 1, bk, bn)
    mp = ap.shape[-2]
    np_ = bp.shape[-1]
    kp = ap.shape[-1]
    grid = (nb, mp // bm, np_ // bn, kp // bk)

    if a_bcast:
        a_spec = pl.BlockSpec((bm, bk), lambda bi, i, j, r: (i, r))
    else:
        a_spec = pl.BlockSpec((1, bm, bk), lambda bi, i, j, r: (bi, i, r))
    if b_bcast:
        b_spec = pl.BlockSpec((bk, bn), lambda bi, i, j, r: (r, j))
    else:
        b_spec = pl.BlockSpec((1, bk, bn), lambda bi, i, j, r: (bi, r, j))

    in_specs = [a_spec, b_spec]
    operands = [ap, bp]
    has_bias = bias is not None
    if has_bias:
        operands.append(_pad2(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda bi, i, j, r: (0, j)))

    # block_rank3 handling differs per operand; use a dedicated body.
    acts = fusion.ACTIVATIONS[activation]

    def body(*refs):
        idx = 0
        a_ref = refs[idx]; idx += 1
        b_ref = refs[idx]; idx += 1
        bias_ref = refs[idx] if has_bias else None
        idx += 1 if has_bias else 0
        out_ref = refs[idx]; idx += 1
        acc_ref = refs[idx]

        r = pl.program_id(3)
        nr = pl.num_programs(3)

        @pl.when(r == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        av = a_ref[...] if a_ref.ndim == 2 else a_ref[0]
        bv = b_ref[...] if b_ref.ndim == 2 else b_ref[0]
        acc_ref[...] += jnp.dot(av, bv, preferred_element_type=acc_dtype)

        @pl.when(r == nr - 1)
        def _():
            acc = acc_ref[...] * jnp.float32(alpha)
            if bias_ref is not None:
                acc += bias_ref[...].astype(jnp.float32)
            out_ref[...] = acts(acc).astype(out_dtype)[None]

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, r: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:, :m, :n]
