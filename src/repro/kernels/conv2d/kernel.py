"""Direct convolution as a batch-reduce GEMM Pallas kernel — paper Alg 4.

Mapping (DESIGN.md Sec. 2):

  * the paper's pointer-list walk over (r, s, c_b) becomes the innermost
    ("arbitrary") grid axis of size R*S*Cb; the ``BlockSpec.index_map``
    computes which weight panel and which input row each step needs — the
    TPU-native expression of A_ptrs/B_ptrs,
  * the output block O[n, oj, oi:oi+bq, kb*bk:...] accumulates in fp32 VMEM
    scratch across all R*S*Cb steps and is written to HBM exactly once —
    the paper's "accumulation chain stays in registers",
  * no im2col: the input stays in its (N, H, W, C) layout; each grid step
    streams one (row, channel-block) panel into VMEM and the in-kernel
    dynamic slice picks the (s, stride) phase,
  * bias + activation are fused on the accumulator (paper Sec. 3.2.2).

Stride handling: BlockSpecs cannot stride within a block, so the kernel
loads ``bq*stride`` contiguous input columns and subsamples in-register
(``reshape(bq, stride, bc)[:, 0]``) — the TPU-legal analogue of the paper's
``leading dimension = str * b_c`` trick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dispatch
from repro.core import fusion
from repro.core.blocking import ConvBlocks, ConvGeometry, round_up


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "activation", "out_dtype",
                     "blocks", "interpret", "acc_dtype"),
)
def conv2d_pallas(
    x,
    w,
    bias=None,
    *,
    stride: int = 1,
    padding: int = 0,
    activation: str = "none",
    out_dtype=None,
    blocks: ConvBlocks | None = None,
    interpret: bool = False,
    acc_dtype=jnp.float32,
):
    """x: (N, H, W, C), w: (R, S, C, K) -> (N, P, Q, K).

    Tile geometry comes from ``blocks`` (a ``ConvBlocks``); when unset it
    resolves through ``dispatch.resolve_blocks`` under the active block
    policy — the kernel itself makes no geometry choices.  The requested
    tile is clipped to the padded problem so any VMEM-feasible candidate
    is legal.
    """
    n, h, wi, c = x.shape
    r_, s_, c2, k = w.shape
    assert c == c2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    p = (h + 2 * padding - r_) // stride + 1
    q = (wi + 2 * padding - s_) // stride + 1

    blk = blocks or dispatch.resolve_blocks(
        "conv2d", q, c, k, x.dtype, backend="pallas",
        geometry=ConvGeometry(stride, r_, s_))
    bq = min(round_up(q, 8), blk.bq)
    bc = min(round_up(c, 128), blk.bc)
    bk = min(round_up(k, 128), blk.bk)
    qp = round_up(q, bq)
    cp = round_up(c, bc)
    kp = round_up(k, bk)
    cb_ = cp // bc
    kb_ = kp // bk

    # Host-side one-time padding (amortized like the paper's weight
    # reformatting): spatial pad + right-pad W so every (oib, s, stride)
    # dynamic slice stays in bounds.
    need_w = (qp - 1) * stride + (s_ - 1) + stride
    xp = jnp.pad(
        x,
        ((0, 0), (padding, padding),
         (padding, max(padding, need_w - wi - padding)), (0, cp - c)),
    )
    wp_ = jnp.pad(w, ((0, 0), (0, 0), (0, cp - c), (0, kp - k)))
    wf = wp_.reshape(r_ * s_, cp, kp)  # (RS, C, K): panel per (r, s)
    wpad = xp.shape[2]

    nsteps = r_ * s_ * cb_
    grid = (n, kb_, p, qp // bq, nsteps)

    def x_index(ni, kbi, oj, oib, rsc):
        r = rsc // (s_ * cb_)
        cb = rsc % cb_
        return (ni, oj * stride + r, 0, cb)

    def w_index(ni, kbi, oj, oib, rsc):
        rs = rsc // cb_
        cb = rsc % cb_
        return (rs, cb, kbi)

    in_specs = [
        pl.BlockSpec((1, 1, wpad, bc), x_index),
        pl.BlockSpec((1, bc, bk), w_index),
    ]
    operands = [xp, wf]
    has_bias = bias is not None
    if has_bias:
        bp = jnp.pad(bias.reshape(1, -1), ((0, 0), (0, kp - k)))
        operands.append(bp)
        in_specs.append(
            pl.BlockSpec((1, bk), lambda ni, kbi, oj, oib, rsc: (0, kbi)))

    def body(*refs):
        x_ref, w_ref = refs[0], refs[1]
        bias_ref = refs[2] if has_bias else None
        out_ref = refs[3] if has_bias else refs[2]
        acc_ref = refs[-1]

        rsc = pl.program_id(4)
        oib = pl.program_id(3)

        @pl.when(rsc == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        s = (rsc // cb_) % s_
        row = x_ref[0, 0]                      # (wpad, bc)
        start = oib * (bq * stride) + s
        patch = jax.lax.dynamic_slice(
            row, (start, 0), (bq * stride, bc))
        if stride > 1:
            patch = patch.reshape(bq, stride, bc)[:, 0, :]
        acc_ref[...] += jnp.dot(
            patch, w_ref[0], preferred_element_type=acc_dtype)

        @pl.when(rsc == nsteps - 1)
        def _():
            acc = acc_ref[...]
            if bias_ref is not None:
                acc += bias_ref[...].astype(jnp.float32)
            acc = fusion.apply(activation, acc)
            out_ref[...] = acc.astype(out_dtype)[None, None]

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, bq, bk), lambda ni, kbi, oj, oib, rsc: (ni, oj, oib, kbi)),
        out_shape=jax.ShapeDtypeStruct((n, p, qp, kp), out_dtype),
        scratch_shapes=[pltpu.VMEM((bq, bk), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:, :, :q, :k]
