"""Differentiable conv2d with dual-convolution backward — paper Sec. 3.2.2.

The paper (citing [27]) implements backward-by-data and weight-update as
"dual convolutions": linear index transformations of the forward loop nest,
reusing the same batch-reduce building block.  Here:

  * dgrad  = conv2d( dilate_{stride}(g), rot180(W) swapped C<->K, stride=1 )
             — runs through the *same* Pallas conv kernel,
  * wgrad  = per-(r,s) batch-reduce GEMM  X_(r,s)^T @ g  with the minibatch
             as the reduction dimension (the paper's "upd" pass, Sec. 4.1.1),
  * bias   = spatial/minibatch sum of g.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch, fusion
from repro.core.blocking import ConvBlocks, ConvGeometry
from repro.kernels.brgemm import kernel as BK
from repro.kernels.conv2d import ref as R
from repro.kernels.conv2d.kernel import conv2d_pallas
from repro.launch.mesh import dp_axes
from repro.sharding import local as _local


class _Cfg(NamedTuple):
    stride: int
    padding: int
    activation: str
    out_dtype: object
    blocks: ConvBlocks | None
    interpret: bool
    acc_dtype: object


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_p(cfg: _Cfg, x, w, bias):
    return conv2d_pallas(
        x, w, bias, stride=cfg.stride, padding=cfg.padding,
        activation=cfg.activation, out_dtype=cfg.out_dtype,
        blocks=cfg.blocks, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype)


def _conv_fwd(cfg, x, w, bias):
    y = _conv_p(cfg, x, w, bias)
    return y, (x, w, bias, y)


def _dilate(g, stride):
    if stride == 1:
        return g
    n, p, q, k = g.shape
    out = jnp.zeros((n, (p - 1) * stride + 1, (q - 1) * stride + 1, k),
                    g.dtype)
    return out.at[:, ::stride, ::stride, :].set(g)


def _conv_bwd(cfg, res, dy):
    x, w, bias, y = res
    st, pad = cfg.stride, cfg.padding
    n, h, wi, c = x.shape
    r_, s_, _, k = w.shape
    p = (h + 2 * pad - r_) // st + 1
    q = (wi + 2 * pad - s_) // st + 1

    dy32 = dy.astype(jnp.float32)
    if not fusion.needs_preact(cfg.activation):
        g = dy32 * fusion.GRAD_FROM_OUTPUT[cfg.activation](
            y.astype(jnp.float32))
    else:
        pre = conv2d_pallas(
            x, w, bias, stride=st, padding=pad, activation="none",
            out_dtype=jnp.float32, blocks=cfg.blocks,
            interpret=cfg.interpret)
        g = dy32 * fusion.GRAD_FROM_PREACT[cfg.activation](pre)
    g = g.astype(x.dtype)

    # --- dgrad: dual convolution through the same Pallas kernel ----------
    g_dil = _dilate(g, st)
    # bottom/right coverage pad so the dual conv reproduces dx of shape H, W
    extra_h = h - ((p - 1) * st + r_ - 2 * pad)
    extra_w = wi - ((q - 1) * st + s_ - 2 * pad)
    g_dil = jnp.pad(g_dil, ((0, 0), (0, extra_h), (0, extra_w), (0, 0)))
    w_dual = jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)  # (R, S, K, C)
    dx = conv2d_pallas(
        g_dil, w_dual, stride=1, padding=r_ - 1 - pad,
        out_dtype=jnp.float32, interpret=cfg.interpret).astype(x.dtype)

    # --- wgrad: batch-reduce GEMM per (r, s); reduce dim = minibatch -----
    xpad = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    g2 = g.reshape(n * p * q, k)
    dw_rs = []
    for r in range(r_):
        for s in range(s_):
            xs = jax.lax.slice(
                xpad,
                (0, r, s, 0),
                (n, r + (p - 1) * st + 1, s + (q - 1) * st + 1, c),
                (1, st, st, 1),
            ).reshape(n * p * q, c)
            dw_rs.append(BK.matmul_pallas(
                xs.T, g2, out_dtype=jnp.float32,
                interpret=cfg.interpret))
    dw = jnp.stack(dw_rs).reshape(r_, s_, c, k).astype(w.dtype)

    dbias = None
    if bias is not None:
        dbias = g.astype(jnp.float32).sum(axis=(0, 1, 2)).astype(bias.dtype)
    return dx, dw, dbias


_conv_p.defvjp(_conv_fwd, _conv_bwd)


@dispatch.register("conv2d", "pallas", available=dispatch.pallas_available,
                   priority=10)
def _conv2d_pallas_backend(x, w, bias, *, stride, padding, activation,
                           out_dtype, blocks):
    n, h, wi, c = x.shape
    r_, s_, _, k = w.shape
    q = (wi + 2 * padding - s_) // stride + 1
    blk = dispatch.resolve_blocks("conv2d", q, c, k, x.dtype,
                                  backend="pallas", blocks=blocks,
                                  geometry=ConvGeometry(stride, r_, s_))
    cfg = _Cfg(stride, padding, activation, out_dtype, blk,
               dispatch.resolve_interpret(), dispatch.resolve_accum_dtype())
    fn = functools.partial(_conv_p, cfg)
    ctx = dispatch.current_context()
    mesh = _local.kernel_mesh(ctx.mesh)
    if mesh is not None:
        # per shard: out channels on the axes the tile was tuned for,
        # images on the data axes where they divide
        aq, ac, ak = _local.problem_axes("conv2d", q, c, k, mesh,
                                         ctx.axis_specs)
        if aq or ac:
            raise NotImplementedError(
                "conv2d: the Pallas kernel cannot run with its pixel or "
                "input-channel dim sharded; pin it to the xla backend")
        dp = dp_axes(mesh)
        an = dp if _local.shard_count(n, dp, mesh) > 1 else None
        fn = _local.per_shard(
            fn, mesh, (P(an, None, None, None), P(None, None, None, ak),
                       P(ak)), P(an, None, None, ak))
    return fn(x, w, bias)


@dispatch.register("conv2d", "xla")
def _conv2d_xla_backend(x, w, bias, *, stride, padding, activation,
                        out_dtype, blocks):
    del blocks  # tiling is an XLA-internal decision on this path
    return R.conv2d_ref(
        x, w, bias, stride=stride, padding=padding, activation=activation,
        out_dtype=out_dtype)


def conv2d(
    x,
    w,
    bias=None,
    *,
    stride: int = 1,
    padding: int = 0,
    activation: str = "none",
    out_dtype=None,
    backend: str | None = None,
    blocks: ConvBlocks | None = None,
):
    """Direct convolution via batch-reduce GEMM. NHWC x RSCK -> NHWC.

    ``blocks`` (a ``ConvBlocks``) is the explicit tier-1 geometry override;
    by default the tile resolves through ``dispatch.resolve_blocks`` under
    the active ``repro.use(blocks_policy=...)`` — and per-shard under
    ``repro.use(mesh=...)``, where the out-channel dim (the canonical
    ``k``) localizes over the model axis before tuning.
    """
    impl = dispatch.get_impl("conv2d", backend)
    return impl(x, w, bias, stride=stride, padding=padding,
                activation=activation, out_dtype=out_dtype, blocks=blocks)
