"""Public, differentiable entry points for the batch-reduce GEMM kernel.

Backend dispatch goes through ``repro.core.dispatch``: each primitive
(``matmul``, ``brgemm``, ``batched_matmul``) registers two backends,

  * ``pallas``  — the Pallas TPU kernel (kernel.py). On CPU it runs in
    interpret mode (Python evaluation of the kernel body) for correctness
    validation; on TPU it compiles via Mosaic.
  * ``xla``     — the pure-jnp reference (ref.py). Bit-comparable numerics
    (fp32 accumulation, identical epilogues). This path is used for the
    512-device dry-run and CPU-scale smoke tests, where interpreting a
    Python kernel under a production mesh is meaningless.

and the ``backend=`` kwarg is the explicit-call-argument tier of the
dispatch precedence (call arg > context > env > hardware default).  Block
geometry and interpret mode resolve through the active
``repro.use(...)`` context; block selection is memoized in the dispatch
tuning cache keyed (op, backend, shapes, dtype, policy, mesh signature).
The (m, n, k) each entry point reports to ``resolve_blocks`` is the
*global* problem it was called with — under ``repro.use(mesh=...)``
dispatch maps it to the per-device local shard before tuning, so the same
call site gets global-shape tiles in single-device runs and per-shard
tiles under a production mesh with no threading here.  Under a concrete
multi-device mesh the Pallas kernel then runs on exactly that shard, inside
``jax.shard_map``: rows on the data axes, the out dim on the model axis,
and the contraction operands gathered first.

The custom VJP expresses the backward passes through the *same* building
block, mirroring the paper's claim that fwd/bwd/upd all reduce to
batch-reduce GEMM calls:
    dX = dPre @ W^T        (brgemm over K-blocks)
    dW = X^T @ dPre        (brgemm: reduction dim = minibatch, cf. paper 4.1.1 "upd")
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch, fusion
from repro.core.blocking import Blocks
from repro.core.dispatch import (  # noqa: F401  (deprecated shims, re-exported)
    resolve_backend,
    set_default_backend,
)
from repro.kernels.brgemm import kernel as K
from repro.kernels.brgemm import quant as Q
from repro.kernels.brgemm import ref as R
from repro.sharding import local as _local


class _Cfg(NamedTuple):
    activation: str
    alpha: float
    beta: float
    out_dtype: object
    blocks: Blocks | None
    interpret: bool
    acc_dtype: object


class LayerWeight:
    """Layer ``layer`` (an int32 scalar) of a layer-stacked weight ``stack``
    (L, k, n), left in its stack.  ``matmul`` on the Pallas backend reads
    the layer's panels where they lie; elsewhere, or where its blocks
    would pad the weight, it takes the layer out first (a copy).  Serving
    layer scans pass GEMM weights this way: a weight sliced out of the
    scanned stack is copied before every Pallas call."""

    def __init__(self, stack, layer):
        self.stack, self.layer = stack, layer

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def dtype(self):
        return self.stack.dtype

    def take(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.layer,
                                            keepdims=False)


def _make_cfg(op, m, n, k, dtype, activation, alpha, beta, out_dtype,
              blocks) -> _Cfg:
    """Resolve context-dependent knobs (trace-time) into a hashable config."""
    blk = dispatch.resolve_blocks(op, m, n, k, dtype, backend="pallas",
                                  blocks=blocks)
    return _Cfg(activation, float(alpha), float(beta), out_dtype, blk,
                dispatch.resolve_interpret(), dispatch.resolve_accum_dtype())


# --------------------------------------------------------------------------
# matmul: C = act(alpha * X @ W + beta * C0 + bias)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_p(cfg: _Cfg, x, w, bias, c0):
    return K.matmul_pallas(
        x, w, bias, c0,
        activation=cfg.activation, alpha=cfg.alpha, beta=cfg.beta,
        out_dtype=cfg.out_dtype, blocks=cfg.blocks, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype,
    )


def _matmul_fwd(cfg, x, w, bias, c0):
    y = _matmul_p(cfg, x, w, bias, c0)
    return y, (x, w, bias, c0, y)


def _act_bar(cfg, res, dy):
    """dy * act'(pre) in fp32, recomputing pre only when required."""
    x, w, bias, c0, y = res
    dy32 = dy.astype(jnp.float32)
    if not fusion.needs_preact(cfg.activation):
        return dy32 * fusion.GRAD_FROM_OUTPUT[cfg.activation](
            y.astype(jnp.float32))
    pre = K.matmul_pallas(
        x, w, bias, c0, activation="none", alpha=cfg.alpha, beta=cfg.beta,
        out_dtype=jnp.float32, blocks=cfg.blocks, interpret=cfg.interpret)
    return dy32 * fusion.GRAD_FROM_PREACT[cfg.activation](pre)


def _matmul_bwd(cfg, res, dy):
    x, w, bias, c0, y = res
    g = _act_bar(cfg, res, dy)  # fp32, (m, n)
    galpha = (g * jnp.float32(cfg.alpha)).astype(x.dtype)
    dx = K.matmul_pallas(
        galpha, w.T, interpret=cfg.interpret).astype(x.dtype)
    dw = K.matmul_pallas(
        x.T, galpha, interpret=cfg.interpret).astype(w.dtype)
    dbias = None
    if bias is not None:
        dbias = g.sum(axis=0).astype(bias.dtype)
    dc0 = None
    if c0 is not None:
        dc0 = (g * jnp.float32(cfg.beta)).astype(c0.dtype)
    return dx, dw, dbias, dc0


_matmul_p.defvjp(_matmul_fwd, _matmul_bwd)


@dispatch.register("matmul", "pallas", available=dispatch.pallas_available,
                   priority=10)
def _matmul_pallas_backend(x, w, bias, c0, *, activation, alpha, beta,
                           out_dtype, blocks):
    m, k = x.shape
    n = w.shape[-1]
    cfg = _make_cfg("matmul", m, n, k, x.dtype, activation, alpha, beta,
                    out_dtype, blocks)
    if isinstance(w, LayerWeight):
        ctx = dispatch.current_context()
        if (ctx.mesh is None and k % cfg.blocks.bk == 0
                and n % cfg.blocks.bn == 0):
            return K.matmul_pallas(
                x, w.stack, bias, c0, activation=cfg.activation,
                alpha=cfg.alpha, beta=cfg.beta, out_dtype=cfg.out_dtype,
                blocks=cfg.blocks, interpret=cfg.interpret,
                acc_dtype=cfg.acc_dtype, layer=w.layer)
        w = w.take()
    fn = _local.per_shard_gemm(
        functools.partial(_matmul_p, cfg), "matmul", m, n, k,
        dispatch.current_context(),
        lambda am, an: ((P(am, None), P(None, an), P(an), P(am, an)),
                        P(am, an)))
    return fn(x, w, bias, c0)


@dispatch.register("matmul", "xla")
def _matmul_xla_backend(x, w, bias, c0, *, activation, alpha, beta,
                        out_dtype, blocks):
    del blocks  # tiling is an XLA-internal decision on this path
    if isinstance(w, LayerWeight):
        w = w.take()
    return R.matmul_ref(
        x, w, bias, activation=activation, alpha=alpha, beta=beta, c0=c0,
        out_dtype=out_dtype, acc_dtype=dispatch.resolve_accum_dtype())


def matmul(
    x,
    w,
    bias=None,
    c0=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    backend: str | None = None,
    blocks: Blocks | None = None,
    quant=None,
):
    """Batch-reduce GEMM over K blocks; x may have any leading dims.

    Quantized execution is ambient: an active ``repro.use(quant=...)``
    context, an explicit ``quant=`` spec, or a pre-quantized
    :class:`~repro.core.quantize.QuantizedTensor` weight routes this call
    to the int8/fp8 kernel with its fused dequant epilogue — same
    signature, no call-site changes.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if isinstance(w, LayerWeight) and Q.active_quant(None, quant):
        w = w.take()
    qcfg = Q.active_quant(w, quant)
    if qcfg is not None and quant is None and c0 is not None and beta != 0.0:
        # Accumulator-chained GEMMs (LSTM gates) have no quantized form;
        # an *ambient* context degrades them to full precision.  An
        # explicit quant= arg falls through and raises.
        qcfg = None
        if isinstance(w, Q.QuantizedTensor):
            w = w.dequantize().astype(x.dtype)
    if qcfg is not None:
        y = Q.matmul_q(x2, w, bias, c0, activation=activation, alpha=alpha,
                       beta=beta, out_dtype=out_dtype, backend=backend,
                       blocks=blocks, qcfg=qcfg)
        return y.reshape(*lead, w.shape[-1])
    c02 = c0.reshape(-1, c0.shape[-1]) if c0 is not None else None
    impl = dispatch.get_impl("matmul", backend)
    y = impl(x2, w, bias, c02, activation=activation, alpha=alpha,
             beta=beta, out_dtype=out_dtype, blocks=blocks)
    return y.reshape(*lead, w.shape[-1])


# --------------------------------------------------------------------------
# brgemm (stacked blocks): C = act(alpha * sum_i A_i @ B_i + beta*C0 + bias)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _brgemm_p(cfg: _Cfg, a, b, bias, c0):
    return K.brgemm_stacked_pallas(
        a, b, c0, bias,
        activation=cfg.activation, alpha=cfg.alpha, beta=cfg.beta,
        out_dtype=cfg.out_dtype, blocks=cfg.blocks, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype,
    )


def _brgemm_fwd(cfg, a, b, bias, c0):
    y = _brgemm_p(cfg, a, b, bias, c0)
    return y, (a, b, bias, c0, y)


def _brgemm_bwd(cfg, res, dy):
    a, b, bias, c0, y = res
    dy32 = dy.astype(jnp.float32)
    if not fusion.needs_preact(cfg.activation):
        g = dy32 * fusion.GRAD_FROM_OUTPUT[cfg.activation](
            y.astype(jnp.float32))
    else:
        pre = K.brgemm_stacked_pallas(
            a, b, c0, bias, activation="none", alpha=cfg.alpha, beta=cfg.beta,
            out_dtype=jnp.float32, blocks=cfg.blocks, interpret=cfg.interpret)
        g = dy32 * fusion.GRAD_FROM_PREACT[cfg.activation](pre)
    galpha = (g * jnp.float32(cfg.alpha)).astype(a.dtype)
    # dA_i = g @ B_i^T : batched GEMM with g broadcast (zero-copy index_map)
    da = K.batched_matmul_pallas(
        galpha, jnp.swapaxes(b, -1, -2), interpret=cfg.interpret
    ).astype(a.dtype)
    # dB_i = A_i^T @ g
    db = K.batched_matmul_pallas(
        jnp.swapaxes(a, -1, -2), galpha, interpret=cfg.interpret
    ).astype(b.dtype)
    dbias = g.sum(axis=0).astype(bias.dtype) if bias is not None else None
    dc0 = (g * jnp.float32(cfg.beta)).astype(c0.dtype) if c0 is not None else None
    return da, db, dbias, dc0


_brgemm_p.defvjp(_brgemm_fwd, _brgemm_bwd)


@dispatch.register("brgemm", "pallas", available=dispatch.pallas_available,
                   priority=10)
def _brgemm_pallas_backend(a, b, bias, c0, *, activation, alpha, beta,
                           out_dtype, blocks):
    _, m, k = a.shape
    n = b.shape[-1]
    cfg = _make_cfg("brgemm", m, n, k, a.dtype, activation, alpha, beta,
                    out_dtype, blocks)
    fn = _local.per_shard_gemm(
        functools.partial(_brgemm_p, cfg), "brgemm", m, n, k,
        dispatch.current_context(),
        lambda am, an: ((P(None, am, None), P(None, None, an), P(an),
                         P(am, an)), P(am, an)))
    return fn(a, b, bias, c0)


@dispatch.register("brgemm", "xla")
def _brgemm_xla_backend(a, b, bias, c0, *, activation, alpha, beta,
                        out_dtype, blocks):
    del blocks
    return R.brgemm_ref(
        a, b, c0, bias, activation=activation, alpha=alpha, beta=beta,
        out_dtype=out_dtype, acc_dtype=dispatch.resolve_accum_dtype())


def brgemm(
    a,
    b,
    bias=None,
    c0=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    beta: float = 0.0,
    out_dtype=None,
    backend: str | None = None,
    blocks: Blocks | None = None,
    quant=None,
):
    """The paper's batch-reduce GEMM. a: (B, m, k), b: (B, k, n) -> (m, n)."""
    qcfg = Q.active_quant(b, quant)
    if qcfg is not None and quant is None and c0 is not None and beta != 0.0:
        qcfg = None  # see matmul: ambient quant skips accumulator chains
        if isinstance(b, Q.QuantizedTensor):
            b = b.dequantize().astype(a.dtype)
    if qcfg is not None:
        return Q.brgemm_q(a, b, bias, c0, activation=activation, alpha=alpha,
                          beta=beta, out_dtype=out_dtype, backend=backend,
                          blocks=blocks, qcfg=qcfg)
    impl = dispatch.get_impl("brgemm", backend)
    return impl(a, b, bias, c0, activation=activation, alpha=alpha,
                beta=beta, out_dtype=out_dtype, blocks=blocks)


# --------------------------------------------------------------------------
# batched_matmul: C_i = act(alpha * A_i @ B_i + bias)   (baseline, no reduce)
# --------------------------------------------------------------------------

@dispatch.register("batched_matmul", "pallas",
                   available=dispatch.pallas_available, priority=10)
def _batched_matmul_pallas_backend(a, b, bias, *, activation, alpha,
                                   out_dtype, blocks):
    m, k = a.shape[-2:]
    n = b.shape[-1]
    blk = dispatch.resolve_blocks("batched_matmul", m, n, k, a.dtype,
                                  backend="pallas", blocks=blocks)
    fn = functools.partial(
        K.batched_matmul_pallas, activation=activation, alpha=float(alpha),
        out_dtype=out_dtype, blocks=blk,
        interpret=dispatch.resolve_interpret(),
        acc_dtype=dispatch.resolve_accum_dtype())
    # either operand may be a 2-D one broadcast over the batch
    fn = _local.per_shard_gemm(
        fn, "batched_matmul", m, n, k, dispatch.current_context(),
        lambda am, an: ((P(*(None,) * (a.ndim - 2), am, None),
                         P(*(None,) * (b.ndim - 1), an), P(an)),
                        P(None, am, an)))
    return fn(a, b, bias)


@dispatch.register("batched_matmul", "xla")
def _batched_matmul_xla_backend(a, b, bias, *, activation, alpha, out_dtype,
                                blocks):
    del blocks
    return R.batched_matmul_ref(
        a, b, bias, activation=activation, alpha=alpha, out_dtype=out_dtype,
        acc_dtype=dispatch.resolve_accum_dtype())


def batched_matmul(
    a,
    b,
    bias=None,
    *,
    activation: str = "none",
    alpha: float = 1.0,
    out_dtype=None,
    backend: str | None = None,
    blocks: Blocks | None = None,
    quant=None,
):
    """Strided-batched GEMM baseline (no cross-batch reduction)."""
    qcfg = Q.active_quant(b, quant)
    if qcfg is not None:
        return Q.batched_matmul_q(a, b, bias, activation=activation,
                                  alpha=alpha, out_dtype=out_dtype,
                                  backend=backend, blocks=blocks, qcfg=qcfg)
    impl = dispatch.get_impl("batched_matmul", backend)
    return impl(a, b, bias, activation=activation, alpha=alpha,
                out_dtype=out_dtype, blocks=blocks)
