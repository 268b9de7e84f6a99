"""Quantized-GEMM routing + XLA fake-quant reference.

This sits between the public entry points (``ops.py``) and the two
execution paths for quantized GEMMs:

  * the Pallas kernels (``quant_kernel.py``) — int8/fp8 storage, fused
    dequant epilogue, quant-aware block tuning;
  * the XLA reference here — the capability-fallback path and the parity
    oracle.  Int8 mirrors the kernel *exactly* (same int8 operands, same
    int32 accumulation, same fp32 dequant epilogue) so pallas-vs-xla
    parity tests can use tight tolerances; fp8 upcasts the quantized
    storage to fp32 before the dot (CPU has no fp8 matmul units) — the
    values are identical since every fp8 number is exactly representable
    in fp32.

Routing rules (``active_quant``): an explicit ``quant=`` call argument
wins, else the ambient ``repro.use(quant=...)`` context, else a
pre-quantized :class:`~repro.core.quantize.QuantizedTensor` weight
implies its own config.  Backend choice reuses the dispatch resolution
for the op, then applies the quant capability gate: int8 runs wherever
the pallas backend runs (interpret on CPU, Mosaic on TPU); fp8 matmul
units exist only on TPU, so off-TPU the quantized op falls back
deterministically to the XLA reference — unless the caller *explicitly*
pinned ``backend="pallas"``, which refuses to fall back, same as
unquantized dispatch.

The quantized path is inference-only (no custom VJP) and does not
support ``c0``/``beta`` accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch, fusion
from repro.core.quantize import (
    QuantConfig,
    QuantizedTensor,
    quantize,
    quantize_weight,
)
from repro.kernels.brgemm import quant_kernel as QK
from repro.sharding import local as _local


def active_quant(w, quant=None) -> QuantConfig | None:
    """The QuantConfig governing this call, or None for full precision.

    Precedence: explicit ``quant=`` call arg > ``use(quant=...)`` context
    > config implied by a pre-quantized weight.  A calibrated param tree
    is therefore inference-ready without any ambient context.
    """
    qcfg = dispatch.resolve_quant(quant)
    if qcfg is not None:
        return qcfg
    if isinstance(w, QuantizedTensor):
        name = str(w.q.dtype)
        return QuantConfig(
            w_dtype=name, a_dtype=name,
            granularity=("per_channel" if w.scale.ndim == w.q.ndim - 1
                         else "per_tensor"))
    return None


def _pallas_quant_ok(qcfg: QuantConfig) -> bool:
    """int8 runs wherever pallas runs; fp8 matmul is TPU-only."""
    if not dispatch.pallas_available():
        return False
    if qcfg.integer:
        return True
    return jax.default_backend() == "tpu"


def _resolve_backend(op: str, backend, qcfg: QuantConfig) -> str:
    """Dispatch resolution + the quant capability gate.

    Explicit ``backend="pallas"`` never falls back (mirror of the
    unquantized rule); everything else degrades to the XLA reference
    when the pallas variant can't run this config here.
    """
    if "int8" in (qcfg.w_dtype, qcfg.a_dtype) and qcfg.w_dtype != qcfg.a_dtype:
        raise NotImplementedError(
            f"mixed integer/float quant storage (w={qcfg.w_dtype}, "
            f"a={qcfg.a_dtype}) has no accumulator dtype; use matching "
            f"int8 or fp8 families")
    name = dispatch.resolve(op, backend)
    if name != "pallas":
        return name
    if _pallas_quant_ok(qcfg):
        return "pallas"
    if backend == "pallas":
        raise RuntimeError(
            f"backend='pallas' was requested explicitly but the quantized "
            f"{op} ({qcfg.tag()}) is not available on "
            f"{jax.default_backend()!r}; fp8 GEMM requires TPU")
    return "xla"


def _check_no_accum(op: str, c0, beta: float):
    if c0 is not None and float(beta) != 0.0:
        raise NotImplementedError(
            f"quantized {op} does not support c0/beta accumulation; "
            f"run the epilogue-accumulating call in full precision")


def _weight_qparams(w, qcfg: QuantConfig, *, batch_shared: bool = False):
    """Quantized storage + per-output-channel fp32 scales for a weight.

    Returns ``(wq, sw)`` with ``sw`` broadcast to the kernel's expected
    per-channel vector: ``(n,)`` for 2-D weights (scalar per-tensor
    scales broadcast), ``(B, n)`` for stacked per-batch weights unless
    ``batch_shared`` (the brgemm reduction) requires one shared vector.
    """
    n = w.shape[-1]
    if isinstance(w, QuantizedTensor):
        if str(w.q.dtype) != qcfg.w_dtype:
            raise ValueError(
                f"pre-quantized weight storage {w.q.dtype} does not match "
                f"QuantConfig.w_dtype={qcfg.w_dtype}")
        wq, sw = w.q, w.scale
    else:
        qt = quantize_weight(
            w, QuantConfig(w_dtype=qcfg.w_dtype, a_dtype=qcfg.a_dtype,
                           granularity=qcfg.granularity))
        wq, sw = qt.q, qt.scale
    if wq.ndim == 2:
        return wq, jnp.broadcast_to(jnp.atleast_1d(sw), (n,))
    # stacked (B, k, n) weights
    if batch_shared:
        if sw.ndim != 0:
            raise ValueError(
                "brgemm sums int32 products across the whole (B, k) "
                "reduction, so weight scales must be batch-shared; "
                "calibrate stacked brgemm weights with per-tensor "
                "granularity, or pass the full-precision weight and let "
                "the op quantize dynamically")
        return wq, jnp.broadcast_to(jnp.atleast_1d(sw), (n,))
    nb = wq.shape[0]
    if sw.ndim == 0:
        return wq, jnp.broadcast_to(sw, (nb, n))
    if sw.ndim == 1:  # per-batch per-tensor (B,)
        return wq, jnp.broadcast_to(sw[:, None], (nb, n))
    return wq, sw  # (B, n)


def _quantize_act(x, qcfg: QuantConfig, *, axis):
    """Dynamic activation quantization; scales keep the unreduced dims."""
    if qcfg.a_granularity == "per_tensor":
        axis = None
    xq, sx = quantize(x, qcfg.a_dtype, axis=axis)
    return xq, sx


def _dequant_epilogue(acc, scale2d, bias, alpha, activation, out_dtype):
    acc = acc.astype(jnp.float32) * scale2d * jnp.float32(alpha)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    acc = fusion.apply(activation, acc)
    return acc.astype(out_dtype)


def _ref_dot(xq, wq, qcfg: QuantConfig):
    """The reference contraction: int32 dot for int8 (bit-identical to the
    kernel), fp32 upcast for fp8 (identical values, CPU-safe)."""
    if qcfg.integer:
        return jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------

def matmul_q(x, w, bias=None, c0=None, *, activation="none", alpha=1.0,
             beta=0.0, out_dtype=None, backend=None, blocks=None,
             qcfg: QuantConfig):
    """Quantized C = act(alpha * dequant(Xq @ Wq) + bias).  x: (m, k)."""
    _check_no_accum("matmul", c0, beta)
    out_dtype = out_dtype or x.dtype
    name = _resolve_backend("matmul", backend, qcfg)
    xq, sx = _quantize_act(x, qcfg, axis=(-1,))
    wq, sw = _weight_qparams(w, qcfg)
    m = x.shape[0]
    sx = jnp.broadcast_to(jnp.atleast_1d(sx), (m,))
    if name == "pallas":
        n, k = wq.shape[-1], wq.shape[-2]
        blk = dispatch.resolve_blocks("matmul", m, n, k, wq.dtype,
                                      backend="pallas", blocks=blocks,
                                      quant=qcfg)
        fn = _local.per_shard_gemm(
            functools.partial(
                QK.matmul_q_pallas, activation=activation,
                alpha=float(alpha), out_dtype=out_dtype, blocks=blk,
                interpret=dispatch.resolve_interpret()),
            "matmul", m, n, k, dispatch.current_context(),
            lambda am, an: ((P(am, None), P(None, an), P(am), P(an), P(an)),
                            P(am, an)))
        return fn(xq, wq, sx, sw, bias)
    return matmul_q_ref(xq, wq, sx, sw, bias, activation=activation,
                        alpha=alpha, out_dtype=out_dtype, qcfg=qcfg)


def matmul_q_ref(xq, wq, sx, sw, bias=None, *, activation="none", alpha=1.0,
                 out_dtype=jnp.float32, qcfg: QuantConfig):
    """XLA fake-quant reference on already-quantized operands."""
    acc = _ref_dot(xq, wq, qcfg)
    scale2d = sx.astype(jnp.float32)[:, None] * sw.astype(jnp.float32)[None, :]
    return _dequant_epilogue(acc, scale2d, bias, alpha, activation, out_dtype)


# --------------------------------------------------------------------------
# brgemm (stacked blocks, batch-shared scales)
# --------------------------------------------------------------------------

def brgemm_q(a, b, bias=None, c0=None, *, activation="none", alpha=1.0,
             beta=0.0, out_dtype=None, backend=None, blocks=None,
             qcfg: QuantConfig):
    """Quantized batch-reduce GEMM.  a: (B, m, k), b: (B, k, n) -> (m, n).

    Scales are *batch-shared* (absmax over the whole (B, k) panel per
    row/channel): the int32 accumulator sums across the entire reduction
    before the single fused dequant, so per-batch scales would change
    the math, not just the layout.
    """
    _check_no_accum("brgemm", c0, beta)
    out_dtype = out_dtype or a.dtype
    name = _resolve_backend("brgemm", backend, qcfg)
    aq, sa = _quantize_act(a, qcfg, axis=(0, 2))
    m = a.shape[1]
    sa = jnp.broadcast_to(jnp.atleast_1d(sa), (m,))
    if isinstance(b, QuantizedTensor):
        bq, sb = _weight_qparams(b, qcfg, batch_shared=True)
    else:
        w_axis = (0, 1) if qcfg.granularity == "per_channel" else None
        bq, sb = quantize(b, qcfg.w_dtype, axis=w_axis)
        sb = jnp.broadcast_to(jnp.atleast_1d(sb), (b.shape[-1],))
    if name == "pallas":
        n, k = bq.shape[-1], bq.shape[-2]
        blk = dispatch.resolve_blocks("brgemm", m, n, k, bq.dtype,
                                      backend="pallas", blocks=blocks,
                                      quant=qcfg)
        fn = _local.per_shard_gemm(
            functools.partial(
                QK.brgemm_q_pallas, activation=activation,
                alpha=float(alpha), out_dtype=out_dtype, blocks=blk,
                interpret=dispatch.resolve_interpret()),
            "brgemm", m, n, k, dispatch.current_context(),
            lambda am, an: ((P(None, am, None), P(None, None, an), P(am),
                             P(an), P(an)), P(am, an)))
        return fn(aq, bq, sa, sb, bias)
    return brgemm_q_ref(aq, bq, sa, sb, bias, activation=activation,
                        alpha=alpha, out_dtype=out_dtype, qcfg=qcfg)


def brgemm_q_ref(aq, bq, sa, sb, bias=None, *, activation="none", alpha=1.0,
                 out_dtype=jnp.float32, qcfg: QuantConfig):
    if qcfg.integer:
        acc = jnp.einsum("imk,ikn->mn", aq, bq,
                         preferred_element_type=jnp.int32)
    else:
        acc = jnp.einsum("imk,ikn->mn", aq.astype(jnp.float32),
                         bq.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    scale2d = sa.astype(jnp.float32)[:, None] * sb.astype(jnp.float32)[None, :]
    return _dequant_epilogue(acc, scale2d, bias, alpha, activation, out_dtype)


# --------------------------------------------------------------------------
# batched_matmul (per-batch scales, no cross-batch reduction)
# --------------------------------------------------------------------------

def batched_matmul_q(a, b, bias=None, *, activation="none", alpha=1.0,
                     out_dtype=None, backend=None, blocks=None,
                     qcfg: QuantConfig):
    """Quantized strided-batched GEMM.  Per-batch scales: no cross-batch
    reduction, so each batch entry dequants independently.

    2-D broadcast operands (shared A or shared B) route to the XLA
    reference — the pallas quant kernel is 3-D-only.
    """
    out_dtype = out_dtype or a.dtype
    name = _resolve_backend("batched_matmul", backend, qcfg)
    if a.ndim == 2 or getattr(b, "ndim", 3) == 2:
        if backend == "pallas":
            raise RuntimeError(
                "backend='pallas' was requested explicitly but the "
                "quantized batched_matmul requires 3-D operands; "
                "broadcast operands run on the XLA reference")
        name = "xla"
    if name == "xla":
        return _batched_ref_from_raw(a, b, bias, activation=activation,
                                     alpha=alpha, out_dtype=out_dtype,
                                     qcfg=qcfg)
    aq, sa = _quantize_act(a, qcfg, axis=(-1,))
    nb, m = a.shape[0], a.shape[1]
    sa = jnp.broadcast_to(jnp.atleast_2d(sa), (nb, m))
    bq, sb = _weight_qparams(b, qcfg)
    if sb.ndim == 1:
        sb = jnp.broadcast_to(sb[None, :], (nb, sb.shape[0]))
    n, k = bq.shape[-1], bq.shape[-2]
    blk = dispatch.resolve_blocks("batched_matmul", m, n, k, bq.dtype,
                                  backend="pallas", blocks=blocks,
                                  quant=qcfg)
    fn = _local.per_shard_gemm(
        functools.partial(
            QK.batched_matmul_q_pallas, activation=activation,
            alpha=float(alpha), out_dtype=out_dtype, blocks=blk,
            interpret=dispatch.resolve_interpret()),
        "batched_matmul", m, n, k, dispatch.current_context(),
        lambda am, an: ((P(None, am, None), P(None, None, an),
                         P(None, am), P(None, an), P(an)), P(None, am, an)))
    return fn(aq, bq, sa, sb, bias)


def _batched_ref_from_raw(a, b, bias, *, activation, alpha, out_dtype, qcfg):
    """Quantize raw (possibly broadcast-2-D) operands and run the ref."""
    aq, sa = _quantize_act(a, qcfg, axis=(-1,))
    sa = jnp.broadcast_to(jnp.atleast_1d(sa), a.shape[:-1])
    if isinstance(b, QuantizedTensor):
        bq, sb = _weight_qparams(b, qcfg)
    else:
        w_axis = (-2,) if qcfg.granularity == "per_channel" else None
        bq, sb = quantize(b, qcfg.w_dtype, axis=w_axis)
    if sb.ndim == 0:
        sb = jnp.broadcast_to(sb, (b.shape[-1],))
    return batched_matmul_q_ref(aq, bq, sa, sb, bias, activation=activation,
                                alpha=alpha, out_dtype=out_dtype, qcfg=qcfg)


def batched_matmul_q_ref(aq, bq, sa, sb, bias=None, *, activation="none",
                         alpha=1.0, out_dtype=jnp.float32,
                         qcfg: QuantConfig):
    """Reference C_i = dequant(Aq_i @ Bq_i).  Operands may be broadcast
    2-D; scales carry matching leading dims."""
    if qcfg.integer:
        pet = jnp.int32
        aq32, bq32 = aq, bq
    else:
        pet = jnp.float32
        aq32, bq32 = aq.astype(jnp.float32), bq.astype(jnp.float32)
    if aq.ndim == 2:
        acc = jnp.einsum("mk,ikn->imn", aq32, bq32,
                         preferred_element_type=pet)
    elif bq.ndim == 2:
        acc = jnp.einsum("imk,kn->imn", aq32, bq32,
                         preferred_element_type=pet)
    else:
        acc = jnp.einsum("imk,ikn->imn", aq32, bq32,
                         preferred_element_type=pet)
    sa = sa.astype(jnp.float32)
    sb = sb.astype(jnp.float32)
    row = sa[..., :, None] if sa.ndim >= 1 else sa
    col = sb[..., None, :] if sb.ndim >= 1 else sb
    scale = row * col
    return _dequant_epilogue(acc, scale, bias, alpha, activation, out_dtype)
