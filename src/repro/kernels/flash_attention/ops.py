"""Differentiable flash-attention entry point with backend dispatch.

Training is a first-class fused workload: on the Pallas path the forward
kernel saves the per-row log-sum-exp statistics as VJP residuals, and the
backward runs the fused Pallas kernels (``bwd.py``) — the `delta`
precompute plus dK/dV and dQ, each a batch-reduce GEMM loop over the
other axis.  Backward tile geometry resolves through
``dispatch.resolve_blocks("flash_attention_bwd", ...)`` at backward trace
time, so a ``repro.use(blocks_policy="autotune")`` context wrapping the
train step (as ``make_train_step`` installs) tunes backward tiles
independently of forward ones.  On the XLA path autodiff handles the
backward natively; the jnp-recompute VJP survives as the registered
``xla`` implementation of the ``flash_attention_bwd`` op — the reference
the fused kernels are tested against, and the deterministic fallback on
platforms without Pallas.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
from jax.sharding import PartitionSpec as P

from repro.core import dispatch
from repro.core.blocking import AttnBlocks, AttnBwdBlocks
from repro.kernels.flash_attention import ref as R
from repro.kernels.flash_attention.bwd import flash_attention_bwd_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.launch.mesh import dp_axes
from repro.sharding import local as _local


class _Cfg(NamedTuple):
    causal: bool
    window: int | None
    scale: float | None
    blocks: AttnBlocks | None
    blocks_bwd: AttnBwdBlocks | None
    interpret: bool
    acc_dtype: object


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_p(cfg: _Cfg, q, k, v):
    return flash_attention_pallas(
        q, k, v, causal=cfg.causal, window=cfg.window, scale=cfg.scale,
        blocks=cfg.blocks, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype)


def _flash_fwd(cfg, q, k, v):
    y, lse = flash_attention_pallas(
        q, k, v, causal=cfg.causal, window=cfg.window, scale=cfg.scale,
        blocks=cfg.blocks, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype, return_residuals=True)
    return y, (q, k, v, y, lse)


def _fused_bwd(q, k, v, y, lse, dy, *, causal, window, scale, blocks,
               interpret, acc_dtype):
    """Resolve the backward tile and run the fused kernels.

    The resolve happens *outside* the kernel's jit so the tile is part of
    the jit key — a different policy context retraces instead of silently
    reusing whatever tile the first ``blocks=None`` trace captured."""
    blk = blocks or dispatch.resolve_blocks(
        "flash_attention_bwd", q.shape[-2], k.shape[-2], q.shape[-1],
        q.dtype, backend="pallas")
    return flash_attention_bwd_pallas(
        q, k, v, y, lse, dy, causal=causal, window=window, scale=scale,
        blocks=blk, interpret=interpret, acc_dtype=acc_dtype)


def _flash_bwd(cfg, res, dy):
    q, k, v, y, lse = res
    # Tile resolution lands here (not at forward dispatch) so
    # inference-only traces never pay for backward tuning, and so the
    # policy active when the cotangent pulls back — e.g. make_train_step's
    # tuned context — is the one that picks the tile.
    return _fused_bwd(q, k, v, y, lse, dy, causal=cfg.causal,
                      window=cfg.window, scale=cfg.scale,
                      blocks=cfg.blocks_bwd, interpret=cfg.interpret,
                      acc_dtype=cfg.acc_dtype)


_flash_p.defvjp(_flash_fwd, _flash_bwd)


def _attention_spec(q, k):
    """``(mesh, spec)`` the kernel runs per shard on, or ``None`` off a
    multi-device mesh: batch on the data axes and heads on the model axis,
    each where it divides (query and KV heads both, so every shard keeps
    whole GQA groups).  The (tq, tk, d) triple stays whole, as
    ``sharding.local.default_axis_specs`` tunes it; a sequence-sharded
    override would need a ring exchange, so it is refused."""
    ctx = dispatch.current_context()
    mesh = _local.kernel_mesh(ctx.mesh)
    if mesh is None:
        return None
    b, hq, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    if any(_local.problem_axes("flash_attention", tq, tk, d, mesh,
                               ctx.axis_specs)):
        raise NotImplementedError(
            "flash_attention: the Pallas kernel cannot run with its "
            "(tq, tk, d) sharded; pin it to the xla backend instead")
    dp = dp_axes(mesh)
    b_ax = dp if _local.shard_count(b, dp, mesh) > 1 else None
    h_ax = ("model" if "model" in mesh.axis_names
            and _local.shard_count(hq, "model", mesh) > 1
            and _local.shard_count(hkv, "model", mesh) > 1 else None)
    return mesh, P(b_ax, h_ax, None, None)


@dispatch.register("flash_attention", "pallas",
                   available=dispatch.pallas_available, priority=10)
def _flash_pallas_backend(q, k, v, *, causal, window, scale, xla_impl,
                          unroll, blocks, blocks_bwd=None):
    del xla_impl, unroll  # XLA-path-only knobs
    tq, d = q.shape[-2:]
    tk = k.shape[-2]
    blk = dispatch.resolve_blocks("flash_attention", tq, tk, d, q.dtype,
                                  backend="pallas", blocks=blocks)
    cfg = _Cfg(causal, window, scale, blk, blocks_bwd,
               dispatch.resolve_interpret(), dispatch.resolve_accum_dtype())
    fn = functools.partial(_flash_p, cfg)
    shard = _attention_spec(q, k)
    if shard is not None:
        mesh, spec = shard
        fn = _local.per_shard(fn, mesh, (spec, spec, spec), spec)
    return fn(q, k, v)


@dispatch.register("flash_attention", "xla")
def _flash_xla_backend(q, k, v, *, causal, window, scale, xla_impl, unroll,
                       blocks, blocks_bwd=None):
    del blocks, blocks_bwd  # tiling is XLA-internal on this path
    if xla_impl == "chunked":
        return R.mha_chunked(q, k, v, causal=causal, window=window,
                             scale=scale, unroll=unroll)
    return R.mha_ref(q, k, v, causal=causal, window=window, scale=scale)


# --------------------------------------------------------------------------
# the backward as a registered op in its own right
# --------------------------------------------------------------------------

@dispatch.register("flash_attention_bwd", "pallas",
                   available=dispatch.pallas_available, priority=10)
def _flash_bwd_pallas_backend(q, k, v, y, lse, dy, *, causal, window, scale,
                              blocks):
    fn = functools.partial(
        _fused_bwd, causal=causal, window=window, scale=scale, blocks=blocks,
        interpret=dispatch.resolve_interpret(),
        acc_dtype=dispatch.resolve_accum_dtype())
    shard = _attention_spec(q, k)
    if shard is not None:
        mesh, spec = shard
        fn = _local.per_shard(fn, mesh, (spec,) * 4 + (P(*spec[:3]), spec),
                              (spec, spec, spec))
    return fn(q, k, v, y, lse, dy)


@dispatch.register("flash_attention_bwd", "xla")
def _flash_bwd_xla_backend(q, k, v, y, lse, dy, *, causal, window, scale,
                           blocks):
    del y, lse, blocks  # the recompute reference rebuilds everything
    _, vjp = jax.vjp(
        lambda q_, k_, v_: R.mha_ref(
            q_, k_, v_, causal=causal, window=window, scale=scale),
        q, k, v)
    return vjp(dy)


def flash_attention_bwd(q, k, v, y, lse, dy, *, causal: bool = True,
                        window: int | None = None,
                        scale: float | None = None,
                        backend: str | None = None,
                        blocks: AttnBwdBlocks | None = None):
    """Standalone fused backward: (dq, dk, dv) from the forward residuals.

    ``jax.grad`` through :func:`flash_attention` reaches this computation
    automatically; the direct entry exists for benchmarks, parity tests,
    and callers managing their own residuals.  ``y``/``lse`` are the
    forward output and per-row log-sum-exp
    (``flash_attention_pallas(..., return_residuals=True)``); the ``xla``
    backend is the jnp-recompute reference and ignores them.
    """
    impl = dispatch.get_impl("flash_attention_bwd", backend)
    return impl(q, k, v, y, lse, dy, causal=causal, window=window,
                scale=scale, blocks=blocks)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, scale: float | None = None,
                    backend: str | None = None, xla_impl: str = "naive",
                    unroll: bool = False,
                    blocks: AttnBlocks | None = None,
                    blocks_bwd: AttnBwdBlocks | None = None,
                    block_q: int | None = None, block_k: int | None = None):
    """xla_impl: 'naive' (full T^2 softmax) or 'chunked' (online softmax,
    flash semantics — the XLA-path memory optimization).

    ``blocks`` (an ``AttnBlocks``) is the explicit tier-1 geometry
    override for the forward tile; ``blocks_bwd`` (an ``AttnBwdBlocks``)
    is the same for the fused backward kernels — by default both resolve
    through ``dispatch.resolve_blocks`` under the active block policy (the
    backward at backward-trace time, under its own
    ``flash_attention_bwd`` cache entry).  Under ``repro.use(mesh=...)``
    the default (tq, tk, d) triple is mesh-invariant — the model axis
    shards heads, which sit outside it — but sequence-parallel setups can
    localize tq/tk via ``use(axis_specs={"flash_attention": ...})``.  The
    old per-dimension ``block_q=``/``block_k=`` kwargs still work but are
    deprecated in favor of ``blocks=``.
    """
    # Validated here, not in the xla impl: a typo'd value must fail the
    # same way whichever backend dispatch resolves to.
    if xla_impl not in ("naive", "chunked"):
        raise ValueError(
            f"unknown xla_impl {xla_impl!r}; expected 'naive' or 'chunked'")
    if block_q is not None or block_k is not None:
        warnings.warn(
            "flash_attention(block_q=..., block_k=...) is deprecated; pass "
            "blocks=AttnBlocks(block_q, block_k) instead",
            DeprecationWarning, stacklevel=2)
        if blocks is not None:
            raise ValueError(
                "pass either blocks= or the deprecated block_q=/block_k=, "
                "not both")
        if block_q is None or block_k is None:
            # A single-dimension override keeps the other dimension on the
            # active block policy instead of a hard-coded default.
            resolved = dispatch.resolve_blocks(
                "flash_attention", q.shape[-2], k.shape[-2], q.shape[-1],
                q.dtype, backend=dispatch.resolve("flash_attention",
                                                  backend))
            block_q = block_q if block_q is not None else resolved.block_q
            block_k = block_k if block_k is not None else resolved.block_k
        blocks = AttnBlocks(block_q=block_q, block_k=block_k)
    impl = dispatch.get_impl("flash_attention", backend)
    return impl(q, k, v, causal=causal, window=window, scale=scale,
                xla_impl=xla_impl, unroll=unroll, blocks=blocks,
                blocks_bwd=blocks_bwd)
