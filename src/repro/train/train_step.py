"""The jit-able train step: loss -> grads -> (optional compression /
accumulation) -> AdamW update.

Mixed precision: the fp32 master copy lives in the optimizer state; the
compute-dtype (usually bf16) working params are re-cast from it every step
(cheap, sharded).  Microbatch gradient accumulation loops with ``lax.scan``
so compute overlaps the reduce-scatter XLA schedules across microbatches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchCfg
from repro.core import dispatch
from repro.models import api
from repro.sharding import annotate
from repro.train import optimizer as opt
from repro.train.schedule import warmup_cosine
from repro.distributed.collectives import compress_grads, decompress_grads


def make_train_step(cfg: ArchCfg, ocfg: opt.AdamWCfg, *,
                    microbatches: int = 1, grad_compression: str = "none",
                    backend: str | None = None, blocks_policy=None,
                    accum_dtype=None, mesh=None, axis_specs=None):
    """Returns train_step(state, batch) -> (state, metrics).

    ``blocks_policy``/``accum_dtype`` scope the whole step's kernels —
    forward *and* backward: the context wraps the full value_and_grad, so
    the conv dgrad/wgrad duals and the fused flash-attention backward
    (its ``flash_attention_bwd`` tile, resolved at backward trace time)
    tune under the same policy (e.g. ``blocks_policy="autotune"``
    measures every GEMM/conv/attention fwd+bwd tile at first trace;
    ``accum_dtype=jnp.bfloat16`` trades accumulator precision for VMEM
    headroom).

    ``mesh`` makes every block resolution per-shard (tiles are tuned for
    the local problem each device runs, not the global shape — see
    ``repro.sharding.local``); when not given, the mesh the launcher
    installed via ``sharding.annotate.use_rules`` is captured at trace
    time, so the dry-run/production path is mesh-aware without extra
    plumbing.  ``axis_specs`` overrides per-op triple sharding."""

    def loss_of(params, batch):
        return api.loss_fn(params, batch, cfg)

    def train_step(state, batch):
        # Execution configuration scopes through the context (captured
        # when the surrounding jit traces).  It wraps the whole step — not
        # just the loss — so the custom-VJP backward rules (dgrad/wgrad
        # kernels, traced when value_and_grad pulls back cotangents)
        # resolve their block geometry under the same tuned context.
        step_mesh = mesh if mesh is not None else annotate.current_mesh()
        # The span brackets the python-side step: per-call when run
        # eagerly, the (expensive, once) trace when the caller jits —
        # either way the dispatch/autotune events it contains show which
        # kernels this step resolved and how.
        with obs.span("train_step", microbatches=microbatches,
                      compression=grad_compression):
            with dispatch.use(backend=backend, blocks_policy=blocks_policy,
                              accum_dtype=accum_dtype, mesh=step_mesh,
                              axis_specs=axis_specs):
                return _train_step(state, batch)

    def _train_step(state, batch):
        with jax.named_scope("optimizer"):
            params = opt.cast_params(state["opt"], cfg.dtype)

        if microbatches > 1:
            def micro(acc, mb):
                (_, metrics), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, mb)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, metrics

            split = jax.tree.map(
                lambda x: x.reshape((microbatches,
                                     x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, metrics = jax.lax.scan(micro, zeros, split)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (_, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)

        if grad_compression != "none":
            grads, scales = compress_grads(grads, kind=grad_compression)
            grads = decompress_grads(grads, scales, kind=grad_compression)

        with jax.named_scope("optimizer"):
            lr_scale = warmup_cosine(state["opt"]["step"])
            new_opt, opt_metrics = opt.adamw_update(grads, state["opt"],
                                                    ocfg, lr_scale)
        metrics = {**metrics, **opt_metrics}
        return {"opt": new_opt}, metrics

    return train_step


def init_state(key, cfg: ArchCfg, ocfg: opt.AdamWCfg):
    params = api.init_params(key, cfg)
    return {"opt": opt.adamw_init(params, ocfg)}


def abstract_state(cfg: ArchCfg, ocfg: opt.AdamWCfg):
    """ShapeDtypeStruct state tree (dry-run: no allocation)."""
    return jax.eval_shape(
        functools.partial(init_state, jax.random.PRNGKey(0), cfg, ocfg))
