"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The layer stack is split into S stages along a "stage" mesh axis; each
microbatch flows stage -> stage via ``jax.lax.ppermute``.  The schedule is
the classic GPipe loop of (S + M - 1) ticks for M microbatches: stage s
computes microbatch m at tick s + m, so the collective_permute overlaps the
next tick's compute (XLA schedules the permute async).

This substrate is exercised at small scale in tests (CPU, 4 stages); the
production meshes here use DP x TP because all 10 assigned archs fit that
way on 512 chips — PP becomes necessary beyond ~1T dense params (DESIGN.md
Sec. 4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_params, x, layer_fn, *, mesh, n_microbatches: int,
                   axis: str = "stage"):
    """Run ``layer_fn(params, x)`` as a pipeline over mesh axis ``axis``.

    stage_params: pytree whose leaves have a leading stage dim (sharded on
    ``axis``); x: (M, mb, ...) microbatched global input (replicated).
    Returns y with the same shape as x.
    """
    n_stages = mesh.shape[axis]
    m = n_microbatches
    assert x.shape[0] == m

    def stage_body(params, x_local):
        # params: this stage's slice (leading dim 1); x_local: full (M, ...)
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_stages + m - 1

        buf = jnp.zeros_like(x_local[0])
        outputs = jnp.zeros_like(x_local)

        def tick(carry, t):
            buf, outputs = carry
            mb_idx = t - stage
            # stage 0 ingests microbatch t from the global input
            inp = jnp.where(
                stage == 0,
                x_local[jnp.clip(t, 0, m - 1)],
                buf)
            active = (mb_idx >= 0) & (mb_idx < m)
            y = layer_fn(params, inp)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch
            outputs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, m - 1)].set(y),
                lambda o: o,
                outputs)
            # everyone forwards to the next stage
            buf_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf_next, outputs), None

        (buf, outputs), _ = jax.lax.scan(
            tick, (buf, outputs), jnp.arange(n_ticks))
        # all-reduce across stages so every stage returns the full output
        # (only the last stage holds real data; others hold zeros)
        outputs = jnp.where(stage == n_stages - 1, outputs,
                            jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    f = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False)
    return f(stage_params, x)
