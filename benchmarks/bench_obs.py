"""Roofline accounting for the building-block ops, plus a traced
autotune demo.

For each op at a representative shape the XLA reference path is timed
(the CPU-benchmark baseline, as everywhere in benchmarks/) and combined
with ``repro.obs.op_cost`` — the analytic FLOP count and minimal byte
traffic of one execution — into the two roofline coordinates:

  * achieved GFLOP/s   (FLOPs / measured seconds)
  * arithmetic intensity (FLOPs / byte — the roofline x-axis)

High-intensity ops (big GEMMs, prefill attention) should sit near the
compute roof; low-intensity ones (decode-shaped GEMV-ish matmuls) are
bandwidth-bound no matter the kernel — the accounting makes the regime
of every op legible next to its measured rate.

The ``obs_autotune_traced`` row runs one measured block search under an
installed tracer and reports how many ``autotune.measure`` spans (one
per candidate, each carrying its own GFLOP/s estimate) it recorded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import repro
from benchmarks.common import emit, timeit
from repro import obs
from repro.core.blocking import ConvGeometry
from repro.kernels.brgemm.ops import brgemm, matmul
from repro.kernels.conv2d.ops import conv2d
from repro.kernels.flash_attention.ops import flash_attention


def _roofline(name: str, us: float, cost: obs.OpCost) -> None:
    gflops = cost.flops / (us * 1e-6) / 1e9
    emit(name, us, f"{gflops:.1f}GFLOPs "
                   f"intensity={cost.intensity:.1f}flop/byte")


def run():
    with repro.use(backend="xla"):
        _run()


def _run():
    rng = np.random.default_rng(0)
    f32 = jnp.float32

    # matmul: a compute-heavy square and a decode-shaped skinny one —
    # the two ends of the serving roofline
    for m, n, k in ((256, 256, 256), (4, 1024, 1024)):
        a = jnp.asarray(rng.normal(size=(m, k)), f32)
        b = jnp.asarray(rng.normal(size=(k, n)), f32)
        us = timeit(jax.jit(lambda a, b: matmul(a, b)), a, b)
        _roofline(f"obs_roofline_matmul_{m}x{n}x{k}", us,
                  obs.op_cost("matmul", m, n, k, f32))

    nb, m, n, k = 16, 64, 64, 64
    a = jnp.asarray(rng.normal(size=(nb, m, k)), f32)
    b = jnp.asarray(rng.normal(size=(nb, k, n)), f32)
    us = timeit(jax.jit(lambda a, b: brgemm(a, b)), a, b)
    _roofline(f"obs_roofline_brgemm_{nb}x{m}x{n}x{k}", us,
              obs.op_cost("brgemm", m, n, k, f32, batch=nb))

    # conv2d: ResNet-ish 3x3 (NHWC x RSCK)
    bsz, h, w, c, kk, r, s, stride = 2, 28, 28, 64, 64, 3, 3, 1
    x = jnp.asarray(rng.normal(size=(bsz, h, w, c)), f32)
    wgt = jnp.asarray(rng.normal(size=(r, s, c, kk)), f32) * 0.1
    us = timeit(jax.jit(lambda x, w: conv2d(x, w, stride=stride,
                                            padding=r // 2)), x, wgt)
    # canonical conv triple: (q, c, k) per output row, batch = N * P rows
    p_out = (h + 2 * (r // 2) - r) // stride + 1
    q_out = (w + 2 * (s // 2) - s) // stride + 1
    _roofline(f"obs_roofline_conv2d_{c}x{kk}x{h}x{w}", us,
              obs.op_cost("conv2d", q_out, c, kk, f32,
                          geometry=ConvGeometry(stride=stride, r=r, s=s),
                          batch=bsz * p_out))

    # flash attention: prefill-shaped (batch 1, 4 heads)
    bh, t, d = 4, 128, 64
    q = jnp.asarray(rng.normal(size=(1, bh, t, d)), f32)
    kv = jnp.asarray(rng.normal(size=(1, bh, t, d)), f32)
    us = timeit(jax.jit(lambda q, k, v: flash_attention(q, k, v)),
                q, kv, kv)
    _roofline(f"obs_roofline_flash_attention_{bh}x{t}x{d}", us,
              obs.op_cost("flash_attention", t, t, d, f32, batch=bh))

    # traced measured search: every candidate measurement is a span
    tracer = obs.Tracer()
    a = jnp.asarray(rng.normal(size=(128, 128)), f32)
    b = jnp.asarray(rng.normal(size=(128, 128)), f32)
    with repro.use(backend="pallas",
                   blocks_policy="autotune", tracer=tracer):
        jax.block_until_ready(matmul(a, b))
    measures = tracer.spans("autotune.measure")
    searches = tracer.spans("autotune.search")
    emit("obs_autotune_traced_128", 0.0,
         f"searches={len(searches)} measured_spans={len(measures)}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
