"""Smoke run of the main paths on a TPU, through their normal entry points.

    python chip_smoke.py               # one chip: serve, then train
    python chip_smoke.py --four-chips  # 2x2-mesh trainer vs 1x1, 4 chips

One chip (the default) runs two phases on smollm-135m at its published
widths with random bf16 weights from ``--seed``:

  * serve: 8 greedy requests (prompts of 64-1500 tokens, 32 new tokens
    each) through ``ContinuousEngine`` on a paged pool with chunked
    prefill, on the hardware-default backend (Pallas kernels, compiled).
    Checks that every request completes, that dispatch picked Pallas with
    interpret mode off and no fallback, and that each prompt's
    last-position prefill logits agree with the XLA backend on the same
    chip;
  * train: three steps of ``launch.train.run`` on a 1x1 mesh (batch 4,
    sequence 1024), which runs the fused flash-attention backward.
    Checks that every loss is finite.

``--four-chips`` runs one phase only: three steps of the same trainer on
a 2x2 (data, model) mesh, where every Pallas kernel runs per shard under
``jax.shard_map``, against a 1x1 mesh on the first of the same chips.

Exits nonzero, printing no result, when JAX finds no TPU or any check
fails.  The last line of standard output is one JSON object naming the
device.  The run is one process and starts no other.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_LENS = (64, 1500)
POOL = dict(n_slots=8, max_len=2048, page_size=128, prefill_chunk=512)
REF_LEN = 1536             # prompts right-pad to this for the reference
TRAIN = dict(batch=4, seq=1024, steps=3)

# Pallas vs XLA prefill logits.  Both paths run the same bf16 weights with
# fp32 accumulation and round every activation to bf16 (a relative step of
# 2^-8); they round at different points (the Pallas GEMM tiles the K sum
# and rounds once per output tile, flash attention rescales an online
# softmax), so each of the 30 blocks perturbs the residual stream by about
# one bf16 rounding.  The head maps that to logits of unit scale (std
# about 1): at full width on the CPU backend (Pallas interpreted vs XLA,
# prompts of 64 and 125 tokens) the largest gap over the 49152-entry
# vocabulary was 0.09 and the mean 0.015.  0.25 leaves room for the
# chip's own rounding and for longer prompts, and stays far below the
# spread of the logits, so a wrong kernel (a dropped tile, a bad mask)
# cannot pass.
LOGIT_ATOL = 0.25
# 2x2 vs 1x1 training losses.  The loss is a mean of fp32 cross-entropy
# over 4096 tokens, from bf16 activations; the meshes tile each GEMM for
# its own shard and sum gradients in another order, which moves the
# loss (about ln 49152 = 10.8) by bf16 rounding, averaged over tokens.
# 2e-3 relative is half a bf16 step at that scale.
LOSS_RTOL = 2e-3


def _require(ok, what) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this check runs on the chip "
                 f"only")
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}", flush=True)
    return devices


def _prompts(cfg, seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, cfg.vocab, int(n)).tolist() for n in lens]


def _serve(engine, prompts):
    from repro.serve import Request

    reqs = [Request(prompt=p, max_tokens=NEW_TOKENS, stop_tokens=())
            for p in prompts]
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    return [out[rid] for rid in sorted(out)], time.perf_counter() - t0


def _prefill_logits(cfg, params, prompts, backend):
    """Last-position prefill logits of each prompt on ``backend``; prompts
    right-pad to one length, so each backend compiles once."""
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch
    from repro.models import api

    def f(p, tokens, logit_pos):
        with dispatch.use(backend=backend):
            logits, _ = api.prefill(p, {"tokens": tokens}, cfg,
                                    api.init_cache(cfg, 1, REF_LEN),
                                    logit_pos=logit_pos)
        return logits[0]

    fn = jax.jit(f)
    rows = []
    for prompt in prompts:
        tokens = np.zeros((1, REF_LEN), np.int32)
        tokens[0, :len(prompt)] = prompt
        rows.append(np.asarray(fn(params, jnp.asarray(tokens),
                                  jnp.int32(len(prompt) - 1))))
    return np.stack(rows)


def serve_phase(cfg, params, seed):
    from repro.core import dispatch
    from repro.obs import telemetry
    from repro.serve import ContinuousEngine, PoolConfig

    prompts = _prompts(cfg, seed)
    print(f"serve: {N_REQUESTS} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each",
          flush=True)
    telemetry.TELEMETRY.reset()
    engine = ContinuousEngine(cfg, params, PoolConfig(**POOL))
    streams, cold_s = _serve(engine, prompts)
    again, warm_s = _serve(engine, prompts)
    print(f"serve: pallas engine cold (compile + run) {cold_s:.3f} s, "
          f"warm rerun {warm_s:.3f} s", flush=True)
    done = sum(len(s) == NEW_TOKENS for s in streams)
    print(f"serve: {done}/{N_REQUESTS} requests completed with "
          f"{NEW_TOKENS} tokens; warm rerun identical: {again == streams}",
          flush=True)
    _require(done == N_REQUESTS,
             f"stream lengths {[len(s) for s in streams]}")

    snap = telemetry.TELEMETRY.snapshot()
    pallas = sum(n for (_, b), n in snap["op_dispatch"].items()
                 if b == "pallas")
    backend, interpret = dispatch.resolve("matmul"), \
        dispatch.resolve_interpret()
    print(f"serve: matmul backend={backend} interpret={interpret} "
          f"pallas dispatches={pallas} fallbacks={snap['fallbacks']}",
          flush=True)
    _require(backend == "pallas" and interpret is False,
             f"backend={backend} interpret={interpret}")
    _require(pallas > 0 and not snap["fallbacks"], f"telemetry {snap}")

    t0 = time.perf_counter()
    got = _prefill_logits(cfg, params, prompts, None)
    t1 = time.perf_counter()
    ref = _prefill_logits(cfg, params, prompts, "xla")
    t2 = time.perf_counter()
    gap = np.abs(got - ref).max(axis=-1)
    top = int((got.argmax(-1) == ref.argmax(-1)).sum())
    print(f"serve: prefill logits pallas {t1 - t0:.3f} s, xla {t2 - t1:.3f} "
          f"s (each compiles once); max |pallas - xla| per prompt "
          f"{np.round(gap, 5).tolist()} (tolerance {LOGIT_ATOL}); "
          f"xla logit std {float(ref.std()):.4f}; same argmax "
          f"{top}/{N_REQUESTS}", flush=True)
    _require(np.isfinite(got).all() and np.isfinite(ref).all(),
             "non-finite prefill logits")
    _require(float(gap.max()) <= LOGIT_ATOL,
             f"logit gap {gap.max()} > {LOGIT_ATOL}")

    xla = ContinuousEngine(cfg, params, PoolConfig(**POOL), backend="xla")
    ref_streams, ref_s = _serve(xla, prompts)
    agree = sum(a == b for a, b in zip(streams, ref_streams))
    # random weights give near-flat logits, so a rounding-level gap can
    # flip a greedy pick; after that the streams part for good
    common = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                   len(a)) for a, b in zip(streams, ref_streams)]
    print(f"serve: xla engine cold {ref_s:.3f} s; greedy streams equal to "
          f"the xla engine's: {agree}/{N_REQUESTS}; tokens equal before "
          f"the first difference {common}", flush=True)


def train_phase(cfg, mesh_shape, devices, seed):
    """Losses of ``TRAIN['steps']`` steps of ``launch.train.run``."""
    from repro.configs.shapes import ShapeCfg
    from repro.launch.mesh import make_mesh
    from repro.launch.train import run

    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
    shape = ShapeCfg("chip_smoke", "train", TRAIN["seq"], TRAIN["batch"])
    t0 = time.perf_counter()
    _, losses = run(cfg, shape, mesh=mesh, steps=TRAIN["steps"],
                    log_every=1, seed=seed)
    print(f"train {mesh_shape[0]}x{mesh_shape[1]}: {TRAIN['steps']} steps "
          f"in {time.perf_counter() - t0:.3f} s (step 0 includes compile); "
          f"losses {losses}", flush=True)
    _require(len(losses) == TRAIN["steps"] and np.isfinite(losses).all(),
             f"losses {losses}")
    return losses


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh trainer against 1x1")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = _tpu_devices()
    import jax

    from repro import configs
    from repro.models import api
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = configs.get(ARCH)
    if args.four_chips:
        if len(devices) < 4:
            sys.exit(f"chip_smoke: --four-chips needs 4 devices, found "
                     f"{len(devices)}")
        l4 = train_phase(cfg, (2, 2), devices[:4], args.seed)
        l1 = train_phase(cfg, (1, 1), devices[:1], args.seed)
        rel = np.abs(np.subtract(l4, l1)) / np.abs(l1)
        print(f"train: 2x2 vs 1x1 relative loss gap "
              f"{rel.tolist()} (tolerance {LOSS_RTOL})", flush=True)
        _require(float(rel.max()) <= LOSS_RTOL,
                 f"loss gap {rel.max()} > {LOSS_RTOL}")
    else:
        t0 = time.perf_counter()
        # one program: eager init dispatches (and compiles) op by op
        params = jax.jit(functools.partial(api.init_params, cfg=cfg))(
            jax.random.PRNGKey(args.seed))
        n = sum(x.size for x in jax.tree.leaves(params))
        dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
        print(f"params: {ARCH} {n:,} ({dtypes}) initialised in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        serve_phase(cfg, params, args.seed)
        del params
        train_phase(cfg, (1, 1), devices[:1], args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
