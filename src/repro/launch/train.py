"""Training launcher: mesh + shardings + data + checkpoints + restart loop.

Usage (CPU-scale example; production meshes come from mesh.py):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 50 --batch 8 --seq 64 --mesh 1x1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.shapes import ShapeCfg
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.runtime.compile_cache import enable_compile_cache
from repro.sharding import rules
from repro.sharding.annotate import use_rules
from repro.train import optimizer as opt
from repro.train import train_step as ts


def run(cfg, shape, *, mesh, steps: int, ckpt_dir=None, save_every=50,
        microbatches: int = 1, log_every: int = 10, seed: int = 0):
    ocfg = opt.AdamWCfg()
    step_fn = ts.make_train_step(cfg, ocfg, microbatches=microbatches)

    with mesh, use_rules(rules.activation_rules(mesh), mesh):
        state = ts.init_state(jax.random.PRNGKey(seed), cfg, ocfg)
        state_sh = rules.param_shardings(state, mesh)
        state = jax.tree.map(jax.device_put, state,
                             state_sh)
        jit_step = jax.jit(step_fn, in_shardings=(state_sh, None),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))

        ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            state, start = ckpt.restore(state, shardings=state_sh)
            start += 1
            print(f"[train] resumed from step {start - 1}")

        pipe = TokenPipeline(cfg, shape, seed=seed, start_step=start)
        losses = []
        for step in range(start, steps):
            t0 = time.perf_counter()
            batch = next(pipe)
            state, metrics = jit_step(state, batch)
            losses.append(float(metrics["loss"]))  # waits for the step
            dt = time.perf_counter() - t0
            if step % log_every == 0 or step == steps - 1:
                # the first step's time includes tracing and compiling
                tok_s = batch["tokens"].size / max(dt, 1e-9)
                print(f"[train] step {step} loss {losses[-1]:.4f} "
                      f"step_s {dt:.3f} tokens/s {tok_s:,.0f}")
            if ckpt and step and step % save_every == 0:
                ckpt.save_async(step, state)
        if ckpt:
            ckpt.wait()
        pipe.close()
        return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_NAMES,
                    default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 2x2 (needs that many devices)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    d, m = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    shape = ShapeCfg("cli", "train", args.seq, args.batch)
    _, losses = run(cfg, shape, mesh=mesh, steps=args.steps,
                    ckpt_dir=args.ckpt_dir,
                    microbatches=args.microbatches)
    print(f"[train] first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
