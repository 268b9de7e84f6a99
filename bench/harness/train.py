"""Training cells: the jitted train step, built as ``launch/train.run``
builds it (``make_train_step``, sharded state, donated), on a 1x1 mesh.

Set-up makes the weights on the device from the seed, builds that one
step object with its state, and drives it through the first
``check_steps`` steps, each on fresh rows of the mix.  The state's step
counter starts at the mix's ``resume_step``, as in a run resumed past
its learning-rate warm-up, so that every checked update moves the
bfloat16 weights the next step computes with.  The losses compared
are those of the first ``loss_steps`` steps: from zero moments, the
full-rate updates move every weight by about the same step whatever
its gradient, so after two of them the loss follows the round-off in
the signs of the smallest gradients, on either side.  It reads what the
checks need before the next step donates the state: the first gradient
as the optimizer got it (from Adam's first moment after step 1) and the
parameters' change after the last of them.  The window then keeps
stepping the same object until ``seconds`` have passed; every step ends
in ``block_until_ready``.

After the window, with the program's state freed, the plain reference
repeats those first steps in float32 from the same weights and rows.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import statistics
import tempfile
import time

from harness import common, costs, peaks, traffic, trace

TRACE_SECONDS = 10.0


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """Widest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's."""
    names = [n for n in ref if keep is None or n in keep]
    med = statistics.median(ref[n] for n in names)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def reference_readings(model, conf, mix, seed, vocab, *, quant=None,
                       rows=None, stale=False):
    """The reference's losses, first clipped gradient norms and change
    norms over the first ``check_steps`` steps (``quant`` gives the
    control, ``rows`` the half-batch fault, ``stale`` the fault of a step
    that updates its weights but computes with the first ones)."""
    import jax
    import jax.numpy as jnp

    opt = mix["optimizer"]
    first = mix["resume_step"]
    w0 = model.make_weights(conf, seed)
    w32 = w_first = jax.tree.map(lambda x: x.astype(jnp.float32), w0)
    m = jax.tree.map(jnp.zeros_like, w32)
    v = jax.tree.map(jnp.zeros_like, w32)
    losses, g1 = [], None
    for s in range(mix["check_steps"]):
        b = traffic.train_batch(mix, seed, s, vocab)
        sl = slice(None) if rows is None else slice(0, rows)
        loss, g = model.loss_and_grads(
            conf, w_first if stale else w32, b["tokens"][sl],
            b["labels"][sl], quant=quant)
        w32, m, v, gc_ = model.adamw(w32, m, v, g, first + s, opt)
        losses.append(float(loss))
        if s == 0:
            g1 = model.leaf_norms(gc_, program=False)
        del g, gc_
    change = model.leaf_norms(w32, program=False, minus=w0)
    return losses, g1, change


def readings(prog, ref, loss_steps: int) -> dict:
    """The three numbers compared: the losses of the first
    ``loss_steps`` steps, the first gradient, the change."""
    (pl, pg, pc), (rl, rg, rc) = prog, ref
    med = statistics.median(rg.values())
    # leaves that the reference's gradient leaves at nought to rounding
    # move under Adam by round-off alone
    moving = {n for n, x in rg.items() if x >= 1e-3 * med}
    return {"train_loss_gap": max(abs(a - b) / abs(b) for a, b in
                                  zip(pl[:loss_steps], rl[:loss_steps])),
            "first_grad_gap": worst_leaf(pg, rg),
            "change_gap": worst_leaf(pc, rc, keep=moving)}


def run(ctx: common.Context) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.sharding import rules
    from repro.sharding.annotate import use_rules
    from repro.train import optimizer as opt
    from repro.train import train_step as ts

    devs = common.devices(ctx)
    common.log(f"compile cache: {common.enable_caches()}")
    counter = common.CompileCounter()
    conf, mix = ctx.conf, ctx.traffic
    model = common.model_module(conf)
    arch = common.program_cfg(conf)
    dims = costs.dims(arch)
    peak = peaks.peak(devs[0].device_kind) if ctx.require_chip else None
    vocab = conf["vocab_size"]
    o = mix["optimizer"]
    ocfg = opt.AdamWCfg(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                        weight_decay=o["weight_decay"],
                        grad_clip=o["grad_clip"])
    n_tok = mix["batch"] * mix["seq_len"]

    mesh = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    step_fn = ts.make_train_step(arch, ocfg)
    with mesh, use_rules(rules.activation_rules(mesh), mesh):
        params = model.make_weights(conf, ctx.seed, layout=model.to_program)
        state = jax.jit(lambda p: {"opt": {
            **opt.adamw_init(p, ocfg),
            "step": jnp.int32(mix["resume_step"])}})(params)
        state_sh = rules.param_shardings(state, mesh)
        state = jax.tree.map(jax.device_put, state, state_sh)
        jit_step = jax.jit(step_fn, in_shardings=(state_sh, None),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))

        losses, norms = [], []
        for s in range(mix["check_steps"]):
            batch = traffic.train_batch(mix, ctx.seed, s, vocab)
            state, metrics = jit_step(state, batch)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
            if s == 0:
                g1 = model.leaf_norms(state["opt"]["m"], program=True,
                                      scale=1.0 / (1.0 - ocfg.b1))
        change = model.leaf_norms(state["opt"]["master"], program=True,
                                  minus=params)
        del params
        common.log(f"first {len(losses)} losses {losses}, gradient norms "
                   f"{norms}")

        log_dir = (tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace
                   else None)
        profiling = contextlib.ExitStack()
        trace_host = None
        steps, failed = [], 0
        counter.armed = True
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_process
        deadline = t0 + ctx.seconds
        trace_from = deadline - min(TRACE_SECONDS, ctx.seconds)
        s = mix["check_steps"]
        while True:
            t = time.perf_counter()
            if t >= deadline:
                break
            if log_dir and trace_host is None and t >= trace_from:
                profiling.enter_context(trace.capture(log_dir))
                profiling.enter_context(_annotate(trace.WINDOW))
                trace_host = [time.perf_counter(), None]
            with _annotate("bench.step"):
                with _annotate("bench.batch"):
                    batch = traffic.train_batch(mix, ctx.seed, s, vocab)
                state, metrics = jit_step(state, batch)
                loss = jax.block_until_ready(metrics["loss"])
            steps.append((t, time.perf_counter()))
            failed += not math.isfinite(float(loss))
            s += 1
        t_close = time.perf_counter()
        if trace_host is not None:
            trace_host[1] = t_close
        profiling.close()
        counter.armed = False
    window = t_close - t0
    e2e = {"setup_s": setup_s,
           "train_tokens_per_s": len(steps) * n_tok / window}
    common.log(f"window: {window:.3f} s, steps {len(steps)} of "
               f"{mix['batch']}x{mix['seq_len']} tokens")
    common.log(f"compiles in window: {counter.count}")
    observed = {"kind": "train", "dims": dims, "peak": peak,
                "window": (t0, t_close), "steps": steps,
                "batch": mix["batch"], "seq": mix["seq_len"],
                "trace": None, "trace_host": trace_host}
    if log_dir:
        observed["trace"] = trace.reduce(*trace.load_events(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    device = common.device_info(devs)

    # ---- correctness --------------------------------------------------
    del state, jit_step, metrics
    gc.collect()
    prog = (losses, g1, change)
    t_ref = time.perf_counter()
    ref = reference_readings(model, conf, mix, ctx.seed, vocab)
    common.log(f"reference losses {ref[0]} ({time.perf_counter() - t_ref:.1f}"
               f" s)")
    checks = common.Checks(ctx.cell["limits"])
    for name, value in readings(prog, ref, mix["loss_steps"]).items():
        checks.add(name, value)
    control = None
    if ctx.control:
        control = {}
        for what, kw in (("fp8", {"quant": "fp8"}),
                         ("half_batch", {"rows": mix["batch"] // 2}),
                         ("stale_weights", {"stale": True})):
            ctl = common.Checks(ctx.cell["limits"])
            for name, value in readings(reference_readings(
                    model, conf, mix, ctx.seed, vocab, **kw), ref,
                    mix["loss_steps"]).items():
                ctl.add(name, value)
            control[what] = {"correct": ctl.ok, "checks": ctl.rows}
        common.log(f"control readings {control}")
    return {"correct": checks.ok, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "observed": observed, "device": device,
            "checks": checks.rows, "control": control}
