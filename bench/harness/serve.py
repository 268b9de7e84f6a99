"""Serving cells: a closed-loop load generator in front of
``ContinuousEngine``.

Set-up makes the weights on the device from the seed, builds the engine
on the cell's paged pool, and serves one request of every prompt length
of the mix's menu, which compiles every prefill, chunk and decode shape
that the window's new requests use.  It then brings the loop to its
steady mix: the slots take the population a steadily running loop holds
(long requests more often, each part-way through, its served part
standing in its prompt), the other clients queue behind them, and the
engine steps until every slot's request has its first token.  The
window then runs the loop for ``seconds``: each client sends its next
request as its last one finishes.  Every token's host time is stamped
in the engine's ``on_token`` callback, inside the ``step()`` that made
it; ``step()`` syncs with the device, so the stamps are real.

After the window, a sample of the requests served in it, finished or
still running, drawn from the seed and always holding the one with the
longest sequence, is run through the plain reference, and every served
token's logit is compared with the reference's best.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time

import numpy as np

from harness import common, costs, peaks, traffic, trace

TRACE_SECONDS = 10.0   # a traced run profiles the window's last seconds


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run(ctx: common.Context) -> dict:
    from repro import obs
    from repro.obs.tracer import Tracer
    from repro.serve import ContinuousEngine, PoolConfig, Request

    devs = common.devices(ctx)
    common.log(f"compile cache: {common.enable_caches()}")
    counter = common.CompileCounter()
    conf, mix_file = ctx.conf, ctx.traffic
    model = common.model_module(conf)
    arch = common.program_cfg(conf)
    dims = costs.dims(arch)
    peak = peaks.peak(devs[0].device_kind) if ctx.require_chip else None

    params = model.make_weights(conf, ctx.seed, layout=model.to_program)
    pool = PoolConfig(**mix_file["pool"])
    engine = ContinuousEngine(arch, params, pool)
    mix = traffic.ServeMix(mix_file, ctx.seed, conf["vocab_size"])
    warm = mix.warmup_prompts()
    engine.serve([Request(prompt=p, max_tokens=2, stop_tokens=())
                  for p in warm])
    common.log(f"warm-up: prompt lengths {sorted(len(p) for p in warm)}")

    stamps, tokens, sizes, prompts = {}, {}, {}, {}
    done, steps = [], []
    step_kv = []

    def on_token(rid, tok, finished):
        seen = len(tokens[rid])
        if seen:   # a decode step's token: keys read = cache + itself
            step_kv.append(sizes[rid][0] + seen - 1)
        stamps[rid].append(time.perf_counter())
        tokens[rid].append(tok)
        if finished:
            done.append(rid)

    def submit(prompt: list[int], n_out: int) -> int:
        with _annotate("bench.submit"):
            rid = engine.submit(Request(prompt=prompt, max_tokens=n_out,
                                        stop_tokens=()), on_token=on_token)
        stamps[rid], tokens[rid] = [], []
        sizes[rid], prompts[rid] = (len(prompt), n_out), prompt
        return rid

    nxt = 0

    def send_next() -> None:
        nonlocal nxt
        p, o = mix.sizes(nxt)
        submit(mix.prompt(nxt, p), o)
        nxt += 1

    def refill() -> None:
        """Each client whose request finished sends its next one."""
        while done:
            done.pop()
            send_next()

    def step() -> None:
        t0 = time.perf_counter()
        with _annotate("bench.step"):
            engine.step()
        steps.append((t0, time.perf_counter(), list(step_kv)))
        step_kv.clear()

    # the loop's steady mix: the slots' population, then the queue
    population = mix.steady_population(pool.n_slots)
    start = [submit(mix.prompt(i, p + k, stream=9), o - k)
             for i, (p, o, k) in enumerate(population)]
    clients = mix_file["clients_per_slot"] * pool.n_slots
    for _ in range(clients - len(start)):
        send_next()
    while any(not tokens[r] for r in start):
        step()
        refill()
    common.log(f"steady mix: {len(start)} requests part-way in the slots "
               f"(prompt + served {sorted(p + k for p, _, k in population)})"
               f", {clients - len(start)} queued, after {len(steps)} steps")
    steps.clear()

    before = dict(vars(engine.metrics))
    tracer = Tracer(capacity=1 << 22) if ctx.trace else None
    if tracer is not None:
        obs.install(tracer)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    profiling = contextlib.ExitStack()
    trace_host = None

    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    deadline = t0 + ctx.seconds
    trace_from = deadline - min(TRACE_SECONDS, ctx.seconds)
    while True:
        t = time.perf_counter()
        if t >= deadline:
            break
        if log_dir and trace_host is None and t >= trace_from:
            profiling.enter_context(trace.capture(log_dir))
            profiling.enter_context(_annotate(trace.WINDOW))
            trace_host = [time.perf_counter(), None]
        refill()
        step()
    t_close = time.perf_counter()
    if trace_host is not None:
        trace_host[1] = t_close
    profiling.close()
    counter.armed = False
    if tracer is not None:
        obs.install(None)
    window = t_close - t0

    # ---- end-to-end numbers -------------------------------------------
    served = [r for r in stamps if any(t0 <= s <= t_close for s in stamps[r])]
    gaps = [b - a for r in served for a, b in zip(stamps[r], stamps[r][1:])
            if t0 <= a and b <= t_close]
    n_tok = sum(1 for r in served for s in stamps[r] if t0 <= s <= t_close)
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": n_tok / window}
    common.log(f"window: {window:.3f} s, requests served {len(served)}, "
               f"finished {sum(len(tokens[r]) == sizes[r][1] for r in served)}"
               f", tokens {n_tok}, engine steps {len(steps)}; gaps between "
               f"tokens {len(gaps)}, p99 {common.percentile(gaps, 99)!r} s")
    common.log(f"compiles in window: {counter.count}")

    # ---- per-layer observations ---------------------------------------
    after = vars(engine.metrics)
    counters = {k: after[k] - before[k] for k in
                ("slot_steps", "slot_capacity_steps", "decode_steps",
                 "prefill_chunks", "prefills", "preemptions",
                 "tokens_generated")}
    common.log(f"engine counters over the window: {counters}")
    observed = {"kind": "serve", "dims": dims, "peak": peak,
                "n_slots": pool.n_slots, "window": (t0, t_close),
                "steps": steps, "counters": counters,
                "spans": None, "trace": None, "trace_host": trace_host}
    if tracer is not None:
        observed["spans"] = {
            n: [(s.t0, s.t1, dict(s.attrs)) for s in tracer.spans(n)
                if t0 <= s.t0 <= t_close]
            for n in ("prefill", "prefill.chunk", "decode")}
    if log_dir:
        observed["trace"] = trace.reduce(*trace.load_events(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    device = common.device_info(devs)

    # ---- correctness --------------------------------------------------
    sample = _sample(sorted(served), prompts, tokens,
                     mix_file["check"]["sample"], ctx.seed)
    seqs = [(prompts[r], tokens[r]) for r in sample]
    control_rows = control_error = None
    del engine
    gc.collect()
    if ctx.control:
        try:
            control_rows = _control_argmax(arch, params, seqs)
        except Exception as e:   # a control that crashes sets no reading
            control_error = f"{type(e).__name__}: {e}"
            common.log(f"control failed: {control_error}")
    del params
    gc.collect()
    checks = common.Checks(ctx.cell["limits"])
    t_ref = time.perf_counter()
    gap, ctl_gap = _reference_gaps(model, conf, ctx.seed, seqs, control_rows)
    common.log(f"reference check took {time.perf_counter() - t_ref:.1f} s")
    checks.add("served_logit_gap", gap)
    common.log(f"checked {len(seqs)} requests, {sum(len(t) for _, t in seqs)}"
               f" served tokens, sequences of "
               f"{[len(p) + len(t) for p, t in seqs]}; widest served-token "
               f"gap {gap!r}")
    control = None
    if ctx.control:
        ctl = common.Checks(ctx.cell["limits"])
        if control_rows is not None:
            ctl.add("served_logit_gap", ctl_gap)
        control = {"int8": {"correct": ctl.ok, "checks": ctl.rows,
                            "error": control_error}}
        common.log(f"control (program int8 GEMMs): {control}")
    return {"correct": checks.ok, "attempted": len(served), "failed": 0,
            "e2e": e2e, "observed": observed, "device": device,
            "checks": checks.rows, "control": control}


def _sample(served, prompts, tokens, k: int, seed: int) -> list[int]:
    """The request with the longest sequence (prompt and served tokens)
    and ``k - 1`` others drawn from the seed."""
    if not served:
        return []
    longest = max(served, key=lambda r: (len(prompts[r]) + len(tokens[r]),
                                         -r))
    rest = [r for r in served if r != longest]
    rng = np.random.default_rng([seed, 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _rows(prompt, served) -> tuple[list[int], np.ndarray]:
    """The sequence whose logits predicted each served token, and the
    rows holding those logits."""
    seq = list(prompt) + list(served[:-1])
    return seq, np.arange(len(prompt) - 1, len(seq))


def _control_argmax(arch, params, seqs, pad: int = 2048):
    """The control: the program's own int8 GEMM path over the same
    prompts and served tokens; the token it puts first at each row."""
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch
    from repro.models import api

    def fwd(p, tok):
        with dispatch.use(quant="int8"):
            return api.forward(p, {"tokens": tok}, arch)[0][0]

    fwd = jax.jit(fwd)
    out = []
    for prompt, served in seqs:
        seq, rows = _rows(prompt, served)
        t = -(-len(seq) // pad) * pad
        tok = np.zeros((1, t), np.int32)
        tok[0, :len(seq)] = seq
        logits = fwd(params, jnp.asarray(tok))
        out.append(np.asarray(jnp.argmax(logits[rows], axis=-1)))
    return out


ROW_BLOCK = 512   # rows are padded to a multiple: few shapes, few compiles


def _widest_gap(ref, toks):
    import jax.numpy as jnp

    got = jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    return jnp.max(jnp.max(ref, axis=-1) - got)


def _reference_gaps(model, conf, seed, seqs, control_rows=None):
    """Widest gap, over every served token of the sample, between the
    reference's best logit and its logit of the served token (and of
    the control's first token, where given).  The rows are padded to a
    multiple of ``ROW_BLOCK`` by repeating the last one, which leaves
    every maximum as it is and keeps the compiled shapes the same from
    seed to seed."""
    import jax
    import jax.numpy as jnp

    if not seqs:
        return math.inf, None
    w = model.make_weights(conf, seed)
    gap = jax.jit(_widest_gap)
    worst, ctl = 0.0, 0.0
    for i, (prompt, served) in enumerate(seqs):
        seq, rows = _rows(prompt, served)
        n = -(-len(rows) // ROW_BLOCK) * ROW_BLOCK
        pick = np.minimum(np.arange(n), len(rows) - 1)
        ref = model.logits_at(conf, w, seq, rows[pick], pad=2048)
        worst = max(worst, float(gap(ref, jnp.asarray(
            np.asarray(served, np.int32)[pick]))))
        if control_rows is not None:
            ctl = max(ctl, float(gap(ref, jnp.asarray(
                np.asarray(control_rows[i], np.int32)[pick]))))
    return worst, (ctl if control_rows is not None else None)
