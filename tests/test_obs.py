"""Observability tests: tracer fast path and thread safety, dispatch
telemetry + blocks-source classification, unified autotune STATS, FLOP
accounting, Chrome export round-trip, latency histograms, engine TTFT
breakdown exactness, and the serve-layer span/event wiring."""
import asyncio
import dataclasses
import json
import re
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import configs, obs
from repro.core import autotune, dispatch
from repro.models import api
from repro.obs.telemetry import TELEMETRY
from repro.serve import (
    AsyncFrontend,
    ContinuousEngine,
    EngineReplica,
    EngineRouter,
    LatencyHistogram,
    PoolConfig,
    Request,
    ServeMetrics,
)

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled."""
    obs.install(None)
    yield
    obs.install(None)


@pytest.fixture(scope="module")
def dense():
    cfg = configs.get("smollm-135m").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, 3 + i % 5).tolist(),
                    max_tokens=2 + i % 3, stop_tokens=())
            for i in range(n)]


class FakeClock:
    """Deterministic strictly-increasing clock."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# ---------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------

def test_disabled_fast_path_allocates_nothing():
    assert obs.current_tracer() is None
    # the no-op span is a shared singleton: same object every call
    s1 = obs.span("anything", x=1)
    s2 = obs.span("else")
    assert s1 is s2 is obs.NULL_SPAN
    with s1 as inner:
        assert inner is obs.NULL_SPAN
        inner.set(a=1).event("e")
    obs.event("nothing")     # all no-ops, no error
    obs.annotate(a=2)


def test_span_nesting_and_parent_links():
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            pass
    spans = tr.spans()
    # completion order: children land before parents
    assert [s.name for s in spans] == ["inner", "outer"]
    by_name = {s.name: s for s in spans}
    assert by_name["outer"].parent_id is None
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert inner.span_id != outer.span_id


def test_injectable_clock_durations():
    tr = obs.Tracer(clock=FakeClock(dt=1.0))
    with tr.span("a"):
        pass                       # t0=1, t1=2
    (rec,) = tr.spans("a")
    assert rec.t0 == 1.0 and rec.t1 == 2.0 and rec.duration_s == 1.0


def test_ring_buffer_capacity_bounds_memory():
    tr = obs.Tracer(capacity=8, clock=FakeClock())
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    recs = tr.records()
    assert len(recs) == 8
    assert recs[0].name == "s12" and recs[-1].name == "s19"


def test_events_parent_to_open_span_and_attrs():
    tr = obs.Tracer(clock=FakeClock())
    tr.event("free")                      # outside any span
    with tr.span("work") as sp:
        tr.event("mark", k="v")
        sp.set(extra=1)
    free, mark = tr.events("free")[0], tr.events("mark")[0]
    assert free.span_id is None
    assert mark.span_id == sp.span_id and mark.attrs == {"k": "v"}
    assert tr.spans("work")[0].attrs["extra"] == 1


def test_add_span_synthetic_with_parent():
    tr = obs.Tracer()
    root = tr.add_span("request", 1.0, 5.0, status="done")
    child = tr.add_span("request.queue", 1.0, 2.0, parent_id=root.span_id)
    assert child.parent_id == root.span_id
    assert root.attrs == {"status": "done"}
    assert root.duration_s == 4.0


def test_install_global_and_scoped_precedence():
    g, s = obs.Tracer(), obs.Tracer()
    prev = obs.install(g)
    assert prev is None
    try:
        assert obs.current_tracer() is g
        with obs.activate(s):
            assert obs.current_tracer() is s     # scoped wins
        assert obs.current_tracer() is g
    finally:
        obs.install(None)
    assert obs.current_tracer() is None


def test_repro_use_tracer_scopes_activation():
    tr = obs.Tracer()
    assert obs.current_tracer() is None
    with repro.use(tracer=tr):
        assert obs.current_tracer() is tr
        with obs.span("inside"):
            pass
    assert obs.current_tracer() is None
    assert [s.name for s in tr.spans()] == ["inside"]


def test_tracer_thread_safety_independent_stacks():
    tr = obs.Tracer()
    obs.install(tr)
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        for j in range(25):
            with obs.span(f"outer{i}"):
                with obs.span(f"inner{i}"):
                    pass

    with ThreadPoolExecutor(4) as ex:
        list(ex.map(work, range(4)))
    obs.install(None)
    assert len(tr.spans()) == 4 * 25 * 2
    # each thread nests on its own stack: every inner's parent is an
    # outer of the *same* worker index, recorded on the same thread
    by_id = {s.span_id: s for s in tr.spans()}
    for s in tr.spans():
        if s.name.startswith("inner"):
            parent = by_id[s.parent_id]
            assert parent.name == "outer" + s.name[len("inner"):]
            assert parent.thread == s.thread


# ---------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------

def test_chrome_round_trip(tmp_path):
    tr = obs.Tracer(clock=FakeClock(dt=0.5))
    with tr.span("outer", op="matmul"):
        with tr.span("inner"):
            pass
        tr.event("mark", k=1)
    path = tmp_path / "trace.json"
    n = obs.export_chrome(tr, str(path))
    trace = obs.chrome.load(str(path))
    assert obs.chrome.validate(trace) == n
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    complete = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(complete) == {"outer", "inner"}
    assert complete["outer"]["args"]["op"] == "matmul"
    # timestamps are microseconds relative to the earliest record
    assert complete["outer"]["dur"] == pytest.approx(2.0e6)
    assert complete["inner"]["ts"] >= 0
    instants = [e for e in events if e["ph"] == "i"]
    assert len(instants) == 1 and instants[0]["name"] == "mark"


def test_chrome_validate_rejects_malformed():
    with pytest.raises(ValueError):
        obs.chrome.validate({"nope": []})
    with pytest.raises(ValueError):
        obs.chrome.validate({"traceEvents": [{"name": "x"}]})


def test_chrome_summarize_and_cli(tmp_path, capsys):
    tr = obs.Tracer(clock=FakeClock())
    for _ in range(3):
        with tr.span("step"):
            pass
    assert tr.summary()["step"]["count"] == 3
    path = tmp_path / "t.json"
    obs.export_chrome(tr, str(path))
    table = obs.summarize(obs.chrome.load(str(path)))
    assert "step" in table and "count" in table
    from repro.obs.__main__ import main as obs_main
    obs_main(["summarize", str(path)])
    out = capsys.readouterr().out
    assert "step" in out and str(path) in out


# ---------------------------------------------------------------------
# flops accounting
# ---------------------------------------------------------------------

def test_op_cost_matmul_and_quant_bytes():
    c = obs.op_cost("matmul", 64, 32, 16, jnp.float32)
    assert c.flops == 2 * 64 * 32 * 16
    assert c.bytes == 64 * 16 * 4 + 16 * 32 * 4 + 64 * 32 * 4
    q = obs.op_cost("matmul", 64, 32, 16, jnp.int8, quant="int8")
    assert q.flops == c.flops
    assert q.bytes == 64 * 16 * 1 + 16 * 32 * 1 + 64 * 32 * 4
    assert q.intensity > c.intensity


def test_op_cost_batch_and_attention():
    b = obs.op_cost("brgemm", 8, 8, 8, jnp.float32, batch=16)
    assert b.flops == 16 * 2 * 8 * 8 * 8
    fa = obs.op_cost("flash_attention", 128, 128, 64, jnp.float32)
    assert fa.flops == 4 * 128 * 128 * 64
    bwd = obs.op_cost("flash_attention_bwd", 128, 128, 64, jnp.float32)
    assert bwd.flops == 10 * 128 * 128 * 64
    with pytest.raises(ValueError):
        obs.op_cost("nonsense", 1, 1, 1, jnp.float32)


# ---------------------------------------------------------------------
# dispatch telemetry
# ---------------------------------------------------------------------

def test_dispatch_resolution_counts():
    TELEMETRY.reset()
    with repro.use(backend="xla"):
        assert dispatch.resolve("brgemm") == "xla"
        dispatch.resolve("matmul")
    snap = TELEMETRY.snapshot()
    assert snap["op_dispatch"][("brgemm", "xla")] == 1
    assert snap["op_dispatch"][("matmul", "xla")] == 1
    assert snap["fallbacks"] == {}


def test_fallback_reason_counted_and_traced():
    dispatch.register("obs_fake_op", "pallas", lambda: None,
                      available=lambda: False)
    dispatch.register("obs_fake_op", "xla", lambda: None)
    tr = obs.Tracer()
    try:
        TELEMETRY.reset()
        with repro.use(backend="pallas", tracer=tr):
            assert dispatch.resolve("obs_fake_op") == "xla"
        snap = TELEMETRY.snapshot()
        assert snap["fallbacks"] == {"pallas_unavailable": 1}
        assert snap["op_dispatch"][("obs_fake_op", "xla")] == 1
        (ev,) = tr.events("dispatch")
        assert ev.attrs["fallback_from"] == "pallas"
        assert ev.attrs["backend"] == "xla"
    finally:
        dispatch._REGISTRY.pop("obs_fake_op", None)


def test_blocks_source_heuristic_then_cache_hit():
    dispatch.clear_tuning_cache()
    TELEMETRY.reset()
    tr = obs.Tracer()
    with repro.use(tracer=tr):
        b1 = dispatch.resolve_blocks("matmul", 640, 640, 640, jnp.float32,
                                     backend="pallas")
        b2 = dispatch.resolve_blocks("matmul", 640, 640, 640, jnp.float32,
                                     backend="pallas")
    assert b1 == b2
    snap = TELEMETRY.snapshot()
    assert snap["blocks_source"] == {"heuristic": 1, "cache-hit": 1}
    assert snap["cache_misses"] == 1 and snap["cache_hits"] == 1
    ev1, ev2 = tr.events("resolve_blocks")
    assert ev1.attrs["source"] == "heuristic"
    assert ev2.attrs["source"] == "cache-hit"
    # the event carries the roofline coordinates of the problem
    assert ev1.attrs["flops"] == 2.0 * 640 ** 3
    assert ev1.attrs["intensity"] > 0
    dispatch.clear_tuning_cache()


def test_blocks_event_carries_quant_tag():
    dispatch.clear_tuning_cache()
    tr = obs.Tracer()
    with repro.use(tracer=tr):
        dispatch.resolve_blocks("matmul", 64, 64, 64, jnp.int8,
                                backend="pallas", quant="int8")
    (ev,) = tr.events("resolve_blocks")
    assert ev.attrs["quant"] == "int8"
    assert ev.attrs["dtype"] == "int8"
    dispatch.clear_tuning_cache()


def test_autotune_unified_stats_and_measured_source(monkeypatch):
    monkeypatch.delenv(dispatch.TUNING_CACHE_ENV, raising=False)
    dispatch.clear_tuning_cache()
    TELEMETRY.reset()
    assert autotune.STATS.searches == 0
    tr = obs.Tracer()
    a = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                    jnp.float32)
    from repro.kernels.brgemm.ops import matmul
    with repro.use(backend="pallas", interpret=True,
                   blocks_policy="autotune", tracer=tr):
        jax.block_until_ready(matmul(a, a))
    # STATS is a property proxy over TELEMETRY: one source of truth
    assert autotune.STATS.searches == TELEMETRY.autotune["searches"] >= 1
    assert autotune.STATS.measured == TELEMETRY.autotune["measured"] >= 1
    assert autotune.STATS.snapshot() == dict(TELEMETRY.autotune)
    assert TELEMETRY.snapshot()["blocks_source"].get(
        "autotune-measured", 0) >= 1
    # writes through the proxy land in the shared store too
    autotune.STATS.searches += 1
    assert TELEMETRY.autotune["searches"] == autotune.STATS.searches
    # per-candidate measurement spans, each stamped with its rate
    searches = tr.spans("autotune.search")
    measures = tr.spans("autotune.measure")
    assert len(searches) >= 1 and len(measures) >= 1
    assert searches[0].attrs["op"] == "matmul"
    assert "best" in searches[0].attrs
    assert all(m.attrs["seconds"] > 0 for m in measures)
    dispatch.clear_tuning_cache()


def test_prometheus_telemetry_families_always_present():
    TELEMETRY.reset()
    from repro.serve.metrics import render_prometheus
    # headers are emitted even with zero samples => stable families
    text = render_prometheus([({"replica": "r0"}, ServeMetrics())])
    for fam in ("repro_op_dispatch_total", "repro_backend_fallbacks_total",
                "repro_tuning_cache_hits_total",
                "repro_tuning_cache_misses_total",
                "repro_blocks_source_total",
                "repro_autotune_searches_total"):
        assert f"# TYPE {fam} counter" in text
    TELEMETRY.record_dispatch("matmul", "xla")
    text = render_prometheus([({"replica": "r0"}, ServeMetrics())])
    assert 'repro_op_dispatch_total{op="matmul",backend="xla"} 1' in text
    TELEMETRY.reset()


# ---------------------------------------------------------------------
# latency histograms
# ---------------------------------------------------------------------

def test_histogram_observe_quantile_merge():
    h = LatencyHistogram(bounds=(0.01, 0.1, 1.0))
    assert h.quantile(0.5) == 0.0                 # empty
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.total_s == pytest.approx(5.56)
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    assert h.quantile(1.0) == 1.0                 # overflow -> last bound
    other = LatencyHistogram(bounds=(0.01, 0.1, 1.0))
    other.observe(0.05, n=3)
    merged = h + other
    assert merged.count == 8
    assert merged.counts[1] == 1 + 3
    with pytest.raises(ValueError):
        h + LatencyHistogram(bounds=(1.0, 2.0))


def test_histogram_prometheus_cumulative_buckets():
    h = LatencyHistogram(bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5, n=2)
    h.observe(7.0)
    lines = h.prometheus_lines("repro_serve_ttft_seconds",
                               '{replica="r0"}')
    text = "\n".join(lines)
    assert 'le="0.1"} 1' in text
    assert 'le="1.0"} 3' in text                  # cumulative
    assert 'le="+Inf"} 4' in text
    assert text.count('replica="r0"') == len(lines)
    assert "_sum" in text and "_count" in text


def test_serve_metrics_snapshot_has_percentiles():
    m = ServeMetrics()
    m.ttft_hist.observe(0.02)
    m.ttft_hist.observe(0.2)
    m.token_latency_hist.observe(0.004, n=10)
    snap = m.snapshot()
    assert snap["ttft_p50_s"] > 0
    assert snap["ttft_p99_s"] >= snap["ttft_p50_s"]
    assert snap["token_latency_p50_s"] > 0


# ---------------------------------------------------------------------
# engine + serve integration
# ---------------------------------------------------------------------

def test_engine_ttft_breakdown_telescopes_exactly(dense):
    cfg, params = dense
    clock = FakeClock(dt=0.25)
    eng = ContinuousEngine(cfg, params,
                           PoolConfig(n_slots=2, max_len=MAX_LEN),
                           clock=clock)
    out = eng.serve(_requests(cfg, 4))
    assert all(len(v) for v in out.values())
    for state in eng.scheduler.finished.values():
        bd = state.ttft_breakdown
        assert bd is not None
        assert bd["queue_s"] >= 0
        assert bd["prefill_s"] > 0 and bd["first_decode_s"] > 0
        assert sum(bd.values()) == pytest.approx(state.ttft_s, abs=1e-12)
    # every first token landed in the TTFT histogram
    assert eng.metrics.ttft_hist.count == 4
    assert eng.metrics.token_latency_hist.count == eng.metrics.slot_steps


def test_engine_request_spans_under_tracer(dense):
    cfg, params = dense
    eng = ContinuousEngine(cfg, params,
                           PoolConfig(n_slots=2, max_len=MAX_LEN))
    tr = obs.Tracer()
    obs.install(tr)
    try:
        eng.serve(_requests(cfg, 3))
    finally:
        obs.install(None)
    names = {s.name for s in tr.spans()}
    assert {"prefill", "decode", "request", "request.queue",
            "request.prefill", "request.first_decode"} <= names
    reqs = tr.spans("request")
    assert len(reqs) == 3
    by_id = {s.span_id: s for s in tr.spans()}
    for child in tr.spans("request.queue"):
        assert by_id[child.parent_id].name == "request"
        assert child.attrs["trace"] == by_id[child.parent_id].attrs["trace"]
    for r in reqs:
        assert r.attrs["trace"] == f"req{r.attrs['request_id']}"
        assert r.attrs["finish_reason"] == "length"
        # the children telescope across the request span's TTFT
        kids = [s for s in tr.spans() if s.parent_id == r.span_id]
        assert sum(k.duration_s for k in kids) == pytest.approx(
            r.attrs["ttft_s"], abs=1e-9)
    assert tr.events("engine.submit")


def test_router_lifecycle_events_and_trace_ids(dense):
    cfg, params = dense
    pool = lambda: PoolConfig(n_slots=2, max_len=MAX_LEN)  # noqa: E731
    flaky = ContinuousEngine(cfg, params, pool())
    calls = [0]
    orig = flaky.step

    def boom():
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("injected")
        return orig()

    flaky.step = boom
    router = EngineRouter(
        [EngineReplica("stable", ContinuousEngine(cfg, params, pool())),
         EngineReplica("flaky", flaky)])
    tr = obs.Tracer()
    obs.install(tr)
    try:
        out = router.serve(_requests(cfg, 4))
    finally:
        obs.install(None)
    assert all(len(v) for v in out.values())
    assert len(tr.events("router.submit")) == 4
    assert tr.events("replica.quarantine")[0].attrs["replica"] == "flaky"
    assert tr.events("router.requeue")
    finishes = tr.events("request.finish")
    assert {e.attrs["trace"] for e in finishes} == \
        {f"t{tid}" for tid in out}
    assert all(e.attrs["status"] == "completed" for e in finishes)
    # the engine-side request spans carry the router's ticket trace ids
    req_traces = {s.attrs["trace"] for s in tr.spans("request")}
    assert req_traces <= {f"t{tid}" for tid in out}


def test_frontend_propagates_tracer_into_executor(dense):
    cfg, params = dense
    eng = ContinuousEngine(cfg, params,
                           PoolConfig(n_slots=2, max_len=MAX_LEN))
    router = EngineRouter([EngineReplica("r0", eng)])
    tr = obs.Tracer()

    async def main():
        with repro.use(tracer=tr):
            async with AsyncFrontend(router) as fe:
                handles = [await fe.submit(r)
                           for r in _requests(cfg, 3)]
                return [await h for h in handles]

    results = asyncio.run(main())
    assert all(r.status == "completed" for r in results)
    # spans were recorded from the executor thread, not the loop thread
    prefills = tr.spans("prefill")
    assert prefills
    assert any(s.thread != threading.get_ident() for s in prefills)
    assert len(tr.spans("request")) == 3


def test_http_shim_generate_metrics_and_400(dense):
    import urllib.error
    import urllib.request

    from repro.serve import HttpFrontend

    cfg, params = dense
    eng = ContinuousEngine(cfg, params,
                           PoolConfig(n_slots=2, max_len=MAX_LEN))
    router = EngineRouter([EngineReplica("r0", eng)])
    with HttpFrontend(router) as hf:
        body = json.dumps({"prompt": [1, 2, 3], "max_tokens": 4,
                           "stop_tokens": []}).encode()
        req = urllib.request.Request(
            hf.url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req).read())
        assert out["status"] == "completed"
        assert len(out["tokens"]) == 4
        assert out["ttft_s"] > 0

        met = urllib.request.urlopen(hf.url + "/metrics")
        assert met.headers["Content-Type"].startswith("text/plain")
        text = met.read().decode()
        assert "repro_serve_ttft_seconds_bucket" in text
        assert "repro_op_dispatch_total" in text

        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                hf.url + "/generate", data=b'{"prompt": []}',
                headers={"Content-Type": "application/json"}))
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(hf.url + "/nope")
        assert e.value.code == 404


def test_request_from_payload_validation():
    from repro.serve import request_from_payload
    req, tier, dl = request_from_payload(
        {"prompt": [1, 2], "max_tokens": 3, "temperature": 0.5,
         "tier": "fp32", "deadline_s": 2.5})
    assert req.prompt == [1, 2] and req.temperature == 0.5
    assert tier == "fp32" and dl == 2.5
    for bad in ({"prompt": []}, {"prompt": "hi"}, {"prompt": [1], "x": 1},
                {"prompt": [1], "max_tokens": 0},
                {"prompt": [1], "stop_tokens": "no"}):
        with pytest.raises(ValueError):
            request_from_payload(bad)


# ---------------------------------------------------------------------
# spans in the profiler trace, named scopes in the compiled programs
# ---------------------------------------------------------------------

def _scope_paths(compiled_text):
    """Every op's ``op_name`` with transform wrappers (``jvp(...)``,
    ``transpose(...)``, ``vmap(...)``) unwrapped to what they hold."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled_text):
        parts = []
        for part in path.split("/"):
            while re.fullmatch(r"\w+\((.*)\)", part):
                part = re.fullmatch(r"\w+\((.*)\)", part).group(1)
            parts.append(part)
        out.add("/".join(parts))
    return out


def _has_scope(paths, scope, *also):
    return any(f"/{scope}/" in f"/{p}/" and all(a in p for a in also)
               for p in paths)


def _lowered_program(kind, cfg, params):
    from repro.serve import kv_cache
    from repro.train import optimizer as opt
    from repro.train import train_step as ts

    if kind == "train":
        ocfg = opt.AdamWCfg()
        state = jax.eval_shape(lambda: ts.init_state(
            jax.random.PRNGKey(0), cfg, ocfg))
        batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
                 for k in ("tokens", "labels")}
        step = ts.make_train_step(cfg, ocfg, backend="pallas")
        return jax.jit(step).lower(state, batch)
    eng = ContinuousEngine(cfg, params, PoolConfig(
        n_slots=2, max_len=MAX_LEN, page_size=8, prefill_chunk=8,
        kv_quant="int8" if kind == "decode_int8" else None))
    pool = eng.pool
    if kind in ("decode", "decode_int8"):
        return eng._decode.lower(
            params, jnp.zeros((2, 1), jnp.int32), pool.data, pool.scales,
            jnp.asarray(pool.page_tables), jnp.asarray(pool.positions))
    if kind == "chunk":
        return eng._chunk_rest.lower(
            params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
            pool.request_cache(), jnp.int32(8))
    assert isinstance(pool, kv_cache.PagedKVCache)
    return pool._insert.lower(pool.data, pool.scales, pool.request_cache(),
                              jnp.int32(0), jnp.asarray(pool.page_tables[0]))


@pytest.mark.parametrize("kind,scopes", [
    # a dense pool decodes in place: no page gather or scatter at all
    ("decode", ["embed", "attention", "attention/core", "attention/kv_write",
                "mlp", "head"]),
    ("decode_int8", ["embed", "attention", "attention/core",
                     "attention/kv_write", "mlp", "head", "kv_gather",
                     "kv_scatter"]),
    ("chunk", ["embed", "attention", "attention/core", "attention/kv_write",
               "mlp", "head"]),
    ("insert", ["kv_insert"]),
    ("train", ["embed", "attention", "attention/core", "mlp", "head",
               "loss", "optimizer"]),
])
def test_named_scopes_reach_compiled_op_metadata(dense, kind, scopes):
    cfg, params = dense
    if kind == "train":    # the Pallas kernels and their custom VJPs
        cfg = dataclasses.replace(cfg, n_layers=1, remat=True)
    text = _lowered_program(kind, cfg, params).compile().as_text()
    paths = _scope_paths(text)
    for scope in scopes:
        assert _has_scope(paths, scope), (scope, sorted(paths)[:20])
    if kind == "decode":
        assert not any(_has_scope(paths, sc)
                       for sc in ("kv_gather", "kv_scatter"))
    if kind == "train":
        # backward ops keep the scopes of the forward they transpose:
        # the fused flash backward and the brgemm's VJP
        raw = set(re.findall(r'op_name="([^"]*)"', text))
        assert any("transpose(jvp(" in p and "attention/core" in p
                   and "flash_attention_bwd" in p for p in raw)
        assert any("transpose(jvp(" in p and "/mlp/" in p
                   and "matmul_pallas" in p for p in raw)


def _engine_spans_tree(tr):
    by_id = {s.span_id: s for s in tr.spans()}
    kids = {}
    for s in tr.spans():
        if s.parent_id is not None:
            kids.setdefault(by_id[s.parent_id].name, set()).add(s.name)
    return kids


def test_engine_step_spans_nest_and_reach_the_profiler(dense, tmp_path):
    import glob
    import os

    from jax.profiler import ProfileData

    cfg, params = dense
    eng = ContinuousEngine(cfg, params, PoolConfig(
        n_slots=2, max_len=MAX_LEN, page_size=8, prefill_chunk=8))
    reqs = _requests(cfg, 3) + [Request(prompt=list(range(1, 12)),
                                        max_tokens=3, stop_tokens=())]
    for r in reqs:
        eng.submit(r)
    eng.step()                       # compiles outside the traces
    tr = obs.Tracer()

    def host_names(log_dir):
        (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True)
        return {e.name for p in ProfileData.from_file(path).planes
                for line in p.lines for e in line.events
                if e.name.startswith("repro.")}

    with jax.profiler.trace(str(tmp_path / "off")):
        eng.step()                   # no tracer: nothing recorded
    assert host_names(str(tmp_path / "off")) == set()
    obs.install(tr)
    try:
        with jax.profiler.trace(str(tmp_path / "on")):
            while eng.has_work():
                eng.step()
    finally:
        obs.install(None)
    kids = _engine_spans_tree(tr)
    assert {"admit", "pages", "decode", "emit"} <= kids["step"]
    assert kids["decode"] == {"decode.upload", "decode.wait"}
    assert "prefill.wait" in kids["admit"]
    assert tr.spans("step") and all(s.parent_id is None
                                    for s in tr.spans("step"))
    names = {s.name for s in tr.spans()}
    # every with-span reached the profiler; synthetic request spans not
    assert host_names(str(tmp_path / "on")) == {
        "repro." + n for n in names if not n.startswith("request")}
    assert not tr.events("engine.prefill_chunk_start")


def test_token_latency_hist_observes_each_requests_token_gap(dense):
    cfg, params = dense
    now = [0.0]
    eng = ContinuousEngine(cfg, params,
                           PoolConfig(n_slots=2, max_len=MAX_LEN),
                           clock=lambda: now[0])
    stamps = {}

    def on_token(rid, tok, finished):
        stamps.setdefault(rid, []).append(now[0])

    for r in _requests(cfg, 3):      # 3 requests on 2 slots
        eng.submit(r, on_token=on_token)
    t = 0.0
    while eng.has_work():
        t += 1.5 + t / 4             # uneven steps
        now[0] = t
        eng.step()
    gaps = [b - a for s in stamps.values() for a, b in zip(s, s[1:])]
    hist = eng.metrics.token_latency_hist
    assert hist.count == len(gaps) == eng.metrics.slot_steps
    assert hist.total_s == pytest.approx(sum(gaps))
    assert sum(gaps) > 0             # not the decode section's length
