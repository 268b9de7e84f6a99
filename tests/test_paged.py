"""Paged KV cache tests: page-allocator invariants under random churn,
paged-vs-slotted greedy token parity (dense + enc-dec), the slotted
fallback for non-pageable architectures, chunked-prefill equivalence,
quantized page storage, preemption under page pressure, the in-place
decode path against the gather path, which pools decode in place, and
the page-aware attention block geometry."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import blocking, dispatch
from repro.models import api
from repro.serve import (
    ContinuousEngine,
    PagedKVCache,
    PoolConfig,
    Request,
    SlotKVCache,
)

MAX_LEN = 32
SRC_LEN = 6
PAGE = 8
PROMPT_LENS = [5, 20, 3, 17, 7]
MAX_TOKENS = [6, 4, 8, 3, 5]


@pytest.fixture(scope="module")
def dense():
    cfg = configs.get("smollm-135m").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def seamless():
    cfg = configs.get("seamless-m4t-large-v2").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).tolist() for n in lens]


def _requests(prompts, src=None):
    return [Request(prompt=p, max_tokens=m, stop_tokens=(),
                    src_embeds=None if src is None else src[i])
            for i, (p, m) in enumerate(zip(prompts, MAX_TOKENS))]


def _serve(cfg, params, pool, requests):
    eng = ContinuousEngine(cfg, params, pool, interpret=True)
    return eng, eng.serve(requests)


# ==========================================================================
# page allocator invariants (no jax compute)
# ==========================================================================

def test_page_allocator_churn_no_leaks_no_double_free(dense):
    cfg, _ = dense
    pool = PagedKVCache(cfg, n_slots=4, max_len=MAX_LEN, page_size=PAGE,
                        n_pages=12)
    rng = np.random.default_rng(0)
    live = {}
    for _ in range(300):
        if live and (rng.random() < 0.4 or pool.n_free == 0):
            slot = rng.choice(sorted(live))
            pool.free(slot)
            del live[slot]
            continue
        slot = pool.alloc()
        if slot is None:
            continue
        n = int(rng.integers(1, MAX_LEN + 1))
        if pool.alloc_pages(slot, -(-n // PAGE)):
            pool.lengths[slot] = n
            live[slot] = n
        else:
            pool.free(slot)   # all-or-nothing: nothing was allocated
    # invariant under churn: every page is either free or in exactly one
    # live slot's table
    held = sum(int(pool.pages_used[s]) for s in live)
    assert held + pool.n_free_pages == pool.n_pages
    table_ids = [int(p) for s in live
                 for p in pool.page_tables[s][:pool.pages_used[s]]]
    assert len(table_ids) == len(set(table_ids)) == held
    for slot in sorted(live):
        pool.free(slot)
    assert pool.n_free == 4 and pool.n_free_pages == pool.n_pages
    assert pool.alloc_count == pool.free_count
    assert pool.page_alloc_count == pool.page_free_count
    assert pool.fragmentation == 0.0 and pool.page_occupancy == 0.0


def test_page_allocator_double_free_and_overflow_raise(dense):
    cfg, _ = dense
    pool = PagedKVCache(cfg, n_slots=2, max_len=MAX_LEN, page_size=PAGE)
    slot = pool.alloc()
    assert pool.ensure(slot, 0)
    pool.free(slot)
    with pytest.raises(ValueError, match="double free"):
        pool.free(slot)
    slot = pool.alloc()
    with pytest.raises(ValueError, match="pages_per_slot"):
        pool.alloc_pages(slot, pool.pages_per_slot + 1)


def test_page_allocator_all_or_nothing(dense):
    cfg, _ = dense
    pool = PagedKVCache(cfg, n_slots=2, max_len=MAX_LEN, page_size=PAGE,
                        n_pages=4)
    a, b = pool.alloc(), pool.alloc()
    assert pool.alloc_pages(a, 3)
    assert not pool.alloc_pages(b, 2)      # only 1 free: refuse whole ask
    assert pool.pages_used[b] == 0          # nothing partially granted
    assert pool.alloc_pages(b, 1)
    assert pool.n_free_pages == 0


def test_fragmentation_counts_trailing_page_waste(dense):
    cfg, _ = dense
    pool = PagedKVCache(cfg, n_slots=2, max_len=MAX_LEN, page_size=PAGE)
    slot = pool.alloc()
    assert pool.ensure(slot, PAGE)          # 2 pages for position 8
    pool.lengths[slot] = PAGE + 1           # 9 live tokens in 16 capacity
    assert pool.fragmentation == pytest.approx(1 - 9 / 16)


def test_paged_pool_rejected_for_windowed_arch():
    cfg = configs.get("recurrentgemma-9b").reduced()
    with pytest.raises(ValueError, match="paging is not supported"):
        PagedKVCache(cfg, n_slots=2, max_len=MAX_LEN, page_size=PAGE)


# ==========================================================================
# paged decode parity
# ==========================================================================

def test_paged_greedy_parity_dense(dense):
    cfg, params = dense
    prompts = _prompts(cfg, PROMPT_LENS)
    _, ref = _serve(cfg, params, PoolConfig(n_slots=3, max_len=MAX_LEN),
                    _requests(prompts))
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN,
                                 page_size=PAGE),
                      _requests(prompts))
    assert eng.paged and isinstance(eng.pool, PagedKVCache)
    assert out == ref
    assert eng.pool.page_alloc_count == eng.pool.page_free_count
    assert eng.pool.n_free_pages == eng.pool.n_pages


def test_paged_greedy_parity_encdec(seamless):
    cfg, params = seamless
    prompts = _prompts(cfg, PROMPT_LENS)
    rng = np.random.default_rng(3)
    src = [jnp.asarray(rng.normal(size=(SRC_LEN, cfg.d_model)), jnp.float32)
           for _ in prompts]
    _, ref = _serve(cfg, params,
                    PoolConfig(n_slots=3, max_len=MAX_LEN, src_len=SRC_LEN),
                    _requests(prompts, src))
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN,
                                 src_len=SRC_LEN, page_size=PAGE),
                      _requests(prompts, src))
    assert eng.paged
    # the cross-KV leaves must have stayed slot-resident
    assert any(t == -1 for t in jax.tree.leaves(eng.pool.time_axes))
    assert out == ref


def test_windowed_arch_falls_back_to_slotted():
    cfg = configs.get("recurrentgemma-9b").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(
        cfg, params, PoolConfig(n_slots=2, max_len=MAX_LEN, page_size=PAGE),
        interpret=True)
    assert not eng.paged and isinstance(eng.pool, SlotKVCache)
    prompts = _prompts(cfg, [4, 6])
    out = eng.serve([Request(prompt=p, max_tokens=3, stop_tokens=())
                     for p in prompts])
    assert all(len(t) == 3 for t in out.values())


def test_preemption_under_page_pressure_keeps_parity(dense):
    cfg, params = dense
    prompts = _prompts(cfg, PROMPT_LENS, seed=1)
    _, ref = _serve(cfg, params, PoolConfig(n_slots=3, max_len=MAX_LEN),
                    _requests(prompts))
    # 8 pages of 4 = 32 tokens of KV for 3 slots wanting up to 96: the
    # engine must preempt to make progress, and still match greedy
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN, page_size=4,
                                 n_pages=8),
                      _requests(prompts))
    assert eng.metrics.preemptions > 0
    assert out == ref
    assert eng.pool.page_alloc_count == eng.pool.page_free_count
    # a dense full-precision pool decodes in place on every step
    assert eng.decode_path == "in_place"
    assert eng.metrics.decode_steps_in_place == eng.metrics.decode_steps > 0


def test_quantized_pages_parity_within_tolerance(dense):
    cfg, params = dense
    prompts = _prompts(cfg, PROMPT_LENS)
    _, ref = _serve(cfg, params, PoolConfig(n_slots=3, max_len=MAX_LEN),
                    _requests(prompts))
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN,
                                 page_size=PAGE, kv_quant="int8"),
                      _requests(prompts))
    assert eng.pool.scales is not None
    paged_leaves = [x for x, t in zip(jax.tree.leaves(eng.pool.data),
                                      jax.tree.leaves(eng.pool.time_axes))
                    if t != -1]
    assert all(x.dtype == jnp.int8 for x in paged_leaves)
    # int8 KV is lossy, so token-for-token equality is not guaranteed;
    # on this reduced model the greedy argmax should still rarely flip
    match = sum(out[k] == ref[k] for k in ref)
    assert match >= len(ref) - 1


def test_kv_quant_requires_paged_pool(dense):
    cfg, params = dense
    with pytest.raises(ValueError, match="kv_quant requires page_size"):
        ContinuousEngine(cfg, params,
                         PoolConfig(n_slots=2, max_len=MAX_LEN,
                                    kv_quant="int8"))


# ==========================================================================
# chunked prefill
# ==========================================================================

def test_chunked_prefill_matches_one_shot_logits(dense):
    cfg, params = dense
    prompt = _prompts(cfg, [19], seed=2)[0]
    cache = api.init_cache(cfg, 1, MAX_LEN)
    logits_full, _ = api.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cfg,
        api.init_cache(cfg, 1, MAX_LEN))
    pos, logits = 0, None
    for chunk in (prompt[0:8], prompt[8:16], prompt[16:19]):
        logits, cache = api.prefill_chunk(
            params, {"tokens": jnp.asarray([chunk], jnp.int32)}, cfg,
            cache, pos)
        pos += len(chunk)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_full),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pos", [0, 100, 250, 368])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_chunk_attention_at_an_offset_matches_reference(pos, backend):
    """A prefill chunk's flash attention at query offset ``pos`` (GQA
    group 3, several key blocks, the live extent ending mid-block) equals
    the masked reference over the whole view."""
    from repro.core.blocking import chunk_attention_blocks
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import mha_ref

    rng = np.random.default_rng(pos)
    t, tk = 16, 384
    q = jnp.asarray(rng.normal(size=(1, 6, t, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 2, tk, 16)), jnp.float32)
            for _ in range(2))
    # keys past pos + t are stale cache rows the chunk must not see
    k = k.at[:, :, pos + t:].set(30.0)
    v = v.at[:, :, pos + t:].set(1e3)
    assert chunk_attention_blocks(t, tk).block_k == 384
    want = mha_ref(q, k, v, causal=True, q_offset=pos, kv_len=pos + t)
    got = jax.jit(lambda q, k, v, p: flash_attention(
        q, k, v, causal=True, q_offset=p, backend=backend,
        blocks=blocking.AttnBlocks(block_q=16, block_k=128)))(
            q, k, v, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_prefill_serving_parity(dense):
    cfg, params = dense
    prompts = _prompts(cfg, PROMPT_LENS)
    _, ref = _serve(cfg, params, PoolConfig(n_slots=3, max_len=MAX_LEN),
                    _requests(prompts))
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN, page_size=4,
                                 prefill_chunk=8),
                      _requests(prompts))
    assert eng.metrics.prefill_chunks > 0
    assert out == ref


def test_chunked_prefill_serving_parity_encdec(seamless):
    cfg, params = seamless
    prompts = _prompts(cfg, PROMPT_LENS)
    rng = np.random.default_rng(3)
    src = [jnp.asarray(rng.normal(size=(SRC_LEN, cfg.d_model)), jnp.float32)
           for _ in prompts]
    _, ref = _serve(cfg, params,
                    PoolConfig(n_slots=3, max_len=MAX_LEN, src_len=SRC_LEN),
                    _requests(prompts, src))
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=3, max_len=MAX_LEN,
                                 src_len=SRC_LEN, page_size=4,
                                 prefill_chunk=8),
                      _requests(prompts, src))
    assert eng.metrics.prefill_chunks > 0
    assert out == ref


def test_chunked_prefill_stalls_decode_at_most_one_step(dense):
    """While a long prompt is chunking, already-running requests must
    keep generating one token every step (no multi-step stalls)."""
    cfg, params = dense
    eng = ContinuousEngine(
        cfg, params,
        PoolConfig(n_slots=2, max_len=MAX_LEN, page_size=4,
                   prefill_chunk=4),
        interpret=True)
    prompts = _prompts(cfg, [3, 20])
    first = eng.submit(Request(prompt=prompts[0], max_tokens=10,
                               stop_tokens=()))
    eng.step()   # request 0 admitted and decoding
    eng.submit(Request(prompt=prompts[1], max_tokens=2, stop_tokens=()))
    first_done = False
    for _ in range(40):
        got = [e for e in eng.step() if e[0] == first]
        if not first_done:
            assert got, "running decode stalled during chunked prefill"
            first_done = any(e[2] for e in got)
        if not eng.has_work():
            break
    assert not eng.has_work() and eng.metrics.prefill_chunks >= 5


def test_chunk_rejected_for_windowed_arch():
    cfg = configs.get("recurrentgemma-9b").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="prefill_chunk is not supported"):
        ContinuousEngine(cfg, params,
                         PoolConfig(n_slots=2, max_len=MAX_LEN,
                                    prefill_chunk=8))


def test_chunk_must_align_to_page(dense):
    cfg, params = dense
    with pytest.raises(ValueError, match="multiple of page_size"):
        ContinuousEngine(cfg, params,
                         PoolConfig(n_slots=2, max_len=MAX_LEN,
                                    page_size=8, prefill_chunk=12))


# ==========================================================================
# in-place decode against the gather path
# ==========================================================================

def _paged_state(cfg, seed=0):
    """A key-major pool of random pages, and three slots' page tables:
    shuffled pages, a free slot, and room for three more tokens each."""
    rng = np.random.default_rng(seed)
    pool = PagedKVCache(cfg, n_slots=3, max_len=MAX_LEN, page_size=PAGE,
                        n_pages=10)
    assert pool.in_place
    data = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), pool.data)
    lengths = np.array([7, 0, 17], np.int32)       # slot 1 is free
    tables = np.full((3, pool.pages_per_slot), pool.n_pages, np.int32)
    perm = rng.permutation(pool.n_pages)
    tables[0, :2], tables[2, :3] = perm[:2], perm[2:5]
    return pool, data, tables, lengths


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_in_place_decode_matches_gather_path(dense, backend):
    cfg, params = dense
    pool, data, tables, lengths = _paged_state(cfg)
    kw = dict(batch_axes=pool.batch_axes, time_axes=pool.time_axes,
              page_size=PAGE, backend=backend)
    gathered = jax.tree.map(api.key_major, data, pool.time_axes)
    live = lengths > 0
    rng = np.random.default_rng(1)
    for _ in range(3):          # slot 0 crosses into its second page
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (3, 1)), jnp.int32)
        pos = jnp.asarray(lengths)
        logits, data, _ = api.decode_step_paged(
            params, tokens, cfg, data, jnp.asarray(tables), pos, **kw)
        want, gathered, _ = api._decode_gather(
            params, tokens, cfg, gathered, jnp.asarray(tables), pos, **kw)
        np.testing.assert_allclose(np.asarray(logits)[live],
                                   np.asarray(want)[live],
                                   atol=1e-4, rtol=1e-4)
        lengths = lengths + live
    # every live row of the pool agrees, the three new rows included
    for name in ("k", "v"):
        got = np.asarray(api.key_major(data["blocks"][name], 3))
        ref = np.asarray(gathered["blocks"][name])
        for s in np.nonzero(live)[0]:
            for p in range(lengths[s]):
                page = tables[s, p // PAGE]
                np.testing.assert_allclose(
                    got[:, page, :, p % PAGE], ref[:, page, :, p % PAGE],
                    atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch,kv_quant,path", [
    ("smollm-135m", None, "in_place"),
    ("smollm-135m", "int8", "gather"),
    ("grok-1-314b", None, "gather"),             # moe
    ("deepseek-v3-671b", None, "gather"),        # MLA + moe
    ("seamless-m4t-large-v2", None, "gather"),   # enc-dec
])
def test_decode_path_follows_the_pool(arch, kv_quant, path):
    cfg = configs.get(arch).reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    src_len = SRC_LEN if api.is_encdec(cfg) else 0
    prompts = _prompts(cfg, [5, 9, 3])
    rng = np.random.default_rng(3)
    src = ([jnp.asarray(rng.normal(size=(SRC_LEN, cfg.d_model)),
                        jnp.float32) for _ in prompts] if src_len else None)
    eng, out = _serve(cfg, params,
                      PoolConfig(n_slots=2, max_len=MAX_LEN, src_len=src_len,
                                 page_size=PAGE, kv_quant=kv_quant),
                      _requests(prompts, src))
    assert eng.decode_path == path and eng.pool.in_place == (path ==
                                                             "in_place")
    steps = eng.metrics.decode_steps
    assert steps > 0
    assert eng.metrics.decode_steps_in_place == (steps if path == "in_place"
                                                 else 0)
    if kv_quant is None:        # exact paths: the slotted pool's tokens
        _, ref = _serve(cfg, params,
                        PoolConfig(n_slots=2, max_len=MAX_LEN,
                                   src_len=src_len),
                        _requests(prompts, src))
        assert out == ref


# ==========================================================================
# page-table view round trip + paged attention geometry
# ==========================================================================

def test_pages_to_view_round_trip():
    rng = np.random.default_rng(0)
    view = jnp.asarray(rng.normal(size=(4, 1, 2, 16, 8)), jnp.float32)
    pages = api.view_to_pages(view, a=1, t=3, page_size=4)
    assert pages.shape == (4, 4, 2, 4, 8)
    back = api.pages_to_view(pages, a=1, t=3)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(view))


def test_paged_attn_geometry_clamps_block_k():
    geom = blocking.PagedAttnGeometry(page_size=128, pages=64)
    blocks = blocking.default_blocks("flash_attention", 256, 8192, 64,
                                     geometry=geom)
    assert blocks.block_k <= 128
    cands = blocking.candidate_blocks("flash_attention", 256, 8192, 64,
                                      geometry=geom)
    assert all(c.block_k <= 128 or c == blocks for c in cands)
    # distinct tuning-cache identity + JSON round trip
    d = geom.asdict()
    assert d["kind"] == "paged_attn"
    assert blocking.geometry_from_dict(d) == geom
    free = blocking.candidate_blocks("flash_attention", 256, 8192, 64)
    assert max(c.block_k for c in free) > 128


def test_paged_geometry_resolves_through_dispatch():
    geom = blocking.PagedAttnGeometry(page_size=256, pages=32)
    paged = dispatch.resolve_blocks("flash_attention", 128, 4096, 64,
                                    jnp.float32, backend="pallas",
                                    geometry=geom)
    flat = dispatch.resolve_blocks("flash_attention", 128, 4096, 64,
                                   jnp.float32, backend="pallas")
    assert paged.block_k <= 256
    assert isinstance(paged, blocking.AttnBlocks)
    assert isinstance(flat, blocking.AttnBlocks)


# ==========================================================================
# trace sampling
# ==========================================================================

def test_trace_sample_rate_every_nth(dense):
    from repro import obs
    cfg, params = dense
    eng = ContinuousEngine(
        cfg, params, PoolConfig(n_slots=2, max_len=MAX_LEN),
        interpret=True, trace_sample_rate=3)
    prompts = _prompts(cfg, [4] * 6)
    tracer = obs.Tracer()
    prev = obs.install(tracer)
    try:
        eng.serve([Request(prompt=p, max_tokens=2, stop_tokens=())
                   for p in prompts])
    finally:
        obs.install(prev)
    reqs = [s for s in tracer.spans() if s.name == "request"]
    # every 3rd submission sampled: requests 0 and 3 of 6
    assert sorted(s.attrs["request_id"] for s in reqs) == [0, 3]
    # counters stay always-on for unsampled requests
    assert eng.metrics.requests_completed == 6


def test_trace_explicit_id_and_opt_out(dense):
    from repro import obs
    cfg, params = dense
    eng = ContinuousEngine(
        cfg, params, PoolConfig(n_slots=2, max_len=MAX_LEN),
        interpret=True, trace_sample_rate=1000)
    prompts = _prompts(cfg, [4] * 3)
    tracer = obs.Tracer()
    prev = obs.install(tracer)
    try:
        reqs = [Request(prompt=p, max_tokens=2, stop_tokens=())
                for p in prompts]
        eng.submit(reqs[0])                   # rate-sampled (first => yes)
        eng.submit(reqs[1], trace="forced")   # explicit id => sampled
        eng.submit(reqs[2], trace="")         # opt-out
        while eng.has_work():
            eng.step()
    finally:
        obs.install(prev)
    sampled = {s.attrs["request_id"] for s in tracer.spans()
               if s.name == "request"}
    assert sampled == {0, 1}
