"""Serving engines: static batch (reference) and continuous batching.

``Engine`` is the original static-batch path: prefill one fixed batch, then
host-loop decode.  It stays as the semantic reference — ``ContinuousEngine``
must match its greedy outputs token-for-token.

``ContinuousEngine`` is the production loop around the tuned kernels: a
slotted KV-cache pool (``serve.kv_cache``), an admission + step scheduler
(``serve.scheduler``), and two jit entry points — per-request prefill and a
single batched decode step over the full slot dimension with per-slot
positions (``api.decode_step_slots``).  Requests join mid-stream as slots
free up, so decode batches stay full and a single long request no longer
stalls the batch.

Both engines scope their serving tier (backend, block policy, accumulation
dtype, interpret mode, mesh) through ``dispatch.use``: the context is
captured at trace time, so each jit entry point re-enters the engine's
context when it traces.  Two engines at different tiers resolve tuned
blocks independently; with ``blocks_policy="autotune"`` the first trace
pays the measured search (or reads the persisted ``REPRO_TUNING_CACHE``)
and every later request reuses the winners.  Under a ``mesh`` (explicit,
or installed by the launcher via ``sharding.annotate.use_rules``) block
resolution is per-shard: tiles are tuned for the local problem each
device runs, not the global batch shape.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchCfg
from repro.core import dispatch
from repro.models import api
from repro.sharding import annotate
from repro.serve.kv_cache import PagedKVCache, SlotKVCache
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Request, RequestState, Scheduler


def completed_lengths(ids, stop_tokens) -> np.ndarray:
    """Per-row generated length of a (B, T) id array: index of the first
    stop token + 1 (the stop token is part of the output), else T."""
    arr = np.asarray(ids)
    lens = np.full(arr.shape[0], arr.shape[1], np.int64)
    stops = list(stop_tokens)
    if not stops:
        return lens
    for b in range(arr.shape[0]):
        hits = np.nonzero(np.isin(arr[b], stops))[0]
        if hits.size:
            lens[b] = hits[0] + 1
    return lens


@dataclasses.dataclass
class ServeConfig:
    max_len: int
    temperature: float = 0.0   # 0 => greedy
    src_len: int = 0           # enc-dec encoder memory length


def _tier_context(backend, blocks_policy, accum_dtype, interpret=None,
                  mesh=None, axis_specs=None, quant=None):
    """The ``dispatch.use`` kwargs of one serving tier, resolved at trace
    time: an unset mesh falls back to whatever the launcher installed via
    ``sharding.annotate.use_rules`` *when the jit entry traces*."""
    return dict(backend=backend, blocks_policy=blocks_policy,
                accum_dtype=accum_dtype, interpret=interpret,
                mesh=mesh if mesh is not None else annotate.current_mesh(),
                axis_specs=axis_specs, quant=quant)


class Engine:
    def __init__(self, cfg: ArchCfg, params, scfg: ServeConfig, *,
                 backend: str | None = None,
                 blocks_policy=None, accum_dtype=None,
                 mesh=None, axis_specs=None,
                 quant=None, decode_quant=None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.backend = backend
        self.blocks_policy = blocks_policy
        self.accum_dtype = accum_dtype
        self.mesh = mesh
        self.axis_specs = axis_specs
        # Per-phase quant tiers: prefill is compute-bound (quantization
        # rarely pays), decode streams weights (int8 halves the bytes), so
        # decode_quant defaults to quant but can diverge — the canonical
        # production mix is quant=None + decode_quant="int8".
        self.quant = quant
        self.decode_quant = decode_quant if decode_quant is not None else quant

        def _tier(q):
            return _tier_context(self.backend, self.blocks_policy,
                                 self.accum_dtype, mesh=self.mesh,
                                 axis_specs=self.axis_specs, quant=q)

        def _prefill(p, b, c):
            with dispatch.use(**_tier(self.quant)):
                return api.prefill(p, b, cfg, c)

        def _decode(p, t, c, pos):
            with dispatch.use(**_tier(self.decode_quant)):
                return api.decode_step(p, t, cfg, c, pos)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    def _init_cache(self, batch_size: int):
        return api.init_cache(self.cfg, batch_size, self.scfg.max_len,
                              self.scfg.src_len)

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    def generate(self, batch, *, n_tokens: int, key=None, stop_tokens=None):
        """batch: prefill inputs. Returns (B, T) generated ids, T <= n_tokens.

        ``stop_tokens=None`` defaults to ``(cfg.eos_token,)`` when the
        config defines one (pass ``()`` to disable).  With stop tokens, the
        loop ends as soon as every row has emitted one, so T can be shorter
        than ``n_tokens``; rows that finish early keep decoding
        (deterministically) until the slowest row is done — use
        :func:`completed_lengths` to truncate per row.
        """
        key = key if key is not None else jax.random.PRNGKey(0)
        if stop_tokens is None:
            stop_tokens = ((self.cfg.eos_token,)
                           if self.cfg.eos_token is not None else ())
        stops = tuple(stop_tokens)
        b = batch["tokens"].shape[0]
        prompt_len = batch["tokens"].shape[1]
        pos_off = (self.cfg.n_patches or 0) if not api.is_encdec(
            self.cfg) else 0

        cache = self._init_cache(b)
        logits, cache = self._prefill(self.params, batch, cache)
        out = []
        # Split before the first sample: sampling with `key` itself and then
        # splitting the same key would correlate the first two steps.
        key, sub = jax.random.split(key)
        tok = self._sample(logits, sub)
        out.append(tok)
        finished = np.isin(np.asarray(tok), stops) if stops else None
        pos = prompt_len + pos_off
        for _ in range(n_tokens - 1):
            if stops and finished.all():
                break
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, tok[:, None], cache,
                                         jnp.int32(pos))
            tok = self._sample(logits, sub)
            out.append(tok)
            if stops:
                finished |= np.isin(np.asarray(tok), stops)
            pos += 1
        return jnp.stack(out, axis=1)


# ==========================================================================
# continuous batching
# ==========================================================================

@dataclasses.dataclass
class PoolConfig:
    """KV pool sizing + prefill shaping.

    ``n_slots`` bounds concurrent requests (decode cost is O(n_slots) every
    step, so size it to the target batch).  ``max_len`` bounds
    prompt + generated tokens per slot.  ``prefill_bucket`` rounds prompt
    lengths up to a multiple (right-padding) so distinct prompt lengths
    share prefill compilations; only valid for architectures where pad
    tokens cannot perturb real ones (full causal attention, no capacity-
    routed MoE, no recurrence): plain dense decoders and enc-dec.

    Paged pool knobs (see ``serve.kv_cache.PagedKVCache``):

    ``page_size`` switches the engine to the paged KV cache — KV memory is
    then budgeted in *pages*, not slot spans, and slots only hold page
    tables.  ``n_pages`` is the page budget (default: enough for every
    slot at full ``max_len``, i.e. no memory saving — size it below that
    to overcommit; the engine preempts the newest request when the pool
    runs dry).  On architectures where paging can't apply (sliding-window
    ring buffers, recurrent state, VLM prefixes) the engine silently
    falls back to the slotted pool.

    ``prefill_chunk`` caps prefill work per scheduler step: prompts longer
    than the chunk are split into ``prefill_chunk``-token chunks processed
    across steps (one per step), so a long prompt never stalls running
    decodes for more than one chunk's compute; shorter prompts share the
    same per-step token budget.  ``kv_quant="int8"`` stores paged KV as
    int8 with per-page scales (requires ``page_size``).
    """
    n_slots: int
    max_len: int
    src_len: int = 0
    prefill_bucket: int | None = None
    page_size: int | None = None
    n_pages: int | None = None
    prefill_chunk: int | None = None
    kv_quant: str | None = None


def _supports_bucketing(cfg: ArchCfg) -> bool:
    return (cfg.block in ("dense", "encdec") and not cfg.window
            and not cfg.n_patches)


def _sample_tokens(logits, temps, top_k, key):
    """Vectorized per-slot sampling: greedy where temp==0, else categorical
    at that slot's temperature, optionally top-k filtered (top_k==0: off)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.clip(top_k, 1, v) - 1
    thresh = jnp.take_along_axis(sorted_desc, kth[:, None], axis=-1)
    masked = jnp.where((top_k[:, None] > 0) & (logits < thresh),
                       -jnp.inf, logits)
    t = jnp.where(temps > 0, temps, 1.0)
    samp = jax.random.categorical(key, masked / t[:, None],
                                  axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, samp, greedy)


def _as_batch1(x, name: str):
    if x is None:
        raise ValueError(f"request requires {name} for this architecture")
    x = jnp.asarray(x)
    return x if x.ndim == 3 else x[None]


class ContinuousEngine:
    """Continuous-batching engine: ``submit() + step()`` or ``serve()``.

    Each step admits waiting requests into free KV-cache slots (prefill +
    first token), runs one batched decode step over the full slot pool with
    per-slot positions, and evicts finished requests the same step.  Greedy
    outputs match the static ``Engine`` token-for-token.
    """

    def __init__(self, cfg: ArchCfg, params, pool: PoolConfig, *,
                 backend: str | None = None, blocks_policy=None,
                 accum_dtype=None, interpret: bool | None = None,
                 mesh=None, axis_specs=None,
                 quant=None, decode_quant=None,
                 priority_fn=None, key=None,
                 trace_sample_rate: int | None = None,
                 clock: Callable[[], float] = time.perf_counter):
        if pool.prefill_bucket is not None and not _supports_bucketing(cfg):
            raise ValueError(
                f"prefill_bucket is not supported for block={cfg.block!r} "
                f"(window={cfg.window}, n_patches={cfg.n_patches}): pad "
                "tokens could perturb real ones")
        if pool.prefill_chunk is not None and not api.supports_paging(cfg):
            raise ValueError(
                f"prefill_chunk is not supported for block={cfg.block!r} "
                f"(window={cfg.window}, n_patches={cfg.n_patches}): chunk "
                "attention needs position-indexed, length-masked KV")
        if pool.prefill_chunk is not None and pool.prefill_bucket is not None:
            raise ValueError("prefill_chunk and prefill_bucket are "
                             "mutually exclusive")
        if (pool.prefill_chunk is not None and pool.page_size
                and pool.prefill_chunk % pool.page_size):
            raise ValueError(
                f"prefill_chunk ({pool.prefill_chunk}) must be a multiple "
                f"of page_size ({pool.page_size}) so chunks stay "
                "page-aligned")
        if pool.kv_quant is not None and not pool.page_size:
            raise ValueError("kv_quant requires page_size (paged pool)")
        self.cfg = cfg
        self.params = params
        self.pool_cfg = pool
        # paged pool where the architecture allows it; slotted fallback
        # (ring buffers / recurrent states have no pageable time axis)
        self.paged = bool(pool.page_size) and api.supports_paging(cfg)
        if self.paged:
            self.pool = PagedKVCache(cfg, pool.n_slots, pool.max_len,
                                     page_size=pool.page_size,
                                     n_pages=pool.n_pages,
                                     src_len=pool.src_len,
                                     kv_quant=pool.kv_quant)
        else:
            self.pool = SlotKVCache(cfg, pool.n_slots, pool.max_len,
                                    src_len=pool.src_len)
        self.scheduler = Scheduler(priority_fn=priority_fn)
        self.metrics = ServeMetrics()
        # every lifecycle stamp (submit/admit/prefill-end/first-token)
        # comes from this one clock, so TTFT breakdown segments telescope
        # exactly; injectable for deterministic tests
        self._clock = clock
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._pos_off = (cfg.n_patches or 0) if not api.is_encdec(cfg) else 0
        # Host-side per-slot sampling state, fed into the jit entries each
        # step; free slots hold zeros and decode as ignored garbage.
        self._tokens = np.zeros(pool.n_slots, np.int32)
        self._temps = np.zeros(pool.n_slots, np.float32)
        self._topk = np.zeros(pool.n_slots, np.int32)
        # request_id -> on_token callback for streaming consumers
        self._on_token: dict[int, Any] = {}
        # chunked prefill in flight (at most one: head-of-line admission
        # keeps staging memory bounded to a single batch-1 view)
        self._staging: dict | None = None
        # sampled per-request tracing: every Nth submitted request gets
        # the full span tree; counters stay always-on for the rest
        self.trace_sample_rate = trace_sample_rate
        self._trace_count = 0
        self._trace_ids: set[int] = set()

        # decode is weight-streaming-bound, so it gets its own quant tier
        # (int8 decode + full-precision prefill is the production mix)
        decode_quant = decode_quant if decode_quant is not None else quant

        def tier(q):
            # Resolved inside the jit closures, i.e. at *trace* time, so
            # an annotate-installed mesh active when the entry first
            # compiles shapes the tier's block resolution.
            return _tier_context(backend, blocks_policy, accum_dtype,
                                 interpret, mesh, axis_specs, quant=q)

        batch_axes = self.pool.batch_axes

        def _prefill(p, batch, cache, logit_pos):
            with dispatch.use(**tier(quant)):
                return api.prefill(p, batch, cfg, cache,
                                   logit_pos=logit_pos)

        if self.paged:
            time_axes = self.pool.time_axes
            page_size = self.pool.page_size
            view_dtypes = self.pool.view_dtypes

            def _decode(p, tokens, data, scales, page_tables, positions):
                with dispatch.use(**tier(decode_quant)):
                    return api.decode_step_paged(
                        p, tokens, cfg, data, page_tables, positions,
                        batch_axes=batch_axes, time_axes=time_axes,
                        page_size=page_size, scales=scales,
                        view_dtypes=view_dtypes)
        else:
            def _decode(p, tokens, cache, positions):
                with dispatch.use(**tier(decode_quant)):
                    return api.decode_step_slots(p, tokens, cfg, cache,
                                                 positions,
                                                 batch_axes=batch_axes)

        def _make_chunk(first):
            def _chunk(p, batch, cache, pos):
                with dispatch.use(**tier(quant)):
                    return api.prefill_chunk(p, batch, cfg, cache, pos,
                                             first_chunk=first)
            return jax.jit(_chunk)

        self._prefill = jax.jit(_prefill)
        # the paged decode donates the pool (data, scales): its scatter
        # then writes the pool where it lies instead of into a copy
        self._decode = jax.jit(_decode,
                               donate_argnums=(2, 3) if self.paged else ())
        # which decode path the pool takes, for the ``decode`` span and
        # the ``decode_steps_in_place`` counter
        self.decode_path = (("in_place" if self.pool.in_place else "gather")
                            if self.paged else "slots")
        if pool.prefill_chunk:
            self._chunk_first = _make_chunk(True)
            self._chunk_rest = _make_chunk(False)
        self._sample = jax.jit(_sample_tokens)
        # greedy fast path: skips the sort/categorical work (and its
        # dispatch cost) when no active slot samples
        self._greedy = jax.jit(
            lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32))

    # ---------------- request lifecycle ----------------

    def submit(self, request: Request, *,
               on_token: Callable[[int, int, bool], Any] | None = None,
               trace: str | None = None) -> int:
        """Queue a request; returns its id (see ``scheduler.finished``).

        ``on_token(request_id, token, finished)`` streams the request's
        tokens as they are produced: it fires once per event, inside the
        ``step()`` that generated the token and in generation order, and
        never again after the ``finished=True`` call.  Exceptions from the
        callback propagate out of ``step()``/``serve()``.

        ``trace`` is an opaque trace id stamped onto the request's spans
        and events (the router passes its ticket id, so one client request
        is followable across retries/replicas); defaults to ``req<id>``.
        An explicit id forces the request to be span-sampled; ``""`` opts
        it out; ``None`` defers to the engine's ``trace_sample_rate``
        (every Nth submitted request gets the full span tree, counters
        stay always-on for the rest; ``None`` rate samples everything).
        """
        n_prompt = len(request.prompt)
        if n_prompt < 1:
            raise ValueError("empty prompt")
        need = self._pos_off + n_prompt + request.max_tokens
        if need > self.pool_cfg.max_len:
            raise ValueError(
                f"prompt ({n_prompt}) + max_tokens ({request.max_tokens}) "
                f"exceeds pool max_len ({self.pool_cfg.max_len})")
        stops = request.stop_tokens
        if stops is None:
            stops = ((self.cfg.eos_token,)
                     if self.cfg.eos_token is not None else ())
        self.metrics.requests_submitted += 1
        self._trace_count += 1
        if trace == "":
            sampled, trace = False, None
        elif trace is not None:
            sampled = True
        else:
            rate = self.trace_sample_rate
            sampled = (rate is None or rate <= 1
                       or (self._trace_count - 1) % rate == 0)
        rid = self.scheduler.submit(request, stop_tokens=tuple(stops),
                                    step=self.metrics.steps,
                                    now=self._clock(), trace=trace)
        if trace is None:
            self.scheduler.waiting[-1].trace = f"req{rid}"
        if sampled:
            self._trace_ids.add(rid)
        if on_token is not None:
            self._on_token[rid] = on_token
        obs.event("engine.submit", request_id=rid,
                  trace=self.scheduler.waiting[-1].trace,
                  prompt_len=n_prompt, max_tokens=request.max_tokens)
        return rid

    def _emit(self, request_id: int, token: int, finished: bool):
        """Build one step event, streaming it to the request's callback."""
        cb = self._on_token.get(request_id)
        if cb is not None:
            cb(request_id, token, finished)
            if finished:
                self._on_token.pop(request_id, None)
        return request_id, token, finished

    def _prompt_batch(self, request: Request):
        """(batch dict, logit_pos) for one request's prefill, optionally
        right-padded to the prefill bucket."""
        prompt = np.asarray(request.prompt, np.int32)
        n = len(prompt)
        pad_to = n
        bucket = self.pool_cfg.prefill_bucket
        if bucket:
            pad_to = min(self.pool_cfg.max_len, -(-n // bucket) * bucket)
        tokens = np.zeros((1, pad_to), np.int32)
        tokens[0, :n] = prompt
        batch = {"tokens": jnp.asarray(tokens)}
        if api.is_encdec(self.cfg):
            src = _as_batch1(request.src_embeds, "src_embeds")
            if src.shape[1] != self.pool_cfg.src_len:
                raise ValueError(
                    f"src_embeds length {src.shape[1]} != pool src_len "
                    f"{self.pool_cfg.src_len}")
            batch["src_embeds"] = src
        if self.cfg.n_patches:
            batch["patch_embeds"] = _as_batch1(request.patch_embeds,
                                               "patch_embeds")
        return batch, self._pos_off + n - 1

    def _admit(self, state: RequestState, slot: int):
        """Prefill + first token; returns the (id, token, finished) event."""
        req = state.request
        state.admit_time = self._clock()
        batch, logit_pos = self._prompt_batch(req)
        tr = obs.current_tracer()
        span = (tr.span("prefill", request_id=state.request_id,
                        trace=state.trace, prompt_len=len(req.prompt),
                        slot=slot)
                if tr is not None and state.request_id in self._trace_ids
                else obs.NULL_SPAN)
        with span:
            logits, rcache = self._prefill(self.params, batch,
                                           self.pool.request_cache(),
                                           jnp.int32(logit_pos))
            if self.paged:
                n_valid = self._pos_off + len(req.prompt)
                if not self.pool.insert(slot, rcache, n_valid):
                    # step() pre-checks the page budget, so this only
                    # trips on a logic error — fail loudly, not silently
                    raise RuntimeError(
                        f"page pool exhausted admitting request "
                        f"{state.request_id}")
            else:
                self.pool.insert(slot, rcache)
        return self._first_token(state, slot, logits)

    def _first_token(self, state: RequestState, slot: int, logits):
        """Sample the first token from prefill logits and activate the
        slot.  Shared tail of one-shot admission (``_admit``) and chunked
        prefill completion (``_staging_step``)."""
        req = state.request
        # prefill dispatch is async; the sample below syncs, so the
        # first_decode segment includes waiting out the prefill tail
        state.prefill_end_time = self._clock()
        self.metrics.prefills += 1
        self.scheduler.start(state, slot, self.metrics.steps)

        # first token comes from the prefill logits
        if req.temperature <= 0.0:
            out = self._greedy(logits)
        else:
            self._key, sub = jax.random.split(self._key)
            out = self._sample(
                logits, jnp.full((1,), req.temperature, jnp.float32),
                jnp.full((1,), req.top_k, jnp.int32), sub)
        with obs.span("prefill.wait"):
            tok = int(np.asarray(out)[0])
        self.metrics.tokens_generated += 1
        # a preempted request re-admits with its tokens folded into the
        # prompt: its TTFT was already recorded at first admission
        first = state.first_token_time is None
        if first:
            self.metrics.ttft_steps_sum += (self.metrics.steps
                                            - state.submit_step)
            self.metrics.ttft_count += 1
        finished = self.scheduler.record_token(state, tok,
                                               self.metrics.steps,
                                               now=self._clock())
        # first token always lands at admission => wall-clock TTFT is known
        if first and state.ttft_s is not None:
            self.metrics.ttft_s_sum += state.ttft_s
            self.metrics.ttft_hist.observe(state.ttft_s)
        if finished:
            self._evict(state)
            return state.request_id, tok, True
        n_valid = self._pos_off + len(req.prompt)
        self._tokens[slot] = tok
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self.pool.positions[slot] = n_valid   # next decode writes here
        self.pool.lengths[slot] = n_valid
        return state.request_id, tok, False

    def _evict(self, state: RequestState) -> None:
        self._release_slot(state.slot)
        self.metrics.requests_completed += 1
        tr = obs.current_tracer()
        if tr is not None and state.request_id in self._trace_ids:
            self._trace_request(tr, state)
        self._trace_ids.discard(state.request_id)

    def _trace_request(self, tracer, state: RequestState) -> None:
        """Emit the request's lifecycle as synthetic spans at eviction.

        A request lives across many ``step()`` calls, so its spans can't be
        open context managers; instead the scheduler's lifecycle stamps are
        replayed as one ``request`` span with ``request.queue`` /
        ``request.prefill`` / ``request.first_decode`` children cut from
        the same stamps as ``ttft_breakdown`` (they telescope exactly).
        """
        end = (state.finish_time if state.finish_time is not None
               else self._clock())
        root = tracer.add_span(
            "request", state.submit_time, end,
            request_id=state.request_id, trace=state.trace,
            status=state.status, finish_reason=state.finish_reason,
            tokens=len(state.generated), ttft_s=state.ttft_s)
        bd = state.ttft_breakdown
        if bd is None:
            return
        tracer.add_span("request.queue", state.submit_time,
                        state.admit_time, parent_id=root.span_id,
                        trace=state.trace)
        tracer.add_span("request.prefill", state.admit_time,
                        state.prefill_end_time, parent_id=root.span_id,
                        trace=state.trace)
        tracer.add_span("request.first_decode", state.prefill_end_time,
                        state.first_token_time, parent_id=root.span_id,
                        trace=state.trace)

    def _release_slot(self, slot: int) -> None:
        self.pool.free(slot)
        self._tokens[slot] = 0
        self._temps[slot] = 0.0
        self._topk[slot] = 0

    # ---------------- chunked prefill / preemption ----------------

    def _start_staging(self, state: RequestState, slot: int) -> None:
        """Begin a chunked prefill: the prompt is longer than the per-step
        prefill budget, so its chunks run one per ``step()`` against a
        private batch-1 cache view; the finished view is inserted into the
        pool in one scatter.  At most one request stages at a time
        (head-of-line admission bounds staging memory to one view)."""
        state.admit_time = self._clock()
        self._staging = {"state": state, "slot": slot,
                         "cache": self.pool.request_cache(),
                         "pos": 0, "first": True,
                         "logits": None, "ready": False}

    def _staging_step(self):
        """Advance the in-flight chunked prefill by one chunk (or retry a
        page-starved pool insert).  Returns ``(prefill tokens consumed,
        event or None)`` — the event fires on the chunk that completes the
        prompt *and* lands in the pool."""
        st = self._staging
        state, slot = st["state"], st["slot"]
        prompt = state.request.prompt
        consumed = 0
        if not st["ready"]:
            pos = st["pos"]
            width = min(self.pool_cfg.prefill_chunk, len(prompt) - pos)
            batch = {"tokens": jnp.asarray(
                np.asarray(prompt[pos:pos + width], np.int32)[None])}
            if api.is_encdec(self.cfg) and st["first"]:
                src = _as_batch1(state.request.src_embeds, "src_embeds")
                if src.shape[1] != self.pool_cfg.src_len:
                    raise ValueError(
                        f"src_embeds length {src.shape[1]} != pool "
                        f"src_len {self.pool_cfg.src_len}")
                batch["src_embeds"] = src
            chunk_fn = self._chunk_first if st["first"] else self._chunk_rest
            tr = obs.current_tracer()
            span = (tr.span("prefill.chunk", request_id=state.request_id,
                            trace=state.trace, pos=pos, width=width,
                            slot=slot)
                    if tr is not None
                    and state.request_id in self._trace_ids
                    else obs.NULL_SPAN)
            with span:
                logits, st["cache"] = chunk_fn(self.params, batch,
                                               st["cache"], jnp.int32(pos))
                if span is not obs.NULL_SPAN:
                    # a recorded span times the chunk's device work, as
                    # ``decode`` does; unrecorded chunks stay async
                    jax.block_until_ready(logits)
            st["first"] = False
            st["pos"] = pos + width
            self.metrics.prefill_chunks += 1
            consumed = width
            if st["pos"] < len(prompt):
                return consumed, None
            st["ready"] = True
            st["logits"] = logits
        # prompt fully prefilled: move the view into the pool (page-
        # starved inserts return False and are retried next step)
        n_valid = self._pos_off + len(prompt)
        if self.paged:
            if not self.pool.insert(slot, st["cache"], n_valid):
                return consumed, None
        else:
            self.pool.insert(slot, st["cache"])
        logits = st["logits"]
        self._staging = None
        return consumed, self._first_token(state, slot, logits)

    def _preempt(self, state: RequestState) -> None:
        """Evict a running request to reclaim its pages: its generated
        tokens fold into the prompt and it requeues first-in-line, so a
        greedy re-admission prefill recomputes the same KV and continues
        with the correct next token — nothing is emitted twice."""
        slot = state.slot
        obs.event("engine.preempt", request_id=state.request_id,
                  trace=state.trace, generated=len(state.generated))
        self.scheduler.preempt(state)
        self._release_slot(slot)
        self.metrics.preemptions += 1

    def _ensure_pages(self) -> None:
        """Paged pools only: guarantee every running slot owns the page
        its next decode write lands in, preempting the newest admissions
        while the free list is dry (newest-first keeps FCFS fairness and
        minimizes recompute)."""
        for slot in sorted(self.scheduler.running):
            state = self.scheduler.running.get(slot)
            if state is None:
                continue   # preempted earlier in this pass
            while not self.pool.ensure(slot, int(self.pool.positions[slot])):
                victim = max(self.scheduler.running.values(),
                             key=lambda s: (s.admit_step, s.request_id))
                self._preempt(victim)
                if victim is state:
                    break

    def gauges(self) -> dict[str, float]:
        """Point-in-time pool gauges (slot occupancy; page stats when
        paged) for metrics exporters."""
        g = {"kv_occupancy": self.pool.occupancy}
        if self.paged:
            g["kv_page_occupancy"] = self.pool.page_occupancy
            g["kv_page_fragmentation"] = self.pool.fragmentation
            g["kv_free_pages"] = float(self.pool.n_free_pages)
        return g

    def has_work(self) -> bool:
        """Whether any request is waiting, staging, or running."""
        return self._staging is not None or self.scheduler.has_work()

    def cancel(self, request_id: int) -> bool:
        """Cancel a waiting or running request mid-flight.

        A running request's KV slot is freed the same step (available to
        the next admission sweep), so a stuck or departed client no longer
        holds its slot until ``max_tokens``.  Its streaming callback is
        dropped without a ``finished=True`` call — cancellation is not a
        generated token.  Returns False when the id is unknown or already
        finished.
        """
        if (self._staging is not None
                and self._staging["state"].request_id == request_id):
            st, self._staging = self._staging, None
            self.scheduler._finish(st["state"], "cancelled",
                                   self.metrics.steps)
            self._release_slot(st["slot"])
            self._on_token.pop(request_id, None)
            self._trace_ids.discard(request_id)
            self.metrics.requests_cancelled += 1
            return True
        state = self.scheduler.cancel(request_id, step=self.metrics.steps)
        if state is None:
            return False
        if state.slot is not None:
            self._release_slot(state.slot)
        self._on_token.pop(request_id, None)
        self._trace_ids.discard(request_id)
        self.metrics.requests_cancelled += 1
        return True

    # ---------------- the serving loop ----------------

    def step(self):
        """One scheduler step: admit, batched decode, evict finished.

        Returns a list of ``(request_id, token, finished)`` events.

        Under an active tracer the step records the span ``step`` holding
        ``admit`` (chunked and one-shot prefills, each first token's
        ``prefill.wait``), ``pages``, ``decode`` (attribute ``path``:
        ``in_place``, ``gather`` or ``slots``; ``decode.upload`` of
        tokens, page tables and positions; ``decode.wait`` for the
        sampled tokens) and ``emit`` (per-slot bookkeeping, callbacks,
        evictions).
        """
        with obs.span("step"):
            return self._step()

    def _step(self):
        t0 = self._clock()
        self.metrics.steps += 1
        step = self.metrics.steps
        depth = self.scheduler.queue_depth
        self.metrics.queue_depth_sum += depth
        self.metrics.max_queue_depth = max(self.metrics.max_queue_depth,
                                           depth)

        events = []
        with obs.span("admit"):
            self._admit_step(events)
        if self.paged:
            with obs.span("pages"):
                self._ensure_pages()
        active = sorted(self.scheduler.running.items())
        if active:
            tr = obs.current_tracer()
            dspan = (tr.span("decode", step=step, n_active=len(active),
                             path=self.decode_path)
                     if tr is not None else obs.NULL_SPAN)
            with dspan:
                with obs.span("decode.upload"):
                    tokens = jnp.asarray(self._tokens)[:, None]
                    if self.paged:
                        tables = jnp.asarray(self.pool.page_tables)
                    positions = jnp.asarray(self.pool.positions)
                if self.paged:
                    logits, self.pool.data, self.pool.scales = self._decode(
                        self.params, tokens, self.pool.data,
                        self.pool.scales, tables, positions)
                else:
                    logits, self.pool.cache = self._decode(
                        self.params, tokens, self.pool.cache, positions)
                if not np.any(self._temps > 0):
                    out = self._greedy(logits)
                else:
                    self._key, sub = jax.random.split(self._key)
                    out = self._sample(
                        logits, jnp.asarray(self._temps),
                        jnp.asarray(self._topk), sub)
                with obs.span("decode.wait"):
                    toks = np.asarray(out)
            # np.asarray above syncs, so td1 is when every active slot's
            # token became known
            td1 = self._clock()
            with obs.span("emit"):
                self._emit_decoded(active, toks, step, td1, events)
        self.metrics.wall_time_s += self._clock() - t0
        return events

    def _admit_step(self, events: list) -> None:
        """Advance the in-flight chunked prefill, then admit waiting
        requests while slots, pages and the step's prefill budget last;
        appends each first-token event to ``events``."""
        # per-step prefill token budget (prefill_chunk): the in-flight
        # chunked prefill advances first, then one-shot admissions share
        # whatever is left — decodes never stall more than one chunk
        budget = self.pool_cfg.prefill_chunk
        spent = 0
        if self._staging is not None:
            consumed, event = self._staging_step()
            spent += consumed
            if event is not None:
                events.append(self._emit(*event))
        while self.pool.n_free and self.scheduler.waiting:
            if budget is not None and spent >= budget:
                break
            state = self.scheduler.next_waiting()
            n_prompt = len(state.request.prompt)
            if budget is not None and n_prompt > budget:
                # prompt longer than a whole step's budget: chunk it.
                # Staging starts only on a step with no prefill work yet,
                # so every chunk gets the full (page-aligned) budget.
                if self._staging is not None or spent:
                    self.scheduler.requeue(state)
                    break
                slot = self.pool.alloc()
                self._start_staging(state, slot)
                consumed, event = self._staging_step()
                spent += consumed
                if event is not None:
                    events.append(self._emit(*event))
                break
            if budget is not None and spent + n_prompt > budget:
                self.scheduler.requeue(state)
                break
            if (self.paged and -(-(self._pos_off + n_prompt)
                                 // self.pool.page_size)
                    > self.pool.n_free_pages):
                # not enough pages for the prompt: hold admission (decode
                # progress frees pages as running requests finish)
                self.scheduler.requeue(state)
                break
            slot = self.pool.alloc()
            try:
                event = self._admit(state, slot)
            except Exception:
                # retry-safe admission: a failed prefill frees the slot
                # and puts the request back first-in-line, so a router
                # retrying this step neither loses nor duplicates it
                self.scheduler.running.pop(slot, None)
                self._release_slot(slot)
                self.scheduler.requeue(state)
                raise
            events.append(self._emit(*event))
            spent += n_prompt

    def _emit_decoded(self, active, toks, step: int, now: float,
                      events: list) -> None:
        """Book a decode step's tokens: counters, each request's gap since
        its previous token, callbacks and evictions."""
        self.metrics.decode_steps += 1
        if self.decode_path == "in_place":
            self.metrics.decode_steps_in_place += 1
        self.metrics.slot_steps += len(active)
        self.metrics.slot_capacity_steps += self.pool.n_slots
        for slot, state in active:
            self.pool.positions[slot] += 1
            self.pool.lengths[slot] += 1
            tok = int(toks[slot])
            self.metrics.tokens_generated += 1
            self.metrics.token_latency_hist.observe(
                now - state.last_token_time)
            finished = self.scheduler.record_token(state, tok, step,
                                                   now=now)
            events.append(self._emit(state.request_id, tok, finished))
            if finished:
                self._evict(state)
            else:
                self._tokens[slot] = tok

    def serve(self, requests, *, key=None) -> dict[int, list[int]]:
        """Run ``requests`` to completion; returns {request_id: token ids}.

        Requests beyond the slot capacity queue and join mid-stream as
        earlier ones finish.  More can be ``submit()``-ed between ``step()``
        calls when driving the loop manually.
        """
        if key is not None:
            self._key = key
        ids = [self.submit(r) for r in requests]
        while self.has_work():
            self.step()
        return {rid: list(self.scheduler.finished[rid].generated)
                for rid in ids}
