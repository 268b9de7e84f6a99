"""Uniform model API + input specs for every (arch x shape) cell.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no device allocation) for the entry point that each shape kind
lowers: ``train_step`` for train shapes, ``prefill``/``decode_step`` for
inference shapes.  ``make_batch`` materializes small concrete batches for
smoke tests.

Modality stubs (per assignment): [vlm] patch embeddings and [audio] frame
embeddings enter as precomputed inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchCfg
from repro.configs.shapes import ShapeCfg
from repro.models import encdec, transformer


def is_encdec(cfg: ArchCfg) -> bool:
    return cfg.block == "encdec"


def get_module(cfg: ArchCfg):
    return encdec if is_encdec(cfg) else transformer


def init_params(key, cfg: ArchCfg):
    return get_module(cfg).init_params(key, cfg)


def loss_fn(params, batch, cfg: ArchCfg, **kw):
    return get_module(cfg).loss_fn(params, batch, cfg, **kw)


def forward(params, batch, cfg: ArchCfg, **kw):
    return get_module(cfg).forward(params, batch, cfg, **kw)


def prefill(params, batch, cfg: ArchCfg, cache, **kw):
    return get_module(cfg).prefill(params, batch, cfg, cache, **kw)


def decode_step(params, tokens, cfg: ArchCfg, cache, pos, **kw):
    return get_module(cfg).decode_step(params, tokens, cfg, cache, pos, **kw)


def prefill_chunk(params, batch, cfg: ArchCfg, cache, pos, *, length=None,
                  first_chunk: bool = True, **kw):
    """One chunk of a longer prompt against a batch-1 cache view.

    ``first_chunk`` is only meaningful for enc-dec (runs the encoder and
    caches cross-KV); decoder-only models ignore it.
    """
    if is_encdec(cfg):
        return encdec.prefill_chunk(params, batch, cfg, cache, pos,
                                    length=length, first_chunk=first_chunk,
                                    **kw)
    return transformer.prefill_chunk(params, batch, cfg, cache, pos,
                                     length=length, **kw)


# --------------------------------------------------------------------------
# slot-indexed decode (continuous batching)
# --------------------------------------------------------------------------

def init_cache(cfg: ArchCfg, batch: int, max_len: int, src_len: int = 0):
    """Serve cache for either module (``src_len`` only used by enc-dec)."""
    if is_encdec(cfg):
        return encdec.init_cache(cfg, batch, max_len, src_len)
    return transformer.init_cache(cfg, batch, max_len)


def cache_batch_axes(cfg: ArchCfg, max_len: int, src_len: int = 0):
    """Per-leaf batch-axis tree for the serve cache.

    The cache pytree mixes leaves whose batch dimension sits at different
    positions (layer-stacked KV leaves carry it at axis 1, grouped
    recurrent states at axis 2, ...).  Rather than hard-coding the layout
    per architecture family, diff the abstract shapes of a batch-1 and a
    batch-2 cache: the single axis whose extent changed is the batch axis.
    The result matches the cache tree structure, so it can be passed
    directly as a ``vmap`` in/out axes tree.
    """
    one = jax.eval_shape(lambda: init_cache(cfg, 1, max_len, src_len))
    two = jax.eval_shape(lambda: init_cache(cfg, 2, max_len, src_len))

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"ambiguous batch axis for cache leaf {a.shape}: {diffs}")
        return diffs[0]

    return jax.tree.map(axis, one, two)


def decode_step_slots(params, tokens, cfg: ArchCfg, cache, positions, *,
                      batch_axes, **kw):
    """One decode step over a slot pool with per-slot positions.

    ``tokens``: (S, 1) int32 — last sampled token per slot; ``positions``:
    (S,) int32 — the absolute position each slot's token is written at;
    ``cache``: a slot pool (batch dimension = S); ``batch_axes``: the tree
    from :func:`cache_batch_axes`.  Returns (logits (S, V), new cache).

    Implemented as a vmap of the ordinary batch-1 ``decode_step`` over the
    slot dimension, so every architecture family's decode path (padded KV,
    ring buffers, compressed MLA caches, recurrent states) gets per-slot
    position/length semantics without per-family code: cache writes become
    scatters and the kv-length masks become per-slot masks under the
    batching rules.  Free slots decode garbage that is never read — their
    writes land at positions a later prefill/decode overwrites before any
    attention mask exposes them.
    """
    def one(tok, c, pos):
        c = jax.tree.map(lambda x, a: jnp.expand_dims(x, a), c, batch_axes)
        logits, c = decode_step(params, tok[None, :], cfg, c, pos, **kw)
        c = jax.tree.map(lambda x, a: jnp.squeeze(x, a), c, batch_axes)
        return logits[0], c

    return jax.vmap(one, in_axes=(0, batch_axes, 0),
                    out_axes=(0, batch_axes))(tokens, cache, positions)


# --------------------------------------------------------------------------
# paged decode (batch-reduce over page lists)
# --------------------------------------------------------------------------

def supports_paging(cfg: ArchCfg) -> bool:
    """Whether the serve cache can be paged for this architecture.

    Paging needs every growing cache leaf to be a position-indexed KV
    tensor whose reads are masked by ``kv_len`` — true for full-attention
    decoders (dense/moe/mla_moe) and the enc-dec decoder.  Sliding-window
    ring buffers index ``pos % window`` (a page holds no stable position
    range) and recurrent states have no time axis at all, so those
    families stay on the slotted pool.
    """
    return (cfg.block in ("dense", "moe", "mla_moe", "encdec")
            and not cfg.window and not cfg.n_patches)


def cache_time_axes(cfg: ArchCfg, src_len: int = 0):
    """Per-leaf *time*-axis tree for the serve cache (-1 = not pageable).

    Discovered structurally, like :func:`cache_batch_axes`: diff the
    abstract shapes of two caches built at different ``max_len`` — the
    single axis whose extent changed with ``max_len`` is the time axis.
    Leaves whose shape does not depend on ``max_len`` (recurrent states,
    ring buffers, enc-dec cross-KV at fixed ``src_len``) get ``-1``: they
    stay slot-resident under paging.
    """
    a = jax.eval_shape(lambda: init_cache(cfg, 1, 16, src_len))
    b = jax.eval_shape(lambda: init_cache(cfg, 1, 32, src_len))

    def axis(x, y):
        diffs = [i for i, (m, n) in enumerate(zip(x.shape, y.shape))
                 if m != n]
        if not diffs:
            return -1
        if len(diffs) != 1:
            raise ValueError(
                f"ambiguous time axis for cache leaf {x.shape}: {diffs}")
        return diffs[0]

    return jax.tree.map(axis, a, b)


def pages_to_view(pages, a: int, t: int):
    """(P pages at axis ``a``, page_size at axis ``t``) -> contiguous
    batch-1 cache view with ``P * page_size`` at the time axis."""
    x = jnp.moveaxis(pages, a, t - 1)
    shape = x.shape[:t - 1] + (x.shape[t - 1] * x.shape[t],) + x.shape[t + 1:]
    return jnp.expand_dims(x.reshape(shape), a)


def view_to_pages(view, a: int, t: int, page_size: int):
    """Inverse of :func:`pages_to_view`."""
    x = jnp.squeeze(view, a)
    shape = (x.shape[:t - 1] + (x.shape[t - 1] // page_size, page_size)
             + x.shape[t:])
    return jnp.moveaxis(x.reshape(shape), t - 1, a)


def _dequant_pages(pages, scale, a: int, dtype):
    """int8 pages * per-page scale (broadcast from axis ``a``) -> dtype."""
    shape = [1] * pages.ndim
    shape[a] = pages.shape[a]
    return (pages.astype(jnp.float32) * scale.reshape(shape)).astype(dtype)


def _quant_pages(pages, a: int):
    """Per-page absmax int8: returns (q, (n_pages_axis,) fp32 scales)."""
    from repro.core.quantize import quantize
    axes = tuple(i for i in range(pages.ndim) if i != a)
    return quantize(pages, "int8", axis=axes)


def decodes_in_place(cfg: ArchCfg, time_axes, scales) -> bool:
    """Whether :func:`decode_step_paged` reads the pool in place.

    True for dense blocks with GQA/MQA ``k``/``v`` leaves and no window,
    full-precision pages (``scales is None``) and no slot-resident
    leaves.  Such a pool stores its pages key-major (:func:`key_major`).
    Every other pool (MLA, MoE, enc-dec, int8 pages) gathers: MoE routing
    must keep its per-slot groups, and int8 dequant stays in the gather.
    """
    return (cfg.block == "dense" and not cfg.mla and not cfg.window
            and scales is None
            and all(t != -1 for t in jax.tree.leaves(time_axes)))


def key_major(pages, t: int):
    """Swap the time axis ``t`` of pages with their last axis (its own
    inverse): the page layout of a pool decoded in place.  With positions
    on the lanes a page of a head is (d, page_size), aligned to the TPU's
    tiles for any head size, so the kernel DMAs it as it lies; XLA stores
    a (page_size, d < 128) page transposed anyway."""
    return jnp.swapaxes(pages, t, -1)


def decode_step_paged(params, tokens, cfg: ArchCfg, data, page_tables,
                      positions, *, batch_axes, time_axes, page_size,
                      scales=None, view_dtypes=None, **kw):
    """One decode step over a paged pool.

    ``data``: the pool pytree — pageable leaves hold ``n_pages`` pages at
    their batch axis and ``page_size`` at their time axis (key-major
    where :func:`decodes_in_place`); slot-resident leaves (``time_axes``
    == -1) hold ``n_slots`` entries at their batch axis.
    ``page_tables``: (S, P) int32 page ids, padded with the sentinel
    ``n_pages`` past each slot's allocation.  ``positions``: (S,)
    absolute write position per slot.  ``scales``: with quantized pages,
    a tuple of (n_pages,) fp32 per-page scale arrays aligned with the
    pageable leaves in flatten order (``view_dtypes`` gives each leaf's
    compute dtype).  Returns (logits (S, V), new data, new scales).

    Two paths, chosen by :func:`decodes_in_place`.  In place: one decode
    at batch S whose attention kernel reads each slot's live pages
    through its page table, then one scatter of the token's K/V rows
    (scope ``attention/kv_write``).  Otherwise each slot gathers its
    page list into a contiguous view and scatters every page back
    (scopes ``kv_gather``, ``kv_scatter``).
    """
    if decodes_in_place(cfg, time_axes, scales):
        return _decode_in_place(params, tokens, cfg, data, page_tables,
                                positions, page_size=page_size, **kw)
    return _decode_gather(params, tokens, cfg, data, page_tables, positions,
                          batch_axes=batch_axes, time_axes=time_axes,
                          page_size=page_size, scales=scales,
                          view_dtypes=view_dtypes, **kw)


def _decode_in_place(params, tokens, cfg: ArchCfg, data, page_tables,
                     positions, *, page_size, **kw):
    """The in-place path of :func:`decode_step_paged`: the pool is read
    by the attention kernel and written once, at each slot's position
    (free slots' sentinel pages drop out)."""
    kv = data["blocks"]
    logits, rows = transformer.decode_step_in_place(
        params, tokens, cfg, kv, page_tables, positions, **kw)
    with jax.named_scope("attention"), jax.named_scope("kv_write"):
        page = jnp.take_along_axis(page_tables,
                                   (positions // page_size)[:, None],
                                   axis=1, mode="clip")[:, 0]
        new = {name: _write_rows(x, rows[name], page, positions % page_size)
               for name, x in kv.items()}
    return logits, {"blocks": new}, None


def _write_rows(pool, rows, page, off):
    """``pool`` (L, n_pages, Hkv, d, page_size) with ``rows`` (L, S, Hkv,
    d) written at each slot's (``page``, ``off``); sentinel pages drop.

    Each slot's page is read, its column ``off`` replaced and the whole
    page written back: a scatter of whole pages keeps the pool's layout,
    where a scatter of single columns has XLA relayout (copy) the pool.
    """
    n_pages, width = pool.shape[1], pool.shape[-1]
    old = pool[:, jnp.clip(page, 0, n_pages - 1)]     # (L, S, Hkv, d, w)
    col = jnp.arange(width) == off[:, None, None, None]
    new = jnp.where(col, rows[..., None].astype(pool.dtype), old)
    return pool.at[:, page].set(new, mode="drop")


def _decode_gather(params, tokens, cfg: ArchCfg, data, page_tables,
                   positions, *, batch_axes, time_axes, page_size,
                   scales=None, view_dtypes=None, **kw):
    """The gather path of :func:`decode_step_paged`.

    Per slot (vmapped): gather its page list (sentinels clip to page 0 —
    garbage that ``kv_len`` masking never exposes), reassemble a
    contiguous batch-1 view of length ``P * page_size``, run the ordinary
    ``decode_step``, and split the view back into pages.  Outside the
    vmap, each leaf's updated pages scatter into the pool in one
    ``mode="drop"`` write (sentinel ids fall out), so the whole step stays
    one jit-compiled call.

    With quantized pages, dequant happens in the gather and fresh scales
    are computed in the scatter.  The gather runs under the named scope
    ``kv_gather``, the split back into pages and the pool scatter under
    ``kv_scatter``.
    """
    data_leaves, treedef = jax.tree.flatten(data)
    a_leaves = treedef.flatten_up_to(batch_axes)
    t_leaves = treedef.flatten_up_to(time_axes)
    quant = scales is not None
    resident = tuple(x for x, t in zip(data_leaves, t_leaves) if t == -1)
    res_axes = tuple(a for a, t in zip(a_leaves, t_leaves) if t == -1)

    def one(tok, pt, res, pos):
        res_it = iter(res)
        scale_it = iter(scales or ())
        dtype_it = iter(view_dtypes or ())
        view_leaves = []
        with jax.named_scope("kv_gather"):
            for x, a, t in zip(data_leaves, a_leaves, t_leaves):
                if t == -1:
                    view_leaves.append(jnp.expand_dims(next(res_it), a))
                    continue
                ids = jnp.clip(pt, 0, x.shape[a] - 1)
                pages = jnp.take(x, ids, axis=a)
                if quant:
                    pages = _dequant_pages(
                        pages, jnp.take(next(scale_it), ids), a,
                        next(dtype_it))
                view_leaves.append(pages_to_view(pages, a, t))
        view = jax.tree.unflatten(treedef, view_leaves)
        logits, new = decode_step(params, tok[None, :], cfg, view, pos, **kw)
        out_pages, out_res = [], []
        with jax.named_scope("kv_scatter"):
            for x, a, t in zip(treedef.flatten_up_to(new), a_leaves,
                               t_leaves):
                if t == -1:
                    out_res.append(jnp.squeeze(x, a))
                else:
                    out_pages.append(view_to_pages(x, a, t, page_size))
        return logits[0], tuple(out_pages), tuple(out_res)

    logits, pages_upd, res_upd = jax.vmap(
        one, in_axes=(0, 0, res_axes, 0),
        out_axes=(0, 0, res_axes))(tokens, page_tables, resident, positions)

    flat_ids = page_tables.reshape(-1)
    new_leaves = list(data_leaves)
    new_scales = list(scales) if quant else None
    pi = ri = 0
    with jax.named_scope("kv_scatter"):
        for i, (x, a, t) in enumerate(zip(data_leaves, a_leaves, t_leaves)):
            if t == -1:
                new_leaves[i] = res_upd[ri]
                ri += 1
                continue
            u = jnp.moveaxis(pages_upd[pi], 0, a)   # slot axis next to pages
            u = u.reshape(u.shape[:a] + (-1,) + u.shape[a + 2:])
            if quant:
                u, sc = _quant_pages(u, a)
                new_scales[pi] = new_scales[pi].at[flat_ids].set(
                    sc, mode="drop")
            idx = (slice(None),) * a + (flat_ids,)
            new_leaves[i] = x.at[idx].set(u.astype(x.dtype), mode="drop")
            pi += 1
    new_data = jax.tree.unflatten(treedef, new_leaves)
    if quant:
        return logits, new_data, tuple(new_scales)
    return logits, new_data, None


# --------------------------------------------------------------------------
# shape bookkeeping
# --------------------------------------------------------------------------

def encdec_src_len(cfg: ArchCfg, shape: ShapeCfg) -> int:
    if shape.kind == "train":
        return shape.seq_len // 2
    return min(4096, shape.seq_len // 8)


def token_len(cfg: ArchCfg, shape: ShapeCfg) -> int:
    """Decoder-token length for the given shape (stub prefixes deducted)."""
    if is_encdec(cfg):
        if shape.kind == "train":
            return shape.seq_len - encdec_src_len(cfg, shape)
        if shape.kind == "prefill":
            return shape.seq_len - encdec_src_len(cfg, shape)
        return shape.seq_len
    if cfg.n_patches and shape.kind in ("train", "prefill"):
        return shape.seq_len - cfg.n_patches
    return shape.seq_len


def input_specs(cfg: ArchCfg, shape: ShapeCfg):
    """ShapeDtypeStructs for the batch of the shape's entry point."""
    b = shape.global_batch
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32
    tl = token_len(cfg, shape)

    if shape.kind in ("train",):
        batch = {"tokens": jax.ShapeDtypeStruct((b, tl), i32),
                 "labels": jax.ShapeDtypeStruct((b, tl), i32)}
        if cfg.n_patches:
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), dt)
        if is_encdec(cfg):
            batch["src_embeds"] = jax.ShapeDtypeStruct(
                (b, encdec_src_len(cfg, shape), cfg.d_model), dt)
        return batch

    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((b, tl), i32)}
        if cfg.n_patches:
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), dt)
        if is_encdec(cfg):
            batch["src_embeds"] = jax.ShapeDtypeStruct(
                (b, encdec_src_len(cfg, shape), cfg.d_model), dt)
        return batch

    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    raise ValueError(shape.kind)


def cache_specs(cfg: ArchCfg, shape: ShapeCfg):
    """Abstract cache tree for serve shapes (eval_shape: no allocation)."""
    b = shape.global_batch

    def build():
        if is_encdec(cfg):
            return encdec.init_cache(
                cfg, b, shape.seq_len, encdec_src_len(cfg, shape))
        return transformer.init_cache(cfg, b, shape.seq_len)

    return jax.eval_shape(build)


def params_specs(key, cfg: ArchCfg):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def make_batch(key, cfg: ArchCfg, shape: ShapeCfg):
    """Concrete random batch (for smoke tests on reduced configs)."""
    specs = input_specs(cfg, shape)
    ks = jax.random.split(key, len(specs))
    out = {}
    for k_, (name, s) in zip(ks, sorted(specs.items())):
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(k_, s.shape, 0, cfg.vocab,
                                           jnp.int32)
        else:
            out[name] = jax.random.normal(k_, s.shape, jnp.float32).astype(
                s.dtype)
    return out
