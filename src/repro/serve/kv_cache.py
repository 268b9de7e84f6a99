"""KV-cache pools for continuous batching: slotted and paged.

``SlotKVCache`` is the slot-span pool: one device-resident cache tree sized
``(n_slots, max_len, ...)``, a host-side free-list allocator over slot
indices.  Capacity is bound by the *longest* request — every slot reserves
``max_len`` positions whether it needs them or not.

``PagedKVCache`` replaces the span per slot with fixed-size *pages*: each
growing cache leaf becomes a pool of ``n_pages`` pages (``page_size``
positions each) and every slot holds a page *table* — the address list a
paged decode batch-reduces over (``api.decode_step_paged``).  Pages are
allocated lazily as generation crosses page boundaries, so KV memory is
bound by *live tokens* (rounded up to a page), not by worst-case request
length; at equal memory the pool admits several times more concurrent
requests on mixed-length workloads.  Leaves whose shape does not grow with
``max_len`` (enc-dec cross-KV, recurrent states) stay slot-resident,
exactly as in the slotted pool.

Freeing a slot (or page) is purely host-side bookkeeping: stale device
state is never read again — page-table sentinels clip/drop on
gather/scatter and the attention length mask (``kv_len = pos + 1``) hides
anything beyond the live prefix.

With ``kv_quant="int8"`` the paged leaves are stored int8 with one fp32
absmax scale per page; dequantization is fused into the decode gather.
A pool that decodes in place (``api.decodes_in_place``: dense GQA,
full-precision pages) stores its pages key-major (``api.key_major``).
The paged pool's insert donates ``data`` and ``scales``, as the engine's
paged decode does: each returns the pool that replaces the one passed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchCfg
from repro.models import api


class SlotKVCache:
    """Fixed-capacity slot pool with a free-list allocator.

    Attributes
    ----------
    cache:       the pooled cache pytree (batch dimension = ``n_slots``).
    batch_axes:  per-leaf batch-axis tree (``api.cache_batch_axes``) —
                 pass to ``api.decode_step_slots``.
    lengths:     (n_slots,) int32, valid kv length per slot (prompt +
                 generated); 0 for free slots.
    positions:   (n_slots,) int32, absolute position the slot's pending
                 token will be written at on the next decode step.
    alloc_count / free_count: lifetime counters (leak check:
                 after drain, ``alloc_count == free_count`` and
                 ``n_free == n_slots``).
    """

    def __init__(self, cfg: ArchCfg, n_slots: int, max_len: int, *,
                 src_len: int = 0):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.src_len = src_len
        self.cache = api.init_cache(cfg, n_slots, max_len, src_len)
        self.batch_axes = api.cache_batch_axes(cfg, max_len, src_len)
        self.lengths = np.zeros(n_slots, np.int32)
        self.positions = np.zeros(n_slots, np.int32)
        self.alloc_count = 0
        self.free_count = 0
        # LIFO over a descending stack => lowest free slot allocated first
        # (deterministic placement for tests and reproducible runs).
        self._free = list(range(n_slots - 1, -1, -1))

        @jax.named_scope("kv_insert")
        def insert(pool, one, slot):
            return jax.tree.map(
                lambda p, o, a: jax.lax.dynamic_update_slice_in_dim(
                    p, o.astype(p.dtype), slot, axis=a),
                pool, one, self.batch_axes)

        self._insert = jax.jit(insert)

    # ---------------- allocator ----------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free) / self.n_slots

    def alloc(self) -> int | None:
        """Pop a free slot index, or None when the pool is full."""
        if not self._free:
            return None
        self.alloc_count += 1
        return self._free.pop()

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"double free of slot {slot}")
        self.free_count += 1
        self.lengths[slot] = 0
        self.positions[slot] = 0
        self._free.append(slot)

    # ---------------- device state ----------------

    def request_cache(self):
        """A zeroed batch-1 cache in the pool's layout (prefill target).

        Built once and shared: jax arrays are immutable, and prefill
        returns an updated copy rather than mutating its input."""
        if not hasattr(self, "_request_cache"):
            self._request_cache = api.init_cache(self.cfg, 1, self.max_len,
                                                 self.src_len)
        return self._request_cache

    def insert(self, slot: int, request_cache) -> None:
        """Scatter a prefilled batch-1 cache into ``slot``."""
        self.cache = self._insert(self.cache, request_cache,
                                  jnp.int32(slot))

    def kv_bytes(self) -> int:
        """Device bytes held by the pool (for capacity-per-GB reporting)."""
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(self.cache))


class PagedKVCache:
    """Paged KV pool: page-pool leaves + per-slot page tables.

    Layout
    ------
    data:        the pool pytree.  Pageable leaves (``time_axes[leaf] >=
                 0``) hold ``n_pages`` pages at the leaf's batch axis and
                 ``page_size`` positions at its time axis; slot-resident
                 leaves keep ``n_slots`` at the batch axis.
    page_tables: (n_slots, pages_per_slot) int32.  Row ``s`` lists slot
                 ``s``'s pages in position order; entries past the
                 allocation hold the sentinel ``n_pages`` (clipped on
                 gather, dropped on scatter).
    scales:      with ``kv_quant``, one (n_pages,) fp32 scale array per
                 pageable leaf (flatten order), else None.
    in_place:    whether decode reads the pool in place
                 (``api.decodes_in_place``); its pageable leaves then
                 hold key-major pages (``api.key_major``).
    lengths / positions: as in :class:`SlotKVCache`.

    The allocator is host-side and O(1) per op: a slot free-list plus a
    page free-list, with lifetime counters for leak checks
    (``page_alloc_count == page_free_count`` after drain).
    """

    def __init__(self, cfg: ArchCfg, n_slots: int, max_len: int, *,
                 page_size: int, n_pages: int | None = None,
                 src_len: int = 0, kv_quant: str | None = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if not api.supports_paging(cfg):
            raise ValueError(
                f"paging is not supported for block={cfg.block!r} "
                f"(window={cfg.window}, n_patches={cfg.n_patches})")
        if kv_quant is not None and kv_quant != "int8":
            raise ValueError(
                f"kv_quant={kv_quant!r}: only 'int8' page storage is "
                "supported")
        self.cfg = cfg
        self.n_slots = n_slots
        self.page_size = page_size
        self.pages_per_slot = -(-max_len // page_size)
        self.max_len = self.pages_per_slot * page_size   # page-aligned view
        self.src_len = src_len
        self.n_pages = (n_pages if n_pages is not None
                        else n_slots * self.pages_per_slot)
        if self.n_pages < self.pages_per_slot:
            raise ValueError(
                f"n_pages={self.n_pages} cannot hold even one full slot "
                f"({self.pages_per_slot} pages)")
        self.kv_quant = kv_quant

        self.batch_axes = api.cache_batch_axes(cfg, self.max_len, src_len)
        self.time_axes = api.cache_time_axes(cfg, src_len)
        paged_tmpl = api.init_cache(cfg, self.n_pages, page_size, src_len)
        resident_tmpl = api.init_cache(cfg, n_slots, page_size, src_len)
        self.view_dtypes = tuple(
            x.dtype for x, t in zip(jax.tree.leaves(paged_tmpl),
                                    jax.tree.leaves(self.time_axes))
            if t != -1)
        if kv_quant:
            paged_tmpl = jax.tree.map(
                lambda x, t: (jnp.zeros(x.shape, jnp.int8) if t != -1
                              else x),
                paged_tmpl, self.time_axes)
            self.scales = tuple(
                jnp.zeros((self.n_pages,), jnp.float32)
                for t in jax.tree.leaves(self.time_axes) if t != -1)
        else:
            self.scales = None
        self.in_place = api.decodes_in_place(cfg, self.time_axes,
                                             self.scales)
        if self.in_place:
            paged_tmpl = jax.tree.map(api.key_major, paged_tmpl,
                                      self.time_axes)
        self.data = jax.tree.map(
            lambda pg, res, t: pg if t != -1 else res,
            paged_tmpl, resident_tmpl, self.time_axes)

        self.lengths = np.zeros(n_slots, np.int32)
        self.positions = np.zeros(n_slots, np.int32)
        # sentinel n_pages: clipped on gather, dropped on scatter
        self.page_tables = np.full((n_slots, self.pages_per_slot),
                                   self.n_pages, np.int32)
        self.pages_used = np.zeros(n_slots, np.int32)
        self.alloc_count = 0
        self.free_count = 0
        self.page_alloc_count = 0
        self.page_free_count = 0
        self._free = list(range(n_slots - 1, -1, -1))
        self._free_pages = list(range(self.n_pages - 1, -1, -1))

        page_size_, in_place = page_size, self.in_place
        batch_axes, time_axes = self.batch_axes, self.time_axes

        @jax.named_scope("kv_insert")
        def insert(data, scales, one, slot, page_ids):
            """Scatter a prefilled batch-1 view: pageable leaves split into
            pages and land at ``page_ids``; resident leaves slice in at
            ``slot``."""
            leaves, treedef = jax.tree.flatten(data)
            ones = treedef.flatten_up_to(one)
            a_l = treedef.flatten_up_to(batch_axes)
            t_l = treedef.flatten_up_to(time_axes)
            new_scales = list(scales) if scales is not None else None
            out, pi = [], 0
            for x, o, a, t in zip(leaves, ones, a_l, t_l):
                if t == -1:
                    out.append(jax.lax.dynamic_update_slice_in_dim(
                        x, o.astype(x.dtype), slot, axis=a))
                    continue
                pages = api.view_to_pages(o, a, t, page_size_)
                if in_place:
                    pages = api.key_major(pages, t)
                if scales is not None:
                    pages, sc = api._quant_pages(pages, a)
                    new_scales[pi] = new_scales[pi].at[page_ids].set(
                        sc, mode="drop")
                idx = (slice(None),) * a + (page_ids,)
                out.append(x.at[idx].set(pages.astype(x.dtype),
                                         mode="drop"))
                pi += 1
            new_data = jax.tree.unflatten(treedef, out)
            if scales is None:
                return new_data, None
            return new_data, tuple(new_scales)

        self._insert = jax.jit(insert, donate_argnums=(0, 1))

    # ---------------- allocator ----------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free) / self.n_slots

    @property
    def page_occupancy(self) -> float:
        return 1.0 - len(self._free_pages) / self.n_pages

    @property
    def fragmentation(self) -> float:
        """Allocated-but-dead fraction: 1 - live tokens / paged capacity.

        Internal fragmentation only (partially filled trailing pages) —
        fixed-size pages cannot fragment externally.
        """
        cap = int(self.pages_used.sum()) * self.page_size
        if cap == 0:
            return 0.0
        return 1.0 - float(self.lengths.sum()) / cap

    def alloc(self) -> int | None:
        """Pop a free slot index, or None when the pool is full."""
        if not self._free:
            return None
        self.alloc_count += 1
        return self._free.pop()

    def alloc_pages(self, slot: int, n: int) -> bool:
        """Append ``n`` pages to ``slot``'s table; all-or-nothing."""
        if n <= 0:
            return True
        used = int(self.pages_used[slot])
        if used + n > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {used}+{n} pages exceeds pages_per_slot="
                f"{self.pages_per_slot}")
        if len(self._free_pages) < n:
            return False
        for i in range(n):
            self.page_tables[slot, used + i] = self._free_pages.pop()
        self.pages_used[slot] = used + n
        self.page_alloc_count += n
        return True

    def ensure(self, slot: int, pos: int) -> bool:
        """Make sure the page containing position ``pos`` is allocated."""
        need = pos // self.page_size + 1
        return self.alloc_pages(slot, need - int(self.pages_used[slot]))

    def free(self, slot: int) -> None:
        """Release a slot and every page it holds."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"double free of slot {slot}")
        used = int(self.pages_used[slot])
        for i in range(used):
            self._free_pages.append(int(self.page_tables[slot, i]))
        self.page_free_count += used
        self.page_tables[slot, :] = self.n_pages
        self.pages_used[slot] = 0
        self.free_count += 1
        self.lengths[slot] = 0
        self.positions[slot] = 0
        self._free.append(slot)

    # ---------------- device state ----------------

    def request_cache(self):
        """A zeroed batch-1 cache view (prefill target), length
        ``pages_per_slot * page_size``.  Built once and shared."""
        if not hasattr(self, "_request_cache"):
            self._request_cache = api.init_cache(self.cfg, 1, self.max_len,
                                                 self.src_len)
        return self._request_cache

    def insert(self, slot: int, request_cache, n_valid: int) -> bool:
        """Allocate pages for ``n_valid`` positions and scatter a prefilled
        batch-1 view into them.  False (nothing changed) when the page
        pool cannot cover the request yet — retryable next step."""
        need = -(-n_valid // self.page_size) - int(self.pages_used[slot])
        if not self.alloc_pages(slot, need):
            return False
        self.data, self.scales = self._insert(
            self.data, self.scales, request_cache, jnp.int32(slot),
            jnp.asarray(self.page_tables[slot]))
        return True

    def kv_bytes(self) -> int:
        """Device bytes held by the pool (pages + scales + resident)."""
        total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(self.data))
        if self.scales is not None:
            total += sum(int(np.prod(s.shape)) * s.dtype.itemsize
                         for s in self.scales)
        return total
