"""Blocking factors for every kernel in the library, on TPU.

The paper picks (m_b, n_b) so the accumulator block lives in registers and
the A/B panels stream from cache (Sec. 2, Fig. 2b).  On TPU the constraints
become:

  * lane dimension (last axis) must be a multiple of 128,
  * sublane dimension (second-minor) a multiple of 8 (fp32) / 16 (bf16) /
    32 (int8) for efficient VREG tiling,
  * MXU is a 128x128 systolic array -> contraction and output dims want to
    be multiples of 128,
  * the working set (A panel + B panel, double-buffered, + fp32 accumulator)
    must fit the ~16 MiB/core VMEM.

Every op family has its own block tuple (GEMM ``Blocks``, conv
``ConvBlocks``, attention ``AttnBlocks``) but they all resolve through one
schema table: :func:`default_blocks` is the static heuristic,
:func:`candidate_blocks` enumerates the pruned VMEM-feasible search grid the
measured autotuner (``core.autotune``) walks, and
``blocks_to_dict``/``blocks_from_dict`` give every tuple a JSON round-trip
for the persisted tuning cache.  Each op maps its loop nest onto a
canonical (m, n, k) triple — see the schema table at the bottom.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

LANE = 128
VMEM_BYTES = 16 * 1024 * 1024
# Leave headroom for Mosaic spills / semaphores / the output buffer.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024


def sublane(dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class Blocks:
    bm: int
    bn: int
    bk: int

    def astuple(self):
        return (self.bm, self.bn, self.bk)


def choose_blocks(
    m: int,
    n: int,
    k: int,
    dtype=jnp.float32,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    prefer_bm: int = 128,
    prefer_bn: int = 128,
    prefer_bk: int = 512,
) -> Blocks:
    """Pick (bm, bn, bk) for a (m x k) @ (k x n) batch-reduce GEMM.

    Small dims are rounded up to the hardware tile (the wrapper pads), large
    dims get the preferred MXU-friendly block, and bk is shrunk until the
    double-buffered working set fits the VMEM budget.  A weight-streaming
    GEMM (see :func:`streams_weight`) takes :func:`_streaming_blocks`.
    """
    itemsize = jnp.dtype(dtype).itemsize
    sub = sublane(dtype)
    if streams_weight(m, n, k):
        return _streaming_blocks(m, n, k, itemsize, sub, vmem_budget)

    bm = min(round_up(m, sub), prefer_bm)
    bm = round_up(bm, sub)
    bn = min(round_up(n, LANE), prefer_bn)
    bk = min(round_up(k, LANE), prefer_bk)

    while gemm_working_set(bm, bn, bk, itemsize) > vmem_budget and bk > LANE:
        bk = max(LANE, bk // 2)
    while gemm_working_set(bm, bn, bk, itemsize) > vmem_budget and bm > sub:
        bm = max(sub, bm // 2)
    return Blocks(bm=bm, bn=bn, bk=bk)


# A GEMM of at most STREAM_ROWS rows against a weight at least STREAM_DIM
# wide in both dims (a wide model's prefill chunk or decode step) spends
# its time streaming the weight.  128 x 128 tiles would re-read each weight
# panel once per 128 rows and take thousands of short grid steps.
STREAM_ROWS = 512
STREAM_DIM = 1024
STREAM_PANEL = 1024


def streams_weight(m: int, n: int, k: int) -> bool:
    return m <= STREAM_ROWS and min(n, k) >= STREAM_DIM


def divisor_block(dim: int, cap: int) -> int:
    """The largest multiple of LANE, at most ``cap``, that divides ``dim``
    rounded up to LANE: a block that tiles the dim with no padding."""
    units = round_up(dim, LANE) // LANE
    return LANE * max(u for u in range(1, max(cap // LANE, 1) + 1)
                      if units % u == 0)


def _streaming_blocks(m, n, k, itemsize, sub, vmem_budget) -> Blocks:
    """Every row in one block, so each weight panel is read once, and
    panels up to STREAM_PANEL wide that divide the weight's dims, so the
    weight is never padded (bk shrinks first to fit VMEM)."""
    bm = round_up(m, sub)
    bn = divisor_block(n, STREAM_PANEL)
    bk = divisor_block(k, STREAM_PANEL)
    while gemm_working_set(bm, bn, bk, itemsize) > vmem_budget and bk > LANE:
        bk = divisor_block(k, bk - LANE)
    while gemm_working_set(bm, bn, bk, itemsize) > vmem_budget and bn > LANE:
        bn = divisor_block(n, bn - LANE)
    return Blocks(bm=bm, bn=bn, bk=bk)


def gemm_working_set(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """VMEM bytes for one GEMM tile: A/B panels double-buffered + fp32
    accumulator scratch + double-buffered output block.  The single
    feasibility model shared by the heuristic and the candidate grid."""
    panels = (bm * bk + bk * bn) * itemsize * 2
    return panels + bm * bn * 4 + bm * bn * itemsize * 2


# --------------------------------------------------------------------------
# op-specific block tuples
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvBlocks:
    """Direct-convolution tile: bq output pixels x bc input channels
    (the reduce panel) x bk output channels."""
    bq: int
    bc: int
    bk: int

    def astuple(self):
        return (self.bq, self.bc, self.bk)


@dataclasses.dataclass(frozen=True)
class AttnBlocks:
    """Flash-attention tile: block_q query rows x block_k kv rows per
    online-softmax step."""
    block_q: int
    block_k: int

    def astuple(self):
        return (self.block_q, self.block_k)


@dataclasses.dataclass(frozen=True)
class AttnBwdBlocks:
    """Flash-attention *backward* tile: block_q query rows x block_k kv
    rows per batch-reduce step of the dQ / dK/dV kernels.

    A separate tuple from ``AttnBlocks`` because the backward working set
    is very different from the forward's (q + dy + lse + delta panels on
    the q side, k + v panels plus dk/dv accumulators on the kv side), so
    the autotuner must be free to pick backward tiles independently of the
    forward winner for the same (tq, tk, d)."""
    block_q: int
    block_k: int

    def astuple(self):
        return (self.block_q, self.block_k)


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """The non-canonical conv2d geometry (stride and filter extent) that
    shapes the real kernel's working set: the input panel streamed per
    grid step spans the *strided* output row plus the filter overhang, not
    the 1x1/stride-1 proxy.  Threaded through ``resolve_blocks`` so the
    candidate pruning, the autotune proxy problem, and the tuning-cache
    key all see the geometry the kernel will actually run."""
    kind = "conv"  # JSON tag (class attribute, not a field)
    stride: int
    r: int
    s: int

    def asdict(self):
        return {"kind": self.kind, **dataclasses.asdict(self)}


@dataclasses.dataclass(frozen=True)
class PagedAttnGeometry:
    """Paged-KV attention geometry: the kv stream is a gather over
    ``pages`` fixed-size pages of ``page_size`` positions, so a kv block
    that straddles a page boundary touches two non-contiguous source
    pages.  Threading this through ``resolve_blocks`` clamps ``block_k``
    to the page size (when a page is at least one lane tile) so every
    online-softmax step reads within one page, and keys the tuning cache
    on the page shape — the same (tq, tk, d) tunes separately for a paged
    serving tier and a contiguous one."""
    kind = "paged_attn"  # JSON tag (class attribute, not a field)
    page_size: int
    pages: int

    def asdict(self):
        return {"kind": self.kind, **dataclasses.asdict(self)}


def geometry_from_dict(d: dict | None):
    """Inverse of a geometry tuple's ``asdict`` (None passes through)."""
    if d is None:
        return None
    d = dict(d)
    cls = _GEOM_KIND_TO_CLS.get(d.pop("kind", None))
    if cls is None:
        raise ValueError(f"unknown geometry kind in {d!r}")
    return cls(**{k: int(v) for k, v in d.items()})


def choose_conv_blocks(
    q: int, c: int, k: int, dtype=jnp.float32, *, geometry=None
) -> ConvBlocks:
    """Static heuristic for conv2d: (q, c, k) = (out pixels/row, C, K)."""
    del geometry  # the heuristic stays static; candidates/proxy use it
    bq = min(round_up(q, 8), 128)
    bc = min(round_up(c, LANE), LANE)
    bk = min(round_up(k, LANE), LANE)
    return ConvBlocks(bq=bq, bc=bc, bk=bk)


def _page_clamp(block_k: int, geometry) -> int:
    """Largest lane-aligned block_k that stays within one KV page (no-op
    for sub-lane pages, where boundary crossings are unavoidable)."""
    if geometry is None or geometry.page_size < LANE:
        return block_k
    return min(block_k, geometry.page_size // LANE * LANE)


def choose_attention_blocks(
    tq: int, tk: int, d: int, dtype=jnp.float32, *, geometry=None
) -> AttnBlocks:
    """Static heuristic for flash attention: (tq, tk, d) = (query len,
    kv len, head dim).  With a ``PagedAttnGeometry``, block_k is clamped
    so no kv block straddles a page boundary."""
    del d
    return AttnBlocks(block_q=min(round_up(tq, 8), 128),
                      block_k=_page_clamp(min(round_up(tk, LANE), LANE),
                                          geometry))


def chunk_attention_blocks(tq: int, tk: int) -> AttnBlocks:
    """Flash attention for a prefill chunk at an offset into a cache: all
    the chunk's queries in one block (they share the live key extent),
    keys in blocks of up to 640 rows that divide the cache, so the blocks
    past the extent cost one skipped grid step each and nothing is
    padded (8,320 keys = 13 blocks of 640)."""
    return AttnBlocks(block_q=round_up(tq, 8),
                      block_k=divisor_block(tk, 5 * LANE))


def choose_attention_bwd_blocks(
    tq: int, tk: int, d: int, dtype=jnp.float32
) -> AttnBwdBlocks:
    """Static heuristic for the flash-attention backward kernels."""
    del d
    return AttnBwdBlocks(block_q=min(round_up(tq, 8), 128),
                         block_k=min(round_up(tk, LANE), LANE))


# --------------------------------------------------------------------------
# candidate grids for the measured autotuner
# --------------------------------------------------------------------------
#
# Each enumerator returns a deterministic, pruned list: only tiles that are
# hardware-legal, not wastefully larger than the (padded) problem, and whose
# working set fits the VMEM budget.  The heuristic pick is always a member,
# so autotuning can never do worse than the heuristic on the measured
# problem.

def _steps(lo: int, hi: int) -> list[int]:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def gemm_candidates(
    m: int, n: int, k: int, dtype=jnp.float32, *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> list[Blocks]:
    itemsize = jnp.dtype(dtype).itemsize
    sub = sublane(dtype)

    bms = [b for b in _steps(sub, 256) if b <= round_up(m, sub) or b == sub]
    bns = [b for b in _steps(LANE, 256)
           if b <= round_up(n, LANE) or b == LANE]
    bks = [b for b in _steps(LANE, 1024)
           if b <= round_up(k, LANE) or b == LANE]
    cands = [
        Blocks(bm, bn, bk)
        for bm in bms for bn in bns for bk in bks
        if gemm_working_set(bm, bn, bk, itemsize) <= vmem_budget
    ]
    heur = choose_blocks(m, n, k, dtype, vmem_budget=vmem_budget)
    if heur not in cands:
        cands.append(heur)
    return sorted(cands, key=lambda b: b.astuple())


def conv_candidates(
    q: int, c: int, k: int, dtype=jnp.float32, *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    geometry: ConvGeometry | None = None,
) -> list[ConvBlocks]:
    itemsize = jnp.dtype(dtype).itemsize
    stride = geometry.stride if geometry is not None else 1
    s_ = geometry.s if geometry is not None else 1

    def working_set(bq, bc, bk):
        # The kernel streams one full padded input row per grid step:
        # (qp-1)*stride + (s-1) + stride columns (kernel.py's need_w), so
        # the panel scales with the *strided problem row*, not just bq.
        qp = round_up(q, bq)
        wpad = (qp - 1) * stride + (s_ - 1) + stride
        panels = (wpad * bc + bc * bk) * itemsize * 2
        return panels + bq * bk * 4 + bq * bk * itemsize * 2

    bqs = [b for b in _steps(8, 256) if b <= round_up(q, 8) or b == 8]
    bcs = [b for b in _steps(LANE, 256)
           if b <= round_up(c, LANE) or b == LANE]
    bks = [b for b in _steps(LANE, 256)
           if b <= round_up(k, LANE) or b == LANE]
    cands = [
        ConvBlocks(bq, bc, bk)
        for bq in bqs for bc in bcs for bk in bks
        if working_set(bq, bc, bk) <= vmem_budget
    ]
    heur = choose_conv_blocks(q, c, k, dtype, geometry=geometry)
    if heur not in cands:
        cands.append(heur)
    return sorted(cands, key=lambda b: b.astuple())


def attention_candidates(
    tq: int, tk: int, d: int, dtype=jnp.float32, *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    geometry: PagedAttnGeometry | None = None,
) -> list[AttnBlocks]:
    itemsize = jnp.dtype(dtype).itemsize
    dp = round_up(d, LANE)

    def working_set(bq, bk):
        panels = (bq * dp + 2 * bk * dp) * itemsize * 2  # q + k + v
        acc = bq * dp * 4 + 2 * bq * LANE * 4            # acc + (m, l)
        return panels + acc + bq * bk * 4                # + scores block

    def in_page(bk):
        # paged KV: only kv blocks that evenly tile a page (boundary
        # crossings would gather from two non-contiguous pages)
        if geometry is None or geometry.page_size < LANE:
            return True
        return bk <= geometry.page_size and geometry.page_size % bk == 0

    bqs = [b for b in _steps(8, 256) if b <= round_up(tq, 8) or b == 8]
    bks = [b for b in _steps(LANE, 512)
           if (b <= round_up(tk, LANE) or b == LANE) and in_page(b)]
    cands = [
        AttnBlocks(bq, bk)
        for bq in bqs for bk in bks
        if working_set(bq, bk) <= vmem_budget
    ]
    heur = choose_attention_blocks(tq, tk, d, dtype, geometry=geometry)
    if heur not in cands:
        cands.append(heur)
    return sorted(cands, key=lambda b: b.astuple())


def attention_bwd_candidates(
    tq: int, tk: int, d: int, dtype=jnp.float32, *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> list[AttnBwdBlocks]:
    itemsize = jnp.dtype(dtype).itemsize
    dp = round_up(d, LANE)

    def working_set(bq, bk):
        # q + dy + y panels on the q side (y feeds the fused delta in the
        # dQ kernel), k + v on the kv side, all double buffered; lse +
        # delta stats rows; dq or dk+dv accumulators (the dk/dv kernel is
        # the larger resident set) plus the dQ kernel's delta accumulator;
        # scores + ds blocks.
        panels = (3 * bq * dp + 2 * bk * dp) * itemsize * 2
        stats = 2 * bq * LANE * 4 * 2
        accs = 2 * bk * dp * 4 + bq * dp * 4 + bq * LANE * 4
        return panels + stats + accs + 2 * bq * bk * 4

    bqs = [b for b in _steps(8, 256) if b <= round_up(tq, 8) or b == 8]
    bks = [b for b in _steps(LANE, 512)
           if b <= round_up(tk, LANE) or b == LANE]
    cands = [
        AttnBwdBlocks(bq, bk)
        for bq in bqs for bk in bks
        if working_set(bq, bk) <= vmem_budget
    ]
    heur = choose_attention_bwd_blocks(tq, tk, d, dtype)
    if heur not in cands:
        cands.append(heur)
    return sorted(cands, key=lambda b: b.astuple())


# --------------------------------------------------------------------------
# per-op schema: one resolution surface for every block tuple
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockSchema:
    """How an op maps onto the canonical (m, n, k) tuning triple."""
    kind: str                    # JSON tag
    cls: type
    dims: tuple[str, str, str]   # what (m, n, k) mean for this op
    heuristic: Callable          # (m, n, k, dtype) -> block tuple
    candidates: Callable         # (m, n, k, dtype) -> [block tuple]
    geometry_cls: type | None = None  # non-canonical-dims tuple, if any


_GEMM_SCHEMA = BlockSchema(
    kind="gemm", cls=Blocks, dims=("m", "n", "k"),
    heuristic=choose_blocks, candidates=gemm_candidates)

BLOCK_SCHEMAS: dict[str, BlockSchema] = {
    "matmul": _GEMM_SCHEMA,
    "brgemm": _GEMM_SCHEMA,
    "batched_matmul": _GEMM_SCHEMA,
    "conv2d": BlockSchema(
        kind="conv", cls=ConvBlocks, dims=("q", "c", "k"),
        heuristic=choose_conv_blocks, candidates=conv_candidates,
        geometry_cls=ConvGeometry),
    "flash_attention": BlockSchema(
        kind="attn", cls=AttnBlocks, dims=("tq", "tk", "d"),
        heuristic=choose_attention_blocks, candidates=attention_candidates,
        geometry_cls=PagedAttnGeometry),
    "flash_attention_bwd": BlockSchema(
        kind="attn_bwd", cls=AttnBwdBlocks, dims=("tq", "tk", "d"),
        heuristic=choose_attention_bwd_blocks,
        candidates=attention_bwd_candidates),
}


def schema_for(op: str) -> BlockSchema:
    schema = BLOCK_SCHEMAS.get(op)
    if schema is None:
        raise ValueError(
            f"no block schema for op {op!r}; known: "
            f"{', '.join(sorted(BLOCK_SCHEMAS))}")
    return schema


def default_blocks(op: str, m: int, n: int, k: int, dtype=jnp.float32, *,
                   geometry=None):
    """The static heuristic pick for ``op`` in its own block tuple type."""
    schema = schema_for(op)
    if geometry is not None and schema.geometry_cls is not None:
        return schema.heuristic(m, n, k, dtype, geometry=geometry)
    return schema.heuristic(m, n, k, dtype)


def candidate_blocks(op: str, m: int, n: int, k: int, dtype=jnp.float32, *,
                     geometry=None):
    """Deterministically ordered VMEM-feasible candidate tiles for ``op``."""
    schema = schema_for(op)
    if geometry is not None and schema.geometry_cls is not None:
        return schema.candidates(m, n, k, dtype, geometry=geometry)
    return schema.candidates(m, n, k, dtype)


_KIND_TO_CLS = {s.kind: s.cls for s in BLOCK_SCHEMAS.values()}
_GEOM_KIND_TO_CLS = {s.geometry_cls.kind: s.geometry_cls
                     for s in BLOCK_SCHEMAS.values()
                     if s.geometry_cls is not None}


def blocks_to_dict(blocks) -> dict:
    """JSON-serializable form of any op's block tuple."""
    for schema in BLOCK_SCHEMAS.values():
        if isinstance(blocks, schema.cls):
            return {"kind": schema.kind, **dataclasses.asdict(blocks)}
    raise TypeError(f"not a block tuple: {blocks!r}")


def blocks_from_dict(d: dict):
    """Inverse of :func:`blocks_to_dict`."""
    d = dict(d)
    cls = _KIND_TO_CLS.get(d.pop("kind", None))
    if cls is None:
        raise ValueError(f"unknown block kind in {d!r}")
    return cls(**{k: int(v) for k, v in d.items()})
