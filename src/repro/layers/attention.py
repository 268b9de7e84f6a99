"""Attention layers: GQA/MQA (+ sliding window) and MLA (DeepSeek-V3).

All projections route through the batch-reduce GEMM building block; the
attention inner loop uses the flash kernel (itself a batch-reduce GEMM with
online-softmax epilogue) on the Pallas backend, or the jnp oracle on XLA.

Five modes:
  * train         — full causal sequence, no cache,
  * prefill       — train-compute + returns the KV cache,
  * prefill_chunk — one chunk of a longer prompt: queries live at absolute
    positions ``pos .. pos+T-1``, attend causally to everything already in
    the cache (``q_offset``), and append their KV at ``pos``.  Chaining
    chunks reproduces one-shot prefill exactly (the causal mask zeroes the
    not-yet-written tail bit-for-bit: ``exp(-1e30 - max) == 0``).
  * decode        — one token against a (padded) cache; GQA caches (k, v),
    MLA caches the *compressed* (c_kv, k_rope) and uses the
    absorbed-matmul formulation (the memory win that motivates MLA).
  * decode_paged  — GQA only: one token per slot against a paged pool
    read in place (``PagedKV``); returns the token's K/V row instead of
    an updated cache, for the caller to write.

Named scopes (under the caller's ``attention``): ``core`` holds the
score-softmax-value work on every path, ``kv_write`` the cache writes.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import brgemm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.paged_attention import paged_attention
from repro.layers import norms
from repro.layers.rope import apply_rope


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int | None = None
    rope_theta: float = 10000.0
    window: int | None = None          # sliding-window size (None = full)
    # --- MLA (used when mla=True) ---
    mla: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    xla_impl: str = "naive"       # XLA-path attention: naive | chunked
    unroll: bool = False

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


def _lin(key, cin, cout, dtype):
    return (jax.random.normal(key, (cin, cout), jnp.float32)
            * (1.0 / cin) ** 0.5).astype(dtype)


def init(key, cfg: AttnCfg, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    if not cfg.mla:
        dh = cfg.dh
        return {
            "wq": _lin(ks[0], cfg.d_model, cfg.n_heads * dh, dtype),
            "wk": _lin(ks[1], cfg.d_model, cfg.n_kv_heads * dh, dtype),
            "wv": _lin(ks[2], cfg.d_model, cfg.n_kv_heads * dh, dtype),
            "wo": _lin(ks[3], cfg.n_heads * dh, cfg.d_model, dtype),
        }
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq_a": _lin(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": norms.rmsnorm_init(cfg.q_lora_rank, dtype),
        "wq_b": _lin(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_dim, dtype),
        "wkv_a": _lin(ks[2], cfg.d_model,
                      cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_norm": norms.rmsnorm_init(cfg.kv_lora_rank, dtype),
        "wkv_b": _lin(ks[3], cfg.kv_lora_rank,
                      cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim),
                      dtype),
        "wo": _lin(ks[4], cfg.n_heads * cfg.v_head_dim, cfg.d_model, dtype),
    }


def init_cache(cfg: AttnCfg, batch: int, max_len: int, dtype=jnp.float32):
    if cfg.mla:
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        }
    dh = cfg.dh
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, dh), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, dh), dtype),
    }


@jax.named_scope("kv_write")
def _write_cache(cache, new: dict, start):
    """``cache`` with each ``new[name]`` written in at index ``start``."""
    cache = dict(cache)
    for name, x in new.items():
        cache[name] = jax.lax.dynamic_update_slice(
            cache[name], x.astype(cache[name].dtype), start)
    return cache


def _split_heads(x, n_heads):
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, -1).transpose(0, 2, 1, 3)  # (B,H,T,dh)


def _merge_heads(x):
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def _gqa_qkv(params, x, cfg, positions, backend):
    q = _split_heads(brgemm.matmul(x, params["wq"], backend=backend),
                     cfg.n_heads)
    k = _split_heads(brgemm.matmul(x, params["wk"], backend=backend),
                     cfg.n_kv_heads)
    v = _split_heads(brgemm.matmul(x, params["wv"], backend=backend),
                     cfg.n_kv_heads)
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def _gqa_train(params, x, cfg, backend):
    positions = jnp.arange(x.shape[1])
    q, k, v = _gqa_qkv(params, x, cfg, positions, backend)
    with jax.named_scope("core"):
        o = flash_attention(q, k, v, causal=True, window=cfg.window,
                            backend=backend, xla_impl=cfg.xla_impl,
                            unroll=cfg.unroll)
    return brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)


def _gqa_prefill(params, x, cfg, cache, backend):
    positions = jnp.arange(x.shape[1])
    q, k, v = _gqa_qkv(params, x, cfg, positions, backend)
    with jax.named_scope("core"):
        o = flash_attention(q, k, v, causal=True, window=cfg.window,
                            backend=backend, xla_impl=cfg.xla_impl,
                            unroll=cfg.unroll)
    cache = _write_cache(cache, {"k": k, "v": v}, (0, 0, 0, 0))
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, cache


def _gqa_prefill_chunk(params, x, cfg, cache, pos, backend):
    """One prompt chunk at absolute positions ``pos .. pos+T-1``.

    The chunk's queries see the whole cache causally (earlier chunks plus
    this one); its K/V land at ``pos``.  Runs on the masked reference
    attention — the fused kernel has no ``q_offset`` — which is exact, not
    approximate, so chunked == one-shot prefill holds bit-for-bit on the
    reference path.
    """
    positions = pos + jnp.arange(x.shape[1])
    q, k, v = _gqa_qkv(params, x, cfg, positions, backend)
    cache = _write_cache(cache, {"k": k, "v": v}, (0, 0, pos, 0))
    with jax.named_scope("core"):
        o = mha_ref(q, cache["k"], cache["v"], causal=True,
                    window=cfg.window, q_offset=pos,
                    kv_len=pos + x.shape[1])
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, cache


class PagedKV(NamedTuple):
    """One layer's read-only view of a key-major paged pool: the
    layer-stacked ``k``/``v`` leaves (L, n_pages, Hkv, d, page_size), the
    ``layer`` to read, each slot's ``page_tables`` row and ``lengths``
    (keys held before this token, which is also its position)."""
    k: jax.Array
    v: jax.Array
    layer: jax.Array
    page_tables: jax.Array
    lengths: jax.Array


def _gqa_decode_paged(params, x, cfg, pages: PagedKV, backend):
    """x: (S, 1, D), one token per slot at position ``pages.lengths``.
    Returns (y, {"k", "v"}: the token's (S, Hkv, d) rows)."""
    q, k, v = _gqa_qkv(params, x, cfg, pages.lengths[:, None], backend)
    k, v = k[:, :, 0], v[:, :, 0]
    with jax.named_scope("core"):
        o = paged_attention(q[:, :, 0], pages.k, pages.v, pages.page_tables,
                            pages.lengths, k, v, layer=pages.layer,
                            backend=backend)
    y = brgemm.matmul(_merge_heads(o[:, :, None]), params["wo"],
                      backend=backend)
    return y, {"k": k, "v": v}


def _gqa_decode(params, x, cfg, cache, pos, backend):
    positions = jnp.full((x.shape[1],), pos)
    q, k, v = _gqa_qkv(params, x, cfg, positions, backend)
    cache = _write_cache(cache, {"k": k, "v": v}, (0, 0, pos, 0))
    with jax.named_scope("core"):
        o = mha_ref(q, cache["k"], cache["v"], causal=False,
                    window=cfg.window, q_offset=pos, kv_len=pos + 1)
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, cache


# --------------------------------------------------------------------------
# MLA
# --------------------------------------------------------------------------

def _mla_q(params, x, cfg, positions, backend):
    b, t, _ = x.shape
    cq = norms.rmsnorm(params["q_norm"],
                       brgemm.matmul(x, params["wq_a"], backend=backend))
    q = brgemm.matmul(cq, params["wq_b"], backend=backend)
    q = q.reshape(b, t, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q = q.transpose(0, 2, 1, 3)
    q_nope, q_rope = (q[..., :cfg.qk_nope_dim],
                      q[..., cfg.qk_nope_dim:])
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)
    return q_nope, q_rope


def _mla_compressed_kv(params, x, cfg, positions, backend):
    ckv_full = brgemm.matmul(x, params["wkv_a"], backend=backend)
    c_kv = norms.rmsnorm(params["kv_norm"],
                         ckv_full[..., :cfg.kv_lora_rank])
    k_rope = ckv_full[..., cfg.kv_lora_rank:]          # (B, T, rope)
    k_rope = apply_rope(k_rope[:, None], positions,
                        theta=cfg.rope_theta)[:, 0]
    return c_kv, k_rope


def _mla_full(params, x, cfg, backend):
    """Train/prefill: expand the compressed KV to per-head K/V."""
    b, t, _ = x.shape
    positions = jnp.arange(t)
    q_nope, q_rope = _mla_q(params, x, cfg, positions, backend)
    c_kv, k_rope = _mla_compressed_kv(params, x, cfg, positions, backend)

    kv = brgemm.matmul(c_kv, params["wkv_b"], backend=backend)
    kv = kv.reshape(b, t, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim:]

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None],
                                  (b, cfg.n_heads, t, cfg.qk_rope_dim))],
        axis=-1)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    with jax.named_scope("core"):
        o = flash_attention(q, k, v, causal=True, scale=scale,
                            backend=backend, xla_impl=cfg.xla_impl,
                            unroll=cfg.unroll)
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, c_kv, k_rope


def _mla_decode(params, x, cfg, cache, pos, backend):
    """Absorbed-matmul decode against the compressed cache."""
    b, t, _ = x.shape
    positions = jnp.full((t,), pos)
    q_nope, q_rope = _mla_q(params, x, cfg, positions, backend)
    c_kv_new, k_rope_new = _mla_compressed_kv(params, x, cfg, positions,
                                              backend)
    cache = _write_cache(cache, {"c_kv": c_kv_new, "k_rope": k_rope_new},
                         (0, pos, 0))

    wkv_b = params["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    w_uk = wkv_b[..., :cfg.qk_nope_dim]    # (L, H, nope)
    w_uv = wkv_b[..., cfg.qk_nope_dim:]    # (L, H, v)

    with jax.named_scope("core"):
        q_eff = jnp.einsum("bhqn,lhn->bhql", q_nope, w_uk)
        s = (jnp.einsum("bhql,bsl->bhqs", q_eff, cache["c_kv"],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhqr,bsr->bhqs", q_rope, cache["k_rope"],
                          preferred_element_type=jnp.float32))
        s = s * (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
        kv_len = pos + 1
        mask = jnp.arange(cache["c_kv"].shape[1])[None, None, None] < kv_len
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_c = jnp.einsum("bhqs,bsl->bhql", p, cache["c_kv"])
        o = jnp.einsum("bhql,lhv->bhqv", o_c, w_uv)
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, cache


def _mla_prefill_chunk(params, x, cfg, cache, pos, backend):
    """One prompt chunk through the absorbed-matmul path.

    Same cache layout and score math as ``_mla_decode``, generalized to
    ``Tq > 1`` queries at absolute positions ``pos .. pos+T-1`` with a
    causal mask against the compressed cache (earlier chunks + this one).
    """
    b, t, _ = x.shape
    positions = pos + jnp.arange(t)
    q_nope, q_rope = _mla_q(params, x, cfg, positions, backend)
    c_kv_new, k_rope_new = _mla_compressed_kv(params, x, cfg, positions,
                                              backend)
    cache = _write_cache(cache, {"c_kv": c_kv_new, "k_rope": k_rope_new},
                         (0, pos, 0))

    wkv_b = params["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    w_uk = wkv_b[..., :cfg.qk_nope_dim]
    w_uv = wkv_b[..., cfg.qk_nope_dim:]

    with jax.named_scope("core"):
        q_eff = jnp.einsum("bhqn,lhn->bhql", q_nope, w_uk)
        s = (jnp.einsum("bhql,bsl->bhqs", q_eff, cache["c_kv"],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhqr,bsr->bhqs", q_rope, cache["k_rope"],
                          preferred_element_type=jnp.float32))
        s = s * (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
        q_pos = pos + jnp.arange(t)[:, None]                  # (Tq, 1)
        s_pos = jnp.arange(cache["c_kv"].shape[1])[None, :]   # (1, S)
        mask = s_pos <= q_pos                                 # causal
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_c = jnp.einsum("bhqs,bsl->bhql", p, cache["c_kv"])
        o = jnp.einsum("bhql,lhv->bhqv", o_c, w_uv)
    y = brgemm.matmul(_merge_heads(o), params["wo"], backend=backend)
    return y, cache


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def apply(params, x, cfg: AttnCfg, *, mode: str = "train", cache=None,
          pos=0, backend: str | None = None):
    """x: (B, T, D). Returns y for train, (y, cache) for prefill/decode,
    (y, new K/V rows) for decode_paged."""
    if cfg.mla:
        if mode == "train":
            y, _, _ = _mla_full(params, x, cfg, backend)
            return y
        if mode == "prefill":
            y, c_kv, k_rope = _mla_full(params, x, cfg, backend)
            return y, _write_cache(cache, {"c_kv": c_kv, "k_rope": k_rope},
                                   (0, 0, 0))
        if mode == "prefill_chunk":
            return _mla_prefill_chunk(params, x, cfg, cache, pos, backend)
        if mode == "decode":
            return _mla_decode(params, x, cfg, cache, pos, backend)
        raise ValueError(mode)
    if mode == "train":
        return _gqa_train(params, x, cfg, backend)
    if mode == "prefill":
        return _gqa_prefill(params, x, cfg, cache, backend)
    if mode == "prefill_chunk":
        return _gqa_prefill_chunk(params, x, cfg, cache, pos, backend)
    if mode == "decode":
        return _gqa_decode(params, x, cfg, cache, pos, backend)
    if mode == "decode_paged":
        return _gqa_decode_paged(params, x, cfg, cache, backend)
    raise ValueError(mode)
