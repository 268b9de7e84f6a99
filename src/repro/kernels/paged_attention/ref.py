"""Pure-jnp paged decode attention (the oracle, and the XLA backend).

Gathers each slot's page list into a contiguous view, appends the
token's own row and runs a masked softmax over it: the same math as the
Pallas kernel, without its in-place page reads.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(q, k_pages, v_pages, page_tables, lengths, k_new,
                        v_new, layer, *, scale=None):
    """Arguments as ``kernel.paged_attention_pallas``; returns (S, Hq, d).

    Sentinel page ids clip to the last page, whose keys the length mask
    hides."""
    n_slots, hq, d = q.shape
    n_pages, hkv = k_pages.shape[1:3]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    ids = jnp.clip(page_tables, 0, n_pages - 1)

    def view(pages, new):
        x = pages[layer][ids]                    # (S, P, Hkv, d, page)
        x = x.transpose(0, 2, 1, 4, 3).reshape(n_slots, hkv, -1, d)
        return jnp.concatenate([x, new[:, :, None].astype(x.dtype)], 2)

    k, v = view(k_pages, k_new), view(v_pages, v_new)
    qg = q.reshape(n_slots, hkv, group, d)
    s = jnp.einsum("shgd,shtd->shgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    t = jnp.arange(k.shape[2])
    live = (t[None, :] < lengths[:, None]) | (t[None, :] == k.shape[2] - 1)
    s = jnp.where(live[:, None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("shgt,shtd->shgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(n_slots, hq, d).astype(q.dtype)
