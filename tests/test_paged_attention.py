"""The paged decode attention kernel (interpret mode) against the float32
reference attention over each slot's gathered view.

Page tables are shuffled, non-contiguous and padded with the sentinel;
one batch holds lengths 1, 127, 128 and 129 (a page boundary on either
side), a full table and a free slot.  Tolerance: bf16 pages, bf16
probabilities into ``p @ v`` and a bf16 output give errors of a few bf16
ulps at unit scale, so |kernel - reference| <= 2e-2 elementwise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocking import AttnBlocks
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.paged_attention import paged_attention

PAGE = 128
PAGES = 3                       # per slot: max_len 384
LENGTHS = [1, 127, 128, 129, PAGE * PAGES, 0]   # 0: a free slot
LAYERS = 2
ATOL = 2e-2


def _case(hkv, group, d, seed=0):
    rng = np.random.default_rng(seed)
    n_slots = len(LENGTHS)
    n_pages = n_slots * PAGES + 2

    def randn(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    # layer 0 is NaN: reading any of it would show in every output
    k_pages = randn(LAYERS, n_pages, hkv, d, PAGE).at[0].set(jnp.nan)
    v_pages = randn(LAYERS, n_pages, hkv, d, PAGE).at[0].set(jnp.nan)
    q, k_new, v_new = (randn(n_slots, hkv * group, d),
                       randn(n_slots, hkv, d), randn(n_slots, hkv, d))
    perm = rng.permutation(n_pages)
    tables = np.full((n_slots, PAGES), n_pages, np.int32)   # sentinel
    used = 0
    for s, n in enumerate(LENGTHS):
        k = -(-n // PAGE)
        tables[s, :k] = perm[used:used + k]
        used += k
    return q, k_pages, v_pages, tables, k_new, v_new


def _reference(q, k_pages, v_pages, tables, k_new, v_new, layer):
    """mha_ref in float32 over each slot's gathered view plus its row."""
    f32 = lambda x: np.asarray(x, np.float32)   # noqa: E731
    hkv, d = k_pages.shape[2:4]
    out = []
    for s, n in enumerate(LENGTHS):
        ids = np.clip(tables[s], 0, k_pages.shape[1] - 1)
        k = f32(k_pages[layer])[ids].transpose(1, 0, 3, 2).reshape(hkv, -1, d)
        v = f32(v_pages[layer])[ids].transpose(1, 0, 3, 2).reshape(hkv, -1, d)
        k = np.concatenate([k[:, :n], f32(k_new[s])[:, None]], axis=1)
        v = np.concatenate([v[:, :n], f32(v_new[s])[:, None]], axis=1)
        o = mha_ref(jnp.asarray(f32(q[s]))[None, :, None],
                    jnp.asarray(k)[None], jnp.asarray(v)[None],
                    causal=False)
        out.append(np.asarray(o)[0, :, 0])
    return np.stack(out)


@pytest.mark.parametrize("hkv,group,d,blocks", [
    (3, 3, 64, None),                  # smollm-135m: 9 heads on 3
    (8, 7, 128, None),                 # deepseek-coder-33b: 56 on 8
    (3, 3, 64, AttnBlocks(8, 256)),    # two pages per block
], ids=["smollm", "deepseek-coder", "two-pages-a-block"])
def test_paged_attention_matches_reference(hkv, group, d, blocks):
    q, k_pages, v_pages, tables, k_new, v_new = _case(hkv, group, d)
    got = paged_attention(q, k_pages, v_pages, jnp.asarray(tables),
                          jnp.asarray(LENGTHS, jnp.int32), k_new, v_new,
                          layer=1, backend="pallas", blocks=blocks)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _reference(q, k_pages, v_pages, tables, k_new, v_new, 1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=ATOL, rtol=0)
    # the XLA backend (the gathering oracle) agrees too
    ref = paged_attention(q, k_pages, v_pages, jnp.asarray(tables),
                          jnp.asarray(LENGTHS, jnp.int32), k_new, v_new,
                          layer=1, backend="xla")
    np.testing.assert_allclose(np.asarray(ref, np.float32), want,
                               atol=ATOL, rtol=0)
