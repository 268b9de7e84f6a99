"""The fused Pallas flash-attention backward: gradient parity against XLA
autodiff, LSE residuals, backward block resolution, and the autotune
surface for ``flash_attention_bwd``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import autotune, blocking, dispatch
from repro.core.blocking import AttnBlocks, AttnBwdBlocks
from repro.kernels.flash_attention import (
    flash_attention,
    flash_attention_bwd,
)
from repro.kernels.flash_attention import ops as FO
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention import bwd as BW


def _randn(*shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed + len(shape))
    return jnp.asarray(rng.normal(size=shape), dtype)


@pytest.fixture(autouse=True)
def _fresh_cache():
    dispatch.clear_tuning_cache()
    yield
    dispatch.clear_tuning_cache()


def _qkv(tq=64, tk=64, hq=2, hkv=2, d=16, seed=0):
    return (_randn(1, hq, tq, d, seed=seed),
            _randn(1, hkv, tk, d, seed=seed + 1),
            _randn(1, hkv, tk, d, seed=seed + 2))


def _grads(backend, q, k, v, dy_w, **kw):
    """dQ/dK/dV of a weighted-sum loss (non-uniform cotangent)."""
    def loss(q_, k_, v_):
        y = flash_attention(q_, k_, v_, backend=backend, **kw)
        return (y.astype(jnp.float32) * dy_w).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# --------------------------------------------------------------------------
# gradient parity: Pallas-fused vs XLA autodiff
# --------------------------------------------------------------------------

# Several blocks with a partial last one (600 = 2 x 256 + 88), GQA so
# q-heads share each clamped kv block; the default tiles split 1100 into
# 2 x 552 queries and 2 x 640 keys.  Short queries against long keys
# clamp the dK/dV kernel's first live q block to the last one.
TILED = dict(tq=600, tk=600, hq=4, hkv=2)


@pytest.mark.parametrize("name,kw,shape", [
    ("causal", dict(causal=True), dict()),
    ("windowed", dict(causal=True, window=24), dict()),
    ("noncausal", dict(causal=False), dict()),
    ("noncausal_ragged", dict(causal=False), dict(tq=40, tk=72)),
    ("gqa", dict(causal=True), dict(hq=4, hkv=2)),
    ("causal_tiles", dict(causal=True, blocks=AttnBlocks(256, 512),
                          blocks_bwd=AttnBwdBlocks(256, 512)), TILED),
    ("windowed_tiles", dict(causal=True, window=200,
                            blocks=AttnBlocks(512, 256),
                            blocks_bwd=AttnBwdBlocks(512, 256)), TILED),
    ("causal_default_tiles", dict(causal=True),
     dict(tq=1100, tk=1100, hq=4, hkv=2)),
    ("causal_short_queries", dict(causal=True, blocks=AttnBlocks(16, 128),
                                  blocks_bwd=AttnBwdBlocks(16, 128)),
     dict(tq=40, tk=300)),
])
def test_grad_parity_f32(name, kw, shape):
    q, k, v = _qkv(**shape)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, backend="pallas", **kw)),
        np.asarray(flash_attention(q, k, v, backend="xla", **kw)),
        rtol=2e-3, atol=2e-3, err_msg=f"{name} y")
    dy_w = _randn(*q.shape, seed=9)
    got = _grads("pallas", q, k, v, dy_w, **kw)
    want = _grads("xla", q, k, v, dy_w, **kw)
    for grad_name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-3,
            err_msg=f"{name} d{grad_name}")


@pytest.mark.parametrize("kw", [dict(causal=True),
                                dict(causal=True, window=24),
                                dict(causal=False)])
def test_grad_parity_bf16_accum(kw):
    q, k, v = _qkv(seed=20)
    dy_w = _randn(*q.shape, seed=29)
    want = _grads("xla", q, k, v, dy_w, **kw)
    with repro.use(accum_dtype=jnp.bfloat16):
        got = _grads("pallas", q, k, v, dy_w, **kw)
    for grad_name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0.1, atol=0.1,
            err_msg=f"bf16-accum d{grad_name}")


def test_standalone_bwd_op_matches_recompute_reference():
    q, k, v = _qkv(seed=30)
    y, lse = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                    return_residuals=True)
    dy = _randn(*q.shape, seed=33)
    got = flash_attention_bwd(q, k, v, y, lse, dy, causal=True,
                              backend="pallas")
    want = flash_attention_bwd(q, k, v, y, lse, dy, causal=True,
                               backend="xla")
    for grad_name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-3,
            err_msg=f"d{grad_name}")


def test_grad_through_attention_layer():
    """The custom VJP composes inside a larger graph (projections around
    the flash kernel), the path a train step actually takes."""
    from repro.layers import attention as A
    cfg = A.AttnCfg(d_model=32, n_heads=2, n_kv_heads=2)
    params = A.init(jax.random.PRNGKey(0), cfg)
    x = _randn(1, 32, 32, seed=40)

    def loss(params, backend):
        y = A.apply(params, x, cfg, mode="train", backend=backend)
        return (y.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(loss)(params, "pallas")
    gx = jax.grad(loss)(params, "xla")
    for key in params:
        np.testing.assert_allclose(
            np.asarray(gp[key]), np.asarray(gx[key]), rtol=5e-3, atol=5e-3,
            err_msg=key)


# --------------------------------------------------------------------------
# residuals: the forward saves LSE stats, the backward recomputes nothing
# --------------------------------------------------------------------------

def test_forward_emits_lse_residuals():
    q, k, v = _qkv(seed=50)
    y, lse = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                    return_residuals=True)
    # y is unchanged by residual emission
    y_plain = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain),
                               rtol=1e-6, atol=1e-6)
    # lse matches the reference log-sum-exp of the masked scores
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    tq, tk = q.shape[-2], k.shape[-2]
    mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    assert lse.shape == q.shape[:3]
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_vjp_residuals_carry_lse_not_recompute():
    """The custom-VJP forward rule saves (q, k, v, y, lse) — the backward
    consumes the saved statistics instead of re-running the online
    softmax reduction."""
    q, k, v = _qkv(seed=60)
    cfg = FO._Cfg(causal=True, window=None, scale=None, blocks=None,
                  blocks_bwd=None, interpret=True, acc_dtype=jnp.float32)
    y, res = FO._flash_fwd(cfg, q, k, v)
    assert len(res) == 5
    rq, rk, rv, ry, rlse = res
    assert ry.shape == y.shape
    assert rlse.shape == q.shape[:3]  # per-row stats, not a (Tq, Tk) blob
    # and the stats are sufficient: backward from exactly these residuals
    dy = _randn(*q.shape, seed=66)
    dq, dk, dv = FO._flash_bwd(cfg, res, dy)
    assert dq.shape == q.shape and dk.shape == k.shape
    assert dv.shape == v.shape


# --------------------------------------------------------------------------
# backward block resolution and autotune
# --------------------------------------------------------------------------

def test_bwd_blocks_resolve_through_own_schema():
    blk = dispatch.resolve_blocks("flash_attention_bwd", 128, 128, 64,
                                  jnp.float32, backend="pallas")
    assert isinstance(blk, AttnBwdBlocks)
    d = blocking.blocks_to_dict(blk)
    assert d["kind"] == "attn_bwd"
    assert blocking.blocks_from_dict(d) == blk


@pytest.mark.parametrize("op,tq,tk,want", [
    # the train cell's 2048 tokens: the fastest tiles of the chip sweep
    ("flash_attention", 2048, 2048, (1024, 1024)),
    ("flash_attention_bwd", 2048, 2048, (1024, 1024)),
    # up to a tile: one block; longer: as few blocks as fit, as even as
    # the alignment allows
    ("flash_attention", 600, 600, (600, 640)),
    ("flash_attention", 1100, 1100, (560, 640)),
    ("flash_attention_bwd", 1100, 1100, (560, 640)),
    # up to 128: one block, as before
    ("flash_attention", 33, 33, (40, 128)),
    ("flash_attention_bwd", 33, 33, (40, 128)),
    ("flash_attention", 128, 128, (128, 128)),
    ("flash_attention_bwd", 128, 128, (128, 128)),
    ("flash_attention", 100, 64, (104, 128)),
    ("flash_attention_bwd", 64, 100, (64, 128)),
])
def test_attention_tiles_follow_the_shape(op, tq, tk, want):
    blk = blocking.default_blocks(op, tq, tk, 64, jnp.bfloat16)
    assert blk.astuple() == want
    itemsize, dp = 2, 128
    fits = (blocking.attention_working_set if op == "flash_attention"
            else blocking.attention_bwd_working_set)
    assert fits(*want, dp, itemsize) <= blocking.ATTN_VMEM_BUDGET


@pytest.mark.parametrize("page_size", [64, 128, 256])
def test_paged_geometry_keeps_its_clamp(page_size):
    """Paged decode reads block_k as the pages one DMA round fetches: a
    lane tile at most, within one page, whatever the kv length."""
    geom = blocking.PagedAttnGeometry(page_size=page_size, pages=24)
    blk = blocking.default_blocks("flash_attention", 3, 24 * page_size,
                                  64, jnp.bfloat16, geometry=geom)
    assert blk == AttnBlocks(8, 128)


@pytest.mark.parametrize("bq,bk,itemsize,fits", [
    (1024, 1024, 2, True),     # compiled for a v5e at the train shape
    (2048, 256, 2, True),
    (2048, 512, 2, False),     # refused by the compiler: VMEM exhausted
    (1024, 2048, 2, False),
    (1024, 1024, 4, False),
])
def test_bwd_working_set_matches_the_compiler(bq, bk, itemsize, fits):
    ws = blocking.attention_bwd_working_set(bq, bk, 128, itemsize)
    assert (ws <= blocking.ATTN_VMEM_BUDGET) == fits


def test_candidates_reach_the_heuristic_neighbourhood():
    for op, cls in (("flash_attention", AttnBlocks),
                    ("flash_attention_bwd", AttnBwdBlocks)):
        cands = blocking.candidate_blocks(op, 2048, 2048, 64, jnp.bfloat16)
        for tile in ((256, 512), (512, 512), (512, 1024), (1024, 512),
                     (1024, 1024)):
            assert cls(*tile) in cands, (op, tile)


def test_bwd_candidates_deterministic_and_include_heuristic():
    c1 = blocking.candidate_blocks("flash_attention_bwd", 128, 256, 64)
    c2 = blocking.candidate_blocks("flash_attention_bwd", 128, 256, 64)
    assert c1 == c2
    assert len(c1) == len(set(c1)) > 1
    assert blocking.default_blocks("flash_attention_bwd", 128, 256, 64) in c1


def test_explicit_blocks_bwd_honored_and_bypass_cache():
    q, k, v = _qkv(seed=70)
    dy_w = _randn(*q.shape, seed=77)
    want = _grads("xla", q, k, v, dy_w, causal=True)
    got = _grads("pallas", q, k, v, dy_w, causal=True,
                 blocks=AttnBlocks(32, 128),
                 blocks_bwd=AttnBwdBlocks(32, 128))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)
    assert not dispatch.tuning_cache_info()  # explicit geometry bypasses


def test_backward_tiles_tune_independently_of_forward():
    """Under a tuned context, grad through flash attention leaves separate
    cache entries for the forward and backward ops."""
    q, k, v = _qkv(tq=32, tk=32, seed=80)
    with repro.use(blocks_policy=lambda op, m, n, k_, dt, be:
                   autotune.autotune_blocks(op, m, n, k_, dt, be,
                                            max_candidates=2, repeats=1)):
        jax.grad(lambda q_: flash_attention(
            q_, k, v, backend="pallas").sum())(q)
    ops_tuned = {key[0] for key in dispatch.tuning_cache_info()}
    assert "flash_attention" in ops_tuned
    assert "flash_attention_bwd" in ops_tuned


def test_autotune_proxy_measures_fused_backward():
    before = autotune.STATS.measured
    blk = autotune.autotune_blocks("flash_attention_bwd", 32, 32, 16,
                                   jnp.float32, "pallas",
                                   max_candidates=2, repeats=1)
    assert isinstance(blk, AttnBwdBlocks)
    assert autotune.STATS.measured == before + 2


# --------------------------------------------------------------------------
# deprecated shim: a partial block_q/block_k resolves through the policy
# --------------------------------------------------------------------------

def test_partial_deprecated_kwarg_resolves_missing_dim_via_policy():
    q, k, v = _qkv(seed=90)
    seen = []

    def policy(op, m, n, k_, dtype, backend):
        seen.append(op)
        return blocking.default_blocks(op, m, n, k_, dtype)

    with repro.use(blocks_policy=policy):
        with pytest.warns(DeprecationWarning, match="block_q"):
            got = flash_attention(q, k, v, backend="pallas", block_q=32)
    assert "flash_attention" in seen  # resolved, not hard-coded to 128
    heur = blocking.default_blocks("flash_attention", q.shape[-2],
                                   k.shape[-2], q.shape[-1], q.dtype)
    want = flash_attention(q, k, v, backend="pallas",
                           blocks=AttnBlocks(32, heur.block_k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# conv autotune fidelity: geometry-true proxy and keyed cache
# --------------------------------------------------------------------------

def test_conv_geometry_keys_cache_separately():
    from repro.kernels.conv2d import conv2d
    x1 = _randn(1, 8, 8, 2, seed=100)
    w1 = _randn(1, 1, 2, 4, seed=101) * 0.3
    # Same canonical (q, c, k) triple, different stride/R/S geometry:
    # a 8x8 stride-1 1x1 conv and a 17x17 stride-2 3x3 conv both have
    # q=8 output pixels per row.
    x2 = _randn(1, 17, 17, 2, seed=102)
    w2 = _randn(3, 3, 2, 4, seed=103) * 0.3
    conv2d(x1, w1, stride=1, backend="pallas")
    conv2d(x2, w2, stride=2, padding=0, backend="pallas")
    conv_keys = [key for key in dispatch.tuning_cache_info()
                 if key[0] == "conv2d"]
    assert len(conv_keys) == 2  # distinct geometry -> distinct entries
    geoms = {key[7] for key in conv_keys}
    assert blocking.ConvGeometry(1, 1, 1) in geoms
    assert blocking.ConvGeometry(2, 3, 3) in geoms


def test_conv_autotune_proxy_uses_true_geometry():
    geom = blocking.ConvGeometry(stride=2, r=3, s=3)
    fn = autotune.proxy_runner(
        "conv2d", 8, 2, 4, jnp.float32,
        blocking.ConvBlocks(8, 128, 128), True, geometry=geom)
    out = jax.block_until_ready(fn())
    assert out.shape == (1, 1, 8, 4)  # q=8 true output pixels at stride 2

    before = autotune.STATS.measured
    blk = autotune.autotune_blocks("conv2d", 16, 2, 4, jnp.float32,
                                   "pallas", geometry=geom,
                                   max_candidates=2, repeats=1)
    assert isinstance(blk, blocking.ConvBlocks)
    assert autotune.STATS.measured == before + 2


def test_load_cache_skips_unknown_geometry_entries(tmp_path):
    """A cache file shared with a newer repo version may hold geometry
    kinds this version doesn't know; the load skips them instead of
    failing the first kernel call."""
    import json
    path = str(tmp_path / "cache.json")
    dispatch.resolve_blocks("conv2d", 28, 128, 64, jnp.float32,
                            backend="pallas",
                            geometry=blocking.ConvGeometry(1, 3, 3))
    assert dispatch.save_cache(path) == 1
    with open(path) as f:
        data = json.load(f)
    data["entries"].append({**data["entries"][0],
                            "geometry": {"kind": "hologram", "phase": 7}})
    with open(path, "w") as f:
        json.dump(data, f)
    dispatch.clear_tuning_cache()
    assert dispatch.load_cache(path) == 1  # alien entry skipped, not fatal


def test_conv_geometry_persists_through_cache_file(tmp_path):
    path = str(tmp_path / "cache.json")
    geom = blocking.ConvGeometry(stride=2, r=3, s=3)
    blk = dispatch.resolve_blocks("conv2d", 28, 128, 64, jnp.float32,
                                  backend="pallas", geometry=geom)
    assert dispatch.save_cache(path) == 1
    dispatch.clear_tuning_cache()
    assert dispatch.load_cache(path) == 1
    again = dispatch.resolve_blocks("conv2d", 28, 128, 64, jnp.float32,
                                    backend="pallas", geometry=geom)
    assert again == blk


# --------------------------------------------------------------------------
# fused delta precompute (rowsum(dY o Y) inside the dQ kernel's first pass)
# --------------------------------------------------------------------------

def test_delta_rowsum_standalone_matches_manual():
    q, k, v = _qkv(seed=70)
    y = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    dy = _randn(*q.shape, seed=77)
    got = BW.delta_rowsum_pallas(y, dy, interpret=True)
    want = (np.asarray(y, np.float32) * np.asarray(dy, np.float32)).sum(-1)
    assert got.shape == q.shape[:3] and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,kw,shape", [
    ("causal", dict(causal=True), dict()),
    ("windowed", dict(causal=True, window=24), dict()),
    ("noncausal_ragged", dict(causal=False), dict(tq=40, tk=72)),
    ("gqa", dict(causal=True), dict(hq=4, hkv=2)),
])
def test_fused_delta_matches_standalone_and_leaves_grads_unchanged(
        name, kw, shape):
    q, k, v = _qkv(seed=80, **shape)
    y, lse = flash_attention_pallas(q, k, v, interpret=True,
                                    return_residuals=True, **kw)
    dy = _randn(*q.shape, seed=88)
    dq, dk, dv, delta = BW.flash_attention_bwd_pallas(
        q, k, v, y, lse, dy, interpret=True, return_delta=True, **kw)
    # the fused rowsum is the standalone kernel's answer, bit for bit
    want = BW.delta_rowsum_pallas(y, dy, interpret=True)
    np.testing.assert_array_equal(np.asarray(delta), np.asarray(want),
                                  err_msg=name)
    # and emitting it does not perturb the gradients
    dq0, dk0, dv0 = BW.flash_attention_bwd_pallas(
        q, k, v, y, lse, dy, interpret=True, **kw)
    for g_name, a, b in (("dq", dq, dq0), ("dk", dk, dk0), ("dv", dv, dv0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} {g_name}")
