"""Pallas BRGEMM kernel vs pure-jnp oracle: shape/dtype/epilogue sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.brgemm import batched_matmul, brgemm, matmul
from repro.kernels.brgemm import ref as R
from repro.core.blocking import Blocks, choose_blocks

RNG = np.random.default_rng(1234)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=3e-5, atol=3e-5)


MATMUL_SHAPES = [
    (1, 1, 1),
    (8, 128, 128),
    (7, 33, 17),          # ragged, forces padding on every dim
    (128, 256, 128),      # exact multiples
    (200, 100, 300),
    (256, 512, 64),
    (130, 129, 131),      # just-over-block
]


@pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    x, w = randn(m, k, dtype=dtype), randn(k, n, dtype=dtype)
    got = matmul(x, w, backend="pallas")
    want = matmul(x, w, backend="xla")
    assert got.shape == (m, n) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize(
    "act", ["none", "relu", "gelu", "silu", "sigmoid", "tanh"])
def test_matmul_fused_epilogues(act):
    x, w, b = randn(48, 96), randn(96, 64), randn(64)
    got = matmul(x, w, b, activation=act, backend="pallas")
    want = matmul(x, w, b, activation=act, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_matmul_alpha_beta_c0():
    x, w, c0 = randn(40, 60), randn(60, 50), randn(40, 50)
    got = matmul(x, w, c0=c0, alpha=0.25, beta=-1.5, backend="pallas")
    want = matmul(x, w, c0=c0, alpha=0.25, beta=-1.5, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("nb", [1, 3, 9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_brgemm_stacked(nb, dtype):
    a, b = randn(nb, 33, 65, dtype=dtype), randn(nb, 65, 47, dtype=dtype)
    got = brgemm(a, b, backend="pallas")
    want = brgemm(a, b, backend="xla")
    assert got.shape == (33, 47)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype))


def test_brgemm_matches_loop_of_gemms():
    """Semantics check straight from the paper's definition."""
    a, b = randn(6, 16, 24), randn(6, 24, 32)
    got = brgemm(a, b, backend="pallas")
    acc = np.zeros((16, 32), np.float32)
    for i in range(6):
        acc += np.asarray(a[i]) @ np.asarray(b[i])
    np.testing.assert_allclose(np.asarray(got), acc, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("bcast", ["none", "a", "b"])
def test_batched_matmul_broadcast(bcast):
    a = randn(24, 40) if bcast == "a" else randn(4, 24, 40)
    b = randn(40, 56) if bcast == "b" else randn(4, 40, 56)
    got = batched_matmul(a, b, backend="pallas")
    want = batched_matmul(a, b, backend="xla")
    assert got.shape == (4, 24, 56)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("act", ["none", "relu", "gelu", "sigmoid", "tanh"])
def test_matmul_grads_match_ref_autodiff(act):
    x, w, b = randn(24, 48), randn(48, 32), randn(32)

    def lp(x, w, b):
        return (matmul(x, w, b, activation=act, backend="pallas") ** 2).sum()

    def lr(x, w, b):
        return (matmul(x, w, b, activation=act, backend="xla") ** 2).sum()

    gp = jax.grad(lp, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, w, b)
    for p, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_brgemm_grads_match_ref_autodiff():
    a, b = randn(3, 16, 24), randn(3, 24, 32)

    def lp(a, b):
        return (brgemm(a, b, activation="silu", backend="pallas") ** 2).sum()

    def lr(a, b):
        return (brgemm(a, b, activation="silu", backend="xla") ** 2).sum()

    gp = jax.grad(lp, argnums=(0, 1))(a, b)
    gr = jax.grad(lr, argnums=(0, 1))(a, b)
    for p, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_explicit_blocks_respected():
    x, w = randn(64, 256), randn(256, 128)
    got = matmul(x, w, backend="pallas", blocks=Blocks(32, 128, 128))
    want = matmul(x, w, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_choose_blocks_vmem_budget():
    blk = choose_blocks(4096, 4096, 65536, jnp.bfloat16)
    bm, bn, bk = blk.astuple()
    itemsize = 2
    ws = (bm * bk + bk * bn) * itemsize * 2 + bm * bn * 4 + bm * bn * itemsize * 2
    assert ws <= 8 * 1024 * 1024
    assert bn % 128 == 0 and bk % 128 == 0 and bm % 16 == 0


@pytest.mark.parametrize("k,n,blocks", [
    (256, 384, Blocks(16, 128, 128)),    # blocks tile the weight: in place
    (200, 300, Blocks(16, 128, 128)),    # they would pad it: layer taken
])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_layer_weight_matches_its_slice(k, n, blocks, backend):
    """A LayerWeight multiplies as the layer it names, on both backends."""
    from repro.kernels.brgemm import LayerWeight

    x = randn(24, k)
    stack = randn(3, k, n)
    layer = jnp.int32(2)
    got = matmul(x, LayerWeight(stack, layer), activation="silu",
                 backend=backend, blocks=blocks)
    want = matmul(x, stack[2], activation="silu", backend=backend,
                  blocks=blocks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# the code cell's GEMMs (deepseek-coder-33b widths): a 512-row prefill
# chunk, a 16-slot decode step, the head; and smollm-135m's, which the
# streaming regime leaves on the 128-wide tiles
STREAMING = [(512, 19200, 7168), (512, 7168, 19200), (512, 7168, 7168),
             (512, 1024, 7168), (369, 19200, 7168), (16, 19200, 7168),
             (16, 7168, 19200), (1, 32256, 7168)]
SMOLLM = [(512, 1536, 576), (32, 49152, 576), (512, 576, 1536),
          (32768, 576, 1536), (576, 49152, 32768)]


@pytest.mark.parametrize("m,n,k", STREAMING + SMOLLM)
def test_streaming_blocks_tile_wide_weights(m, n, k):
    from repro.core.blocking import gemm_working_set, streams_weight

    blk = choose_blocks(m, n, k, jnp.bfloat16)
    bm, bn, bk = blk.astuple()
    assert gemm_working_set(bm, bn, bk, 2) <= 8 * 1024 * 1024
    if (m, n, k) in STREAMING:
        assert streams_weight(m, n, k)
        # all rows in one block, the weight read once and never padded
        assert bm >= m and n % bn == 0 and k % bk == 0
        assert bn >= 768 and bk >= 512
    else:
        assert not streams_weight(m, n, k)
        assert (bn, bk) == (128, 512) and bm <= 128
