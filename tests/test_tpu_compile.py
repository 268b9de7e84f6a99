"""The main path's Pallas kernels compile for a described TPU v5e.

Nothing runs: each test lowers and compiles at smollm-135m widths for a
chip that is described (``jax.experimental.topologies``), not attached,
so the TPU compiler refuses here what it would refuse on the chip
(unaligned slices, VMEM overuse, kernels it cannot partition).  The
topology is described inside a module fixture, never at import, and all
of these tests stay in this one file (see the fixture).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import repro
from repro.kernels.brgemm import matmul
from repro.kernels.flash_attention import flash_attention_bwd
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.launch.mesh import make_mesh

HQ, HKV, D, T = 9, 3, 64, 2048     # smollm-135m heads at a 2048 prompt


@pytest.fixture(scope="module")
def topo():
    # Only one process may hold the TPU library; describing the topology
    # loads it, so this happens in the fixture of the one worker given
    # this file, never while a module is imported.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    # off the chip, dispatch's hardware default is xla and interpret mode
    # is on: pin the compiled Pallas path explicitly
    with repro.use(backend="pallas", interpret=False):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n", [(8, 576, 1536), (2048, 576, 49152)],
                         ids=["decode_up", "prefill_head"])
def test_matmul_compiles(one_chip, m, k, n):
    text = _compiled_text(matmul, _sds((m, k), one_chip),
                          _sds((k, n), one_chip))
    assert "tpu_custom_call" in text


def test_flash_forward_with_residuals_compiles(one_chip):
    q = _sds((1, HQ, T, D), one_chip)
    kv = _sds((1, HKV, T, D), one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, return_residuals=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_flash_backward_compiles(one_chip):
    q = _sds((1, HQ, T, D), one_chip)
    kv = _sds((1, HKV, T, D), one_chip)
    lse = _sds((1, HQ, T), one_chip, jnp.float32)
    text = _compiled_text(
        lambda q, k, v, y, lse, dy: flash_attention_bwd(
            q, k, v, y, lse, dy, causal=True), q, kv, kv, q, lse, q)
    # delta+dQ and dK/dV are separate kernels
    assert text.count("tpu_custom_call") >= 2


def test_sharded_matmul_compiles_on_2x2(topo):
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    x = _sds((2048, 576), NamedSharding(mesh, P("data", None)))
    # FSDP x TP weight: the contraction dim is gathered before the kernel
    w = _sds((576, 1536), NamedSharding(mesh, P("data", "model")))
    with repro.use(mesh=mesh):
        text = _compiled_text(matmul, x, w)
    assert "tpu_custom_call" in text
    assert "all-gather" in text
