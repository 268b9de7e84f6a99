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
from repro.kernels.paged_attention import paged_attention
from repro.launch.mesh import make_mesh

HQ, HKV, D, T = 9, 3, 64, 2048     # smollm-135m heads at a 2048 prompt
# the chat cell's paged pool: 32 slots of 24 pages of 128
SLOTS, PAGES, PAGE = 32, 24, 128


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


def test_train_cell_attention_compiles_with_default_tiles(one_chip):
    """The train cell's attention (batch 16, 2048 tokens) with the tiles
    the heuristic gives it: the forward with residuals and the fused
    backward, so a default tile too large for VMEM is refused here."""
    q = _sds((16, HQ, T, D), one_chip)
    kv = _sds((16, HKV, T, D), one_chip)
    lse = _sds((16, HQ, T), one_chip, jnp.float32)
    fwd = _compiled_text(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, return_residuals=True), q, kv, kv)
    bwd = _compiled_text(
        lambda q, k, v, y, lse, dy: flash_attention_bwd(
            q, k, v, y, lse, dy, causal=True), q, kv, kv, q, lse, q)
    assert "tpu_custom_call" in fwd
    assert bwd.count("tpu_custom_call") >= 2


def test_sharded_matmul_compiles_on_2x2(topo):
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    x = _sds((2048, 576), NamedSharding(mesh, P("data", None)))
    # FSDP x TP weight: the contraction dim is gathered before the kernel
    w = _sds((576, 1536), NamedSharding(mesh, P("data", "model")))
    with repro.use(mesh=mesh):
        text = _compiled_text(matmul, x, w)
    assert "tpu_custom_call" in text
    assert "all-gather" in text


@pytest.mark.parametrize("slots,pages,hq,hkv,d,layers", [
    (SLOTS, PAGES, HQ, HKV, D, 30),      # smollm-135m chat
    (8, 65, 56, 8, 128, 6),              # deepseek-coder-33b, 8320 long
], ids=["smollm", "deepseek-coder"])
def test_paged_attention_compiles(one_chip, slots, pages, hq, hkv, d,
                                  layers):
    pool = _sds((layers, slots * pages, hkv, d, PAGE), one_chip)
    text = _compiled_text(
        lambda q, k, v, pt, n, kn, vn, layer: paged_attention(
            q, k, v, pt, n, kn, vn, layer=layer),
        _sds((slots, hq, d), one_chip), pool, pool,
        _sds((slots, pages), one_chip, jnp.int32),
        _sds((slots,), one_chip, jnp.int32),
        _sds((slots, hkv, d), one_chip), _sds((slots, hkv, d), one_chip),
        _sds((), one_chip, jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_donates_the_pool(one_chip):
    """Two smollm-135m layers on the chat cell's pool: the engine's
    decode and the pool's insert alias the pool to their output and hold
    less than the pool in temporaries (no copy of it)."""
    import dataclasses

    from repro import configs
    from repro.models import api
    from repro.serve import ContinuousEngine, PoolConfig

    cfg = dataclasses.replace(configs.get("smollm-135m"), n_layers=2)
    params = jax.tree.map(
        lambda x: _sds(x.shape, one_chip, x.dtype),
        api.params_specs(None, cfg))
    eng = ContinuousEngine(
        cfg, params, PoolConfig(n_slots=SLOTS, max_len=PAGES * PAGE,
                                page_size=PAGE, prefill_chunk=512),
        backend="pallas", interpret=False)
    assert eng.decode_path == "in_place"
    data = jax.tree.map(lambda x: _sds(x.shape, one_chip, x.dtype),
                        eng.pool.data)
    pool_bytes = eng.pool.kv_bytes()
    compiled = eng._decode.lower(
        params, _sds((SLOTS, 1), one_chip, jnp.int32), data, None,
        _sds((SLOTS, PAGES), one_chip, jnp.int32),
        _sds((SLOTS,), one_chip, jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes
    assert "tpu_custom_call" in compiled.as_text()
    # the insert of a prefilled request writes its pages where they lie
    view = jax.tree.map(lambda x: _sds(x.shape, one_chip, x.dtype),
                        eng.pool.request_cache())
    mem = eng.pool._insert.lower(
        data, None, view, _sds((), one_chip, jnp.int32),
        _sds((PAGES,), one_chip, jnp.int32)).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


def test_code_cell_programs_read_weights_in_place(one_chip):
    """Two deepseek-coder-33b layers on the code cell's pool: the decode
    step and a prefill chunk hand each layer's GEMM weights to the kernel
    inside their stacks, so no layer's weight is copied out of its stack
    (a (7168, 19200) MLP weight is 275 MB, and was copied for every GEMM
    of every step)."""
    import dataclasses
    import re

    from repro import configs
    from repro.models import api
    from repro.serve import ContinuousEngine, PoolConfig

    cfg = dataclasses.replace(configs.get("deepseek-coder-33b"), n_layers=2)
    params = jax.tree.map(
        lambda x: _sds(x.shape, one_chip, x.dtype),
        api.params_specs(None, cfg))
    slots, pages = 16, 65
    eng = ContinuousEngine(
        cfg, params, PoolConfig(n_slots=slots, max_len=pages * PAGE,
                                page_size=PAGE, prefill_chunk=512),
        backend="pallas", interpret=False)
    data = jax.tree.map(lambda x: _sds(x.shape, one_chip, x.dtype),
                        eng.pool.data)
    decode = eng._decode.lower(
        params, _sds((slots, 1), one_chip, jnp.int32), data, None,
        _sds((slots, pages), one_chip, jnp.int32),
        _sds((slots,), one_chip, jnp.int32)).compile()
    view = jax.tree.map(lambda x: _sds(x.shape, one_chip, x.dtype),
                        eng.pool.request_cache())
    chunk = eng._chunk_rest.lower(
        params, {"tokens": _sds((1, 512), one_chip, jnp.int32)}, view,
        _sds((), one_chip, jnp.int32)).compile()
    for compiled in (decode, chunk):
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        for shape in ("7168,19200", "19200,7168", "7168,7168", "7168,1024"):
            assert not re.search(
                rf"= bf16\[(1,)?{shape}\]\S* (fusion|copy)\(", text), shape
    assert decode.memory_analysis().temp_size_in_bytes < 64 << 20
