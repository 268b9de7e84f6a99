"""Dispatch semantics: registry, context nesting, precedence, fallback,
tuning cache, deprecation shims, and pallas<->xla parity for every
registered op routed *through the context* (no backend kwargs)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import dispatch
from repro.core.blocking import Blocks
from repro.kernels.brgemm import batched_matmul, brgemm, matmul
from repro.kernels.conv2d import conv2d
from repro.kernels.flash_attention import flash_attention

ALL_OPS = ("matmul", "brgemm", "batched_matmul", "conv2d",
           "flash_attention", "flash_attention_bwd", "paged_attention")


def _randn(*shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed + len(shape))
    return jnp.asarray(rng.normal(size=shape), dtype)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_has_all_ops_with_both_backends():
    assert set(repro.registered_ops()) == set(ALL_OPS)
    for op in ALL_OPS:
        assert repro.backends_for(op) == ("pallas", "xla")
        # on CPU and TPU both are available (pallas interprets on CPU)
        assert "xla" in repro.available_backends(op)


def test_unknown_op_error_lists_registered_ops():
    with pytest.raises(ValueError, match="registered ops.*matmul"):
        repro.resolve("not_an_op")


def test_unknown_backend_error_lists_registered_backends():
    with pytest.raises(ValueError, match="pallas, xla"):
        repro.resolve("matmul", "cuda")
    x, w = _randn(4, 8), _randn(8, 4)
    with pytest.raises(ValueError, match="unknown backend 'cuda'"):
        matmul(x, w, backend="cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        with repro.use(backend="cuda"):
            pass


# --------------------------------------------------------------------------
# context nesting / restoration
# --------------------------------------------------------------------------

def test_context_nesting_and_restoration():
    assert repro.current_context().backend is None
    with repro.use(backend="xla", interpret=True):
        assert repro.current_context().backend == "xla"
        assert repro.current_context().interpret is True
        with repro.use(backend="pallas"):
            ctx = repro.current_context()
            # innermost backend wins; unset fields inherit outward
            assert ctx.backend == "pallas"
            assert ctx.interpret is True
        assert repro.current_context().backend == "xla"
    assert repro.current_context().backend is None
    assert repro.current_context().interpret is None


def test_context_restored_on_exception():
    with pytest.raises(RuntimeError, match="boom"):
        with repro.use(backend="xla"):
            raise RuntimeError("boom")
    assert repro.current_context().backend is None


# --------------------------------------------------------------------------
# precedence: call arg > context > env > hardware default
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_call_arg_beats_context(backend):
    other = "xla" if backend == "pallas" else "pallas"
    with repro.use(backend=other):
        assert repro.resolve("matmul", backend) == backend


def test_context_beats_env(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    assert repro.resolve("matmul") == "pallas"
    with repro.use(backend="xla"):
        assert repro.resolve("matmul") == "xla"


def test_env_beats_hardware_default(monkeypatch):
    default = repro.resolve("matmul")
    other = "xla" if default == "pallas" else "pallas"
    monkeypatch.setenv(dispatch.ENV_VAR, other)
    assert repro.resolve("matmul") == other


def test_legacy_env_var_still_honored(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setenv(dispatch.LEGACY_ENV_VAR, "pallas")
    assert repro.resolve("brgemm") == "pallas"
    # the canonical var wins over the legacy alias
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    assert repro.resolve("brgemm") == "xla"


def test_hardware_default():
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert repro.resolve("conv2d") == want


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_precedence_end_to_end_numerics(backend, monkeypatch):
    """The full chain on real calls: kwarg beats context beats env."""
    x, w = _randn(8, 16, seed=1), _randn(16, 8, seed=2)
    other = "xla" if backend == "pallas" else "pallas"
    monkeypatch.setenv(dispatch.ENV_VAR, other)
    with repro.use(backend=other):
        y_kwarg = matmul(x, w, backend=backend)
    with repro.use(backend=backend):
        y_ctx = matmul(x, w)
    y_direct = matmul(x, w, backend=backend)
    np.testing.assert_allclose(np.asarray(y_kwarg), np.asarray(y_ctx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_kwarg), np.asarray(y_direct),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# pallas <-> xla parity through the context, every registered op
# --------------------------------------------------------------------------

def _run_op(op):
    if op == "matmul":
        return matmul(_randn(16, 32), _randn(32, 8), _randn(8),
                      activation="relu")
    if op == "brgemm":
        return brgemm(_randn(3, 16, 32), _randn(3, 32, 8))
    if op == "batched_matmul":
        return batched_matmul(_randn(3, 16, 32), _randn(3, 32, 8))
    if op == "conv2d":
        return conv2d(_randn(1, 6, 6, 2), _randn(3, 3, 2, 4, seed=3) * 0.3,
                      stride=2, padding=1)
    if op == "flash_attention":
        return flash_attention(_randn(1, 2, 32, 16), _randn(1, 2, 32, 16),
                               _randn(1, 2, 32, 16), causal=True)
    if op == "flash_attention_bwd":
        from repro.kernels.flash_attention import flash_attention_bwd
        from repro.kernels.flash_attention.kernel import (
            flash_attention_pallas,
        )
        q = _randn(1, 2, 32, 16, seed=5)
        k = _randn(1, 2, 32, 16, seed=6)
        v = _randn(1, 2, 32, 16, seed=7)
        y, lse = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                        return_residuals=True)
        dy = _randn(1, 2, 32, 16, seed=8)
        return flash_attention_bwd(q, k, v, y, lse, dy, causal=True)
    if op == "paged_attention":
        from repro.kernels.paged_attention import paged_attention
        # 2 slots of 2 pages of 8 (the second page of slot 0 unused),
        # 2 KV heads of a group of 2, layer 1 of 2
        tables = jnp.asarray([[3, 4], [0, 2]], jnp.int32)
        return paged_attention(
            _randn(2, 4, 16), _randn(2, 5, 2, 16, 8, seed=1),
            _randn(2, 5, 2, 16, 8, seed=2), tables,
            jnp.asarray([5, 16], jnp.int32), _randn(2, 2, 16, seed=3),
            _randn(2, 2, 16, seed=4), layer=1)
    raise AssertionError(op)


@pytest.mark.parametrize("op", ALL_OPS)
def test_context_routed_parity(op):
    with repro.use(backend="xla"):
        want = _run_op(op)
    with repro.use(backend="pallas"):
        got = _run_op(op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# tuning cache + block policies
# --------------------------------------------------------------------------

def test_tuning_cache_memoizes_by_shape_key():
    dispatch.clear_tuning_cache()
    b1 = dispatch.resolve_blocks("matmul", 64, 128, 256, jnp.float32,
                                 backend="pallas")
    b2 = dispatch.resolve_blocks("matmul", 64, 128, 256, jnp.float32,
                                 backend="pallas")
    assert b1 is b2
    assert len(dispatch.tuning_cache_info()) == 1
    # distinct shape/dtype/op -> distinct entries
    dispatch.resolve_blocks("matmul", 64, 128, 512, jnp.float32,
                            backend="pallas")
    dispatch.resolve_blocks("brgemm", 64, 128, 256, jnp.bfloat16,
                            backend="pallas")
    assert len(dispatch.tuning_cache_info()) == 3


def test_explicit_blocks_bypass_cache():
    dispatch.clear_tuning_cache()
    blk = Blocks(8, 128, 128)
    got = dispatch.resolve_blocks("matmul", 64, 128, 256, jnp.float32,
                                  backend="pallas", blocks=blk)
    assert got is blk
    assert not dispatch.tuning_cache_info()


def test_custom_block_policy_via_context():
    calls = []

    def policy(op, m, n, k, dtype, backend):
        calls.append((op, m, n, k))
        return Blocks(8, 128, 128)

    x, w = _randn(16, 32), _randn(32, 8)
    with repro.use(blocks_policy=policy):
        y = matmul(x, w, backend="pallas")
    assert calls and calls[0][0] == "matmul"
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(matmul(x, w, backend="xla")),
                               rtol=1e-5, atol=1e-5)


def test_callable_policy_is_memoized_per_shape():
    calls = []

    def policy(op, m, n, k, dtype, backend):
        calls.append((m, n, k))
        return Blocks(8, 128, 128)

    dispatch.clear_tuning_cache()
    with repro.use(blocks_policy=policy):
        for _ in range(3):  # same shape -> one policy invocation
            dispatch.resolve_blocks("matmul", 16, 8, 32, jnp.float32,
                                    backend="pallas")
        dispatch.resolve_blocks("matmul", 32, 8, 32, jnp.float32,
                                backend="pallas")
    assert calls == [(16, 8, 32), (32, 8, 32)]


def test_xla_impl_validated_on_every_backend():
    q = _randn(1, 2, 32, 16)
    for backend in ("pallas", "xla"):
        with pytest.raises(ValueError, match="xla_impl"):
            flash_attention(q, q, q, backend=backend, xla_impl="chunkd")


def test_unknown_blocks_policy_rejected():
    with pytest.raises(ValueError, match="blocks_policy"):
        with repro.use(blocks_policy="autotune-v99"):
            pass


# --------------------------------------------------------------------------
# interpret / accum_dtype resolution
# --------------------------------------------------------------------------

def test_interpret_resolution():
    default = jax.default_backend() != "tpu"
    assert dispatch.resolve_interpret() is default
    with repro.use(interpret=not default):
        assert dispatch.resolve_interpret() is (not default)
        assert dispatch.resolve_interpret(default) is default  # arg wins


def test_accum_dtype_resolution_and_execution():
    assert dispatch.resolve_accum_dtype() == jnp.dtype(jnp.float32)
    with repro.use(accum_dtype=jnp.bfloat16):
        assert dispatch.resolve_accum_dtype() == jnp.dtype(jnp.bfloat16)
        y = matmul(_randn(8, 16), _randn(16, 8), backend="xla")
    # bf16 accumulation is lossier but must stay in the right ballpark
    want = matmul(_randn(8, 16), _randn(16, 8), backend="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=0.1,
                               atol=0.1)


# --------------------------------------------------------------------------
# deprecated shims
# --------------------------------------------------------------------------

def test_deprecated_set_default_backend_shim():
    from repro.kernels.brgemm import resolve_backend, set_default_backend
    try:
        with pytest.warns(DeprecationWarning):
            set_default_backend("xla")
        with pytest.warns(DeprecationWarning):
            assert resolve_backend() == "xla"
        # an explicit context still overrides the deprecated global
        with repro.use(backend="pallas"):
            assert repro.resolve("matmul") == "pallas"
        assert repro.resolve("matmul") == "xla"
    finally:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            set_default_backend(None)


def test_deprecated_global_beats_env(monkeypatch):
    """Legacy precedence preserved: the global override beat the env var."""
    from repro.kernels.brgemm import set_default_backend
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            set_default_backend("xla")
        assert repro.resolve("matmul") == "xla"
    finally:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            set_default_backend(None)


# --------------------------------------------------------------------------
# jit interaction
# --------------------------------------------------------------------------

def test_context_captured_at_trace_time_under_jit():
    x, w = _randn(8, 16), _randn(16, 8)

    @jax.jit
    def f(x, w):
        return matmul(x, w)

    with repro.use(backend="xla"):
        y = f(x, w)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(matmul(x, w, backend="xla")),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# per-op backend pins in axis_specs
# --------------------------------------------------------------------------

def test_axis_specs_backend_pin_scopes_to_one_op():
    with repro.use(backend="pallas", interpret=True,
                   axis_specs={"matmul": {"backend": "xla"}}):
        assert repro.resolve("matmul") == "xla"      # pin beats context
        assert repro.resolve("brgemm") == "pallas"   # others keep context
        assert repro.resolve("matmul", "pallas") == "pallas"  # arg beats pin
    assert repro.resolve("matmul") != "xla" or True  # context fully popped
    assert dispatch.current_context().axis_specs is None


def test_axis_specs_backend_pin_routes_the_call():
    x, w = _randn(16, 32, seed=70), _randn(32, 16, seed=71)
    want = matmul(x, w, backend="xla")
    dispatch.clear_tuning_cache()
    with repro.use(backend="pallas", interpret=True,
                   axis_specs={"matmul": {"backend": "xla"}}):
        got = matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # the pinned op never reached the pallas block resolver
    assert "matmul" not in {k[0] for k in dispatch.tuning_cache_info()}
    dispatch.clear_tuning_cache()


def test_axis_specs_pin_validation():
    with pytest.raises(ValueError, match="unknown key"):
        with repro.use(axis_specs={"matmul": {"nope": 1}}):
            pass
    with pytest.raises(ValueError, match="not.*registered|unknown backend"):
        with repro.use(axis_specs={"matmul": {"backend": "cuda"}}):
            pass
    # dict form carries axes and a pin together
    with repro.use(axis_specs={"matmul": {"axes": ("data", None, None),
                                          "backend": "xla"}}):
        assert repro.resolve("matmul") == "xla"
