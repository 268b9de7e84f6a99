"""Linear RoPE scaling (``rope_scale``) against the plain reference.

deepseek-coder-33b's published block (linear RoPE x4, theta 1e5, untied
head) at a CPU size with its group of 7 query heads per KV head, on
seeded random weights from the benchmark's reference
(``bench/models/llama_rope_linear.py``), both sides in float32: the
program's full forward, its one-shot prefill, and a request served by
``ContinuousEngine`` through chunked prefill and in-place paged decode
agree with the reference's logits.  The same program with its positions
left unscaled fails every one of these comparisons, and at scale 1 the
rotary map is today's, op for op.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import dispatch
from repro.layers import attention, rope
from repro.models import api
from repro.serve import ContinuousEngine, PoolConfig, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on both sides, the reference at "highest" matmul precision: the
# logits differ only by summation order, about 1e-5 at these sizes
ATOL = 2e-4
# what unscaled positions cost: the rotation of every position past the
# first changes, and the logits move by far more than round-off
UNSCALED_AT_LEAST = 100 * ATOL

CONF = {
    "model_type": "llama_rope_linear", "hidden_size": 112,
    "intermediate_size": 128, "num_attention_heads": 7,
    "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "rope_theta": 100000.0,
    "rope_scaling": {"type": "linear", "factor": 4.0},
    "tie_word_embeddings": False, "torch_dtype": "float32",
}
CASES = pytest.mark.parametrize("rope_scale,agrees", [(4.0, True),
                                                      (1.0, False)],
                                ids=["published", "unscaled"])


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "bench", "models", "llama_rope_linear.py")
    spec = importlib.util.spec_from_file_location("ref_rope_linear", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    w = mod.make_weights(CONF, 2**33 + 15)
    return mod, w, mod.to_program(CONF, w)


def _cfg(rope_scale):
    return dataclasses.replace(
        configs.get("deepseek-coder-33b"), n_layers=2, d_model=112,
        n_heads=7, n_kv_heads=1, head_dim=16, d_ff=128, vocab=256,
        dtype="float32", remat=False, rope_scale=rope_scale)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _ref(reference, tokens, rows):
    mod, w, _ = reference
    return np.asarray(mod.logits_at(CONF, w, tokens, rows, q_block=16,
                                    pad=16))


def _judge(got, want, agrees):
    assert float(np.abs(want).max()) > 1.0
    gap = float(np.abs(np.asarray(got) - want).max())
    if agrees:
        assert gap <= ATOL, gap
    else:
        assert gap >= UNSCALED_AT_LEAST, gap


@CASES
def test_forward_matches_reference(reference, rope_scale, agrees):
    tokens = _tokens(48)
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        got, _ = api.forward(reference[2],
                             {"tokens": jnp.asarray(tokens)[None]},
                             _cfg(rope_scale))
    _judge(got[0], _ref(reference, tokens, np.arange(48)), agrees)


@CASES
def test_one_shot_prefill_matches_reference(reference, rope_scale, agrees):
    cfg = _cfg(rope_scale)
    tokens = _tokens(40, seed=1)
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        got, _ = api.prefill(reference[2],
                             {"tokens": jnp.asarray(tokens)[None]}, cfg,
                             api.init_cache(cfg, 1, 64))
    _judge(got, _ref(reference, tokens, np.array([39])), agrees)


@CASES
def test_chunked_prefill_and_paged_decode_match_reference(
        reference, rope_scale, agrees):
    """A 44-token prompt in chunks of 16, 16 and 12, then 7 decode steps
    in place on a pool of 8-token pages; every served token's logits
    against the reference's, teacher-forced on the served tokens."""
    eng = ContinuousEngine(
        _cfg(rope_scale), reference[2],
        PoolConfig(n_slots=2, max_len=64, page_size=8, prefill_chunk=16),
        backend="xla")
    assert eng.decode_path == "in_place"
    logits = []

    def recorded(fn):
        def run(*args):
            out = fn(*args)
            logits.append(np.asarray(out[0]))
            return out
        return run

    eng._chunk_rest = recorded(eng._chunk_rest)
    eng._decode = recorded(eng._decode)
    prompt = _tokens(44, seed=2).tolist()
    with jax.default_matmul_precision("highest"):
        served = eng.serve([Request(prompt=prompt, max_tokens=8,
                                    stop_tokens=())])[0]
    assert eng.metrics.prefill_chunks == 3
    assert eng.metrics.decode_steps_in_place == 7
    # the last chunk's logits, then slot 0's row of each decode step
    got = np.stack([logits[1][0]] + [x[0] for x in logits[2:]])
    want = _ref(reference, prompt + served[:-1], np.arange(43, 51))
    _judge(got, want, agrees)


def _rope_before_scaling(x, positions, *, theta=10000.0):
    """``apply_rope`` as it was before it took a scale."""
    d = x.shape[-1]
    freqs = rope.rope_freqs(d, theta=theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def test_scale_one_is_todays_rotary_map():
    x = jnp.ones((2, 3, 5, 8), jnp.bfloat16)
    pos = jnp.arange(5)
    assert (str(jax.make_jaxpr(lambda a, p: rope.apply_rope(a, p))(x, pos))
            == str(jax.make_jaxpr(_rope_before_scaling)(x, pos)))


def test_scale_one_reproduces_smollm_logits_exactly(monkeypatch):
    cfg = configs.get("smollm-135m").reduced()
    assert cfg.rope_scale == 1.0
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(_tokens(24, seed=3) % cfg.vocab)[None]

    def logits():
        with dispatch.use(backend="xla"):
            return np.asarray(jax.jit(
                lambda p, t: api.forward(p, {"tokens": t}, cfg)[0])(
                    params, tokens))

    now = logits()
    monkeypatch.setattr(
        attention, "apply_rope",
        lambda x, p, *, theta, scale: _rope_before_scaling(x, p,
                                                           theta=theta))
    np.testing.assert_array_equal(now, logits())
