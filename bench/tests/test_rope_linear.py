"""The reference with linear RoPE scaling (``models/llama_rope_linear.py``)
against the program's deepseek-coder-33b block at a CPU size: logits,
loss and gradients in float32, and the serving check of a whole run."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common, serve

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _conf(**program):
    conf = common.load_json(os.path.join(DATA, "tiny-llama-rope-linear.json"))
    conf["torch_dtype"] = "float32"
    conf["program"]["replace"].update(dtype="float32", **program)
    return conf


def test_reference_logits_match_the_program_forward():
    from repro.core import dispatch
    from repro.models import api

    conf = _conf()
    model = common.model_module(conf)
    w = model.make_weights(conf, 2**34 + 19)
    tokens = np.random.default_rng(0).integers(0, 256, 48).astype(np.int32)
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        got, _ = api.forward(model.to_program(conf, w),
                             {"tokens": jnp.asarray(tokens)[None]},
                             common.program_cfg(conf))
    want = model.logits_at(conf, w, tokens, np.arange(48), q_block=16,
                           pad=16)
    assert float(jnp.abs(want).max()) > 1.0
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=0)


def test_reference_loss_and_gradient_match_the_program():
    from repro.core import dispatch
    from repro.models import api

    conf = _conf()
    model = common.model_module(conf)
    arch = common.program_cfg(conf)
    w = model.make_weights(conf, 13)
    toks = np.random.default_rng(1).integers(0, 256, (2, 41)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(api.loss_fn, has_aux=True)(
            model.to_program(conf, w), batch, arch)
    ref_loss, ref_g = model.loss_and_grads(conf, w, batch["tokens"],
                                           batch["labels"])
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = model.leaf_norms(g, program=True)
    want = model.leaf_norms(ref_g, program=False)
    assert got.keys() == want.keys() and "head" in want
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-3, abs=1e-7), k


def test_factor_one_is_the_plain_llama_reference():
    """Without scaling the copy computes what ``llama.py`` computes, bit
    for bit: the rotary map is the only change."""
    conf = _conf()
    conf["rope_scaling"] = None
    plain = dict(conf, model_type="llama")
    scaled, llama = common.model_module(conf), common.model_module(plain)
    w = llama.make_weights(plain, 5)
    tokens = np.random.default_rng(2).integers(0, 256, 32)
    rows = np.arange(32)
    np.testing.assert_array_equal(
        np.asarray(scaled.logits_at(conf, w, tokens, rows, pad=16,
                                    q_block=16)),
        np.asarray(llama.logits_at(plain, w, tokens, rows, pad=16,
                                   q_block=16)))


def test_the_factor_is_read_from_the_configuration():
    model = common.model_module(_conf())
    assert model.factor(_conf()) == 4.0
    assert model.factor({"rope_scaling": None}) == 1.0
    with pytest.raises(ValueError, match="not linear"):
        model.factor({"rope_scaling": {"type": "dynamic", "factor": 2.0}})


def _ctx(conf, seed):
    t = common.load_json(os.path.join(DATA, "tiny-serve.json"))
    return common.Context(
        workload="tiny.rope-linear", conf=conf, traffic=t,
        cell=common.load_json(os.path.join(DATA, "tiny-limits.json")),
        seed=seed, seconds=2.0, trace=False, chips=1,
        t_process=time.perf_counter(), require_chip=False)


def test_serving_program_passes_and_unscaled_program_fails():
    """A whole serving run (chunked prefill, in-place paged decode) at
    the tiny size reads correct; the same run with the program's
    positions left unscaled does not."""
    conf = common.load_json(os.path.join(DATA, "tiny-llama-rope-linear.json"))
    res = serve.run(_ctx(conf, 2**33 + 7))
    assert res["correct"], res["checks"]
    conf["program"]["replace"]["rope_scale"] = 1.0
    res = serve.run(_ctx(conf, 2**33 + 7))
    assert not res["correct"], res["checks"]
