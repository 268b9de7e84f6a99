"""The plain reference against the program's own forward pass and loss,
both in float32, at a size the CPU runs in a second."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common


def _conf(tied: bool):
    conf = common.load_json(
        __import__("os").path.join(__import__("os").path.dirname(__file__),
                                   "data", "tiny-llama.json"))
    conf["torch_dtype"] = "float32"
    conf["tie_word_embeddings"] = tied
    conf["program"]["replace"].update(dtype="float32", tie_embeddings=tied)
    return conf


@pytest.mark.parametrize("tied", [False, True])
def test_reference_logits_match_the_program_forward(tied):
    from repro.core import dispatch
    from repro.models import api

    conf = _conf(tied)
    model = common.model_module(conf)
    arch = common.program_cfg(conf)
    w = model.make_weights(conf, 2**34 + 17)
    params = model.to_program(conf, w)
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        got, _ = api.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                             arch)
    want = model.logits_at(conf, w, tokens, np.arange(40), q_block=16,
                           pad=16)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=0)


def test_reference_loss_and_gradient_match_the_program():
    from repro.core import dispatch
    from repro.models import api

    conf = _conf(False)
    model = common.model_module(conf)
    arch = dataclasses.replace(common.program_cfg(conf), remat=False)
    w = model.make_weights(conf, 11)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, (2, 33)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with dispatch.use(backend="xla"), jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(api.loss_fn, has_aux=True)(
            model.to_program(conf, w), batch, arch)
    ref_loss, ref_g = model.loss_and_grads(conf, w, batch["tokens"],
                                           batch["labels"])
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = model.leaf_norms(g, program=True)
    want = model.leaf_norms(ref_g, program=False)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-3, abs=1e-7), k
