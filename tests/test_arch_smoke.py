"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs; plus prefill/decode parity checks
for a representative subset."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import ShapeCfg
from repro.models import api

SMALL_TRAIN = ShapeCfg("smoke_train", "train", 32, 2)
SMALL_PREFILL = ShapeCfg("smoke_prefill", "prefill", 32, 2)


def _reduced(name):
    return configs.get(name).reduced()


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_train_step_shapes_and_finite(name):
    cfg = _reduced(name)
    shape = SMALL_TRAIN
    batch = api.make_batch(jax.random.PRNGKey(0), cfg, shape)
    params = api.init_params(jax.random.PRNGKey(1), cfg)

    loss, metrics = api.loss_fn(params, batch, cfg)
    assert np.isfinite(float(loss)), (name, metrics)

    grads = jax.grad(lambda p: api.loss_fn(p, batch, cfg)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat), \
        f"{name}: non-finite grads"

    logits, _ = api.forward(params, batch, cfg)
    tl = api.token_len(cfg, shape)
    assert logits.shape == (shape.global_batch, tl, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_prefill_decode_matches_forward(name):
    """Serving path parity: prefill + stepwise decode == train forward."""
    cfg = _reduced(name)
    if cfg.block == "xlstm":
        cfg = dataclasses.replace(cfg, mlstm_chunk=4)
    if cfg.n_experts:
        # dropless capacity so train forward == serve path exactly (the
        # capacity-dropped train approximation is exercised elsewhere)
        cfg = dataclasses.replace(cfg,
                                  moe_capacity_factor=float(cfg.n_experts))
    shape = SMALL_PREFILL
    t_pre, n_dec = 24, 4
    max_len = t_pre + n_dec

    params = api.init_params(jax.random.PRNGKey(1), cfg)
    full_shape = ShapeCfg("tmp", "train", max_len + (cfg.n_patches or 0)
                          + (api.encdec_src_len(cfg, shape)
                             if api.is_encdec(cfg) else 0),
                          shape.global_batch)
    # build a consistent token stream
    key = jax.random.PRNGKey(2)
    b = shape.global_batch
    tokens = jax.random.randint(key, (b, max_len), 0, cfg.vocab, jnp.int32)
    batch_train = {"tokens": tokens, "labels": tokens}
    if cfg.n_patches:
        batch_train["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (b, cfg.n_patches, cfg.d_model))
    if api.is_encdec(cfg):
        batch_train["src_embeds"] = jax.random.normal(
            jax.random.PRNGKey(4), (b, 8, cfg.d_model))

    logits_full, _ = api.forward(params, batch_train, cfg)

    # prefill on the first t_pre tokens; absolute positions include any
    # modality prefix (the serve engine tracks this offset)
    pos_off = cfg.n_patches or 0
    if api.is_encdec(cfg):
        from repro.models import encdec
        cache = encdec.init_cache(cfg, b, max_len, 8)
        batch_pre = {"tokens": tokens[:, :t_pre],
                     "src_embeds": batch_train["src_embeds"]}
    else:
        from repro.models import transformer
        cache = transformer.init_cache(cfg, b, max_len + pos_off)
        batch_pre = {"tokens": tokens[:, :t_pre]}
        if cfg.n_patches:
            batch_pre["patch_embeds"] = batch_train["patch_embeds"]
    logits_pre, cache = api.prefill(params, batch_pre, cfg, cache)
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_full[:, t_pre - 1]),
        rtol=2e-3, atol=2e-3, err_msg=f"{name} prefill mismatch")

    for i in range(t_pre, max_len):
        logits_i, cache = api.decode_step(
            params, tokens[:, i:i + 1], cfg, cache, i + pos_off)
        np.testing.assert_allclose(
            np.asarray(logits_i), np.asarray(logits_full[:, i]),
            rtol=5e-3, atol=5e-3, err_msg=f"{name} decode step {i}")


def test_param_counts_match_published_scale():
    """Full configs must land near their published parameter counts."""
    expect = {
        "deepseek-v3-671b": (671e9, 0.10),
        "grok-1-314b": (314e9, 0.10),
        "starcoder2-15b": (15e9, 0.15),
        "smollm-135m": (135e6, 0.15),
        "deepseek-coder-33b": (33.3e9, 0.01),
        "mistral-large-123b": (123e9, 0.10),
        "xlstm-1.3b": (1.3e9, 0.35),
        "llava-next-34b": (34e9, 0.15),
        "recurrentgemma-9b": (9e9, 0.35),
    }
    for name, (target, tol) in expect.items():
        total, _ = configs.get(name).param_counts()
        assert abs(total - target) / target < tol, \
            f"{name}: {total/1e9:.2f}B vs {target/1e9:.2f}B"


def test_deepseek_coder_33b_is_the_published_model():
    """Published config.json: theta 1e5, linear RoPE scaling x4, untied
    head; 3.64B parameters at the benchmark's 6 of 62 layers."""
    import dataclasses

    cfg = configs.get("deepseek-coder-33b")
    assert (cfg.rope_theta, cfg.rope_scale, cfg.tie_embeddings) == (
        100000.0, 4.0, False)
    total, _ = dataclasses.replace(cfg, n_layers=6).param_counts()
    assert total == 6 * (7168 * 128 * (2 * 56 + 2 * 8) + 3 * 7168 * 19200) \
        + 2 * 32256 * 7168
    assert round(total / 1e9, 2) == 3.64


def test_deepseek_active_params():
    total, active = configs.get("deepseek-v3-671b").param_counts()
    assert active < total * 0.12  # ~37B active of 671B
