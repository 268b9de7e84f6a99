"""Single-layer transformer blocks for every assigned architecture family.

Each block exposes ``*_init(key, cfg)``, ``*_apply(params, x, cfg, ...)`` and
``*_cache(cfg, batch, max_len)``; stacking/scanning lives in
``models/transformer.py``.  The aux dict (MoE losses) keeps a fixed structure
so heterogeneous stacks scan cleanly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchCfg
from repro.layers import attention, mlp, moe, norms, recurrent

ZERO_AUX = {"load_balance_loss": 0.0, "router_z_loss": 0.0,
            "dropped_fraction": 0.0}


def _dtype(cfg: ArchCfg):
    return jnp.dtype(cfg.dtype)


def attn_cfg(cfg: ArchCfg, *, window=None) -> attention.AttnCfg:
    return attention.AttnCfg(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        window=window if window is not None else cfg.window,
        mla=cfg.mla, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        xla_impl=cfg.attention_impl, unroll=cfg.scan_unroll)


def moe_cfg(cfg: ArchCfg) -> moe.MoECfg:
    return moe.MoECfg(
        d_model=cfg.d_model, d_ff=cfg.moe_d_ff, n_experts=cfg.n_experts,
        top_k=cfg.top_k, n_shared=cfg.n_shared_experts,
        capacity_factor=cfg.moe_capacity_factor)


# --------------------------------------------------------------------------
# dense / moe decoder block: x += attn(ln(x)); x += ffn(ln(x))
# --------------------------------------------------------------------------

def decoder_block_init(key, cfg: ArchCfg, *, use_moe: bool):
    ks = jax.random.split(key, 2)
    dt = _dtype(cfg)
    p = {
        "ln1": norms.rmsnorm_init(cfg.d_model, dt),
        "attn": attention.init(ks[0], attn_cfg(cfg), dt),
        "ln2": norms.rmsnorm_init(cfg.d_model, dt),
    }
    if use_moe:
        p["moe"] = moe.init(ks[1], moe_cfg(cfg), dt)
    else:
        p["mlp"] = mlp.init(ks[1], cfg.d_model, cfg.d_ff,
                            gated=cfg.gated_mlp, dtype=dt)
    return p


def decoder_block_apply(params, x, cfg: ArchCfg, *, mode="train",
                        cache=None, pos=0, backend=None):
    with jax.named_scope("attention"):
        x, new_cache = _attention_sublayer(params, x, cfg, mode=mode,
                                           cache=cache, pos=pos,
                                           backend=backend)
    with jax.named_scope("mlp"):
        h = norms.rmsnorm(params["ln2"], x)
        if "moe" in params:
            y, aux = moe.apply(params["moe"], h, moe_cfg(cfg),
                               backend=backend)
        else:
            y = mlp.apply(params["mlp"], h, activation=cfg.mlp_activation,
                          backend=backend)
            aux = ZERO_AUX
        return x + y, new_cache, aux


def _attention_sublayer(params, x, cfg: ArchCfg, *, mode, cache, pos,
                        backend):
    """x + attn(ln(x)) and the updated cache."""
    acfg = attn_cfg(cfg)
    h = norms.rmsnorm(params["ln1"], x)
    if mode == "train":
        return x + attention.apply(params["attn"], h, acfg, mode="train",
                                   backend=backend), cache
    if cfg.window and not cfg.mla:
        if mode == "prefill_chunk":
            raise ValueError(
                "chunked prefill is not supported for sliding-window archs "
                "(ring cache holds only the trailing window)")
        # sliding-window archs serve from a ring buffer of size `window`
        if mode == "decode":
            y, new_cache = _ring_decode(params["attn"], h, acfg, cache, pos,
                                        backend)
        else:  # prefill
            y = attention.apply(params["attn"], h, acfg, mode="train",
                                backend=backend)
            new_cache = _ring_from_prefill(params["attn"], h, acfg, cache,
                                           backend)
        return x + y, new_cache
    y, new_cache = attention.apply(
        params["attn"], h, acfg, mode=mode, cache=cache, pos=pos,
        backend=backend)
    return x + y, new_cache


def decoder_block_cache(cfg: ArchCfg, batch: int, max_len: int):
    acfg = attn_cfg(cfg)
    length = min(max_len, cfg.window) if cfg.window else max_len
    return attention.init_cache(acfg, batch, length, _dtype(cfg))


# --------------------------------------------------------------------------
# xLSTM block: x += mixer(ln(x));  mixer in {mLSTM, sLSTM}
# --------------------------------------------------------------------------

def mlstm_cfg(cfg: ArchCfg) -> recurrent.MLSTMCfg:
    dh = cfg.d_model // cfg.n_heads
    return recurrent.MLSTMCfg(d_model=cfg.d_model, n_heads=cfg.n_heads,
                              dk=dh, dv=dh, chunk=cfg.mlstm_chunk,
                              unroll=cfg.scan_unroll)


def slstm_cfg(cfg: ArchCfg) -> recurrent.SLSTMCfg:
    return recurrent.SLSTMCfg(d_model=cfg.d_model, n_heads=cfg.n_heads)


def mlstm_block_init(key, cfg: ArchCfg):
    dt = _dtype(cfg)
    return {"ln": norms.rmsnorm_init(cfg.d_model, dt),
            "mlstm": recurrent.mlstm_init(key, mlstm_cfg(cfg), dt)}


def mlstm_block_apply(params, x, cfg, *, state=None, backend=None):
    h = norms.rmsnorm(params["ln"], x)
    y, state = recurrent.mlstm_apply(params["mlstm"], h, mlstm_cfg(cfg),
                                     state=state, backend=backend)
    return x + y, state


def mlstm_block_state(cfg: ArchCfg, batch: int):
    m = mlstm_cfg(cfg)
    return (jnp.zeros((batch, m.n_heads, m.dk, m.dv), jnp.float32),
            jnp.zeros((batch, m.n_heads, m.dk), jnp.float32),
            jnp.full((batch, m.n_heads), -1e30, jnp.float32))


def slstm_block_init(key, cfg: ArchCfg):
    dt = _dtype(cfg)
    return {"ln": norms.rmsnorm_init(cfg.d_model, dt),
            "slstm": recurrent.slstm_init(key, slstm_cfg(cfg), dt)}


def slstm_block_apply(params, x, cfg, *, state=None, backend=None):
    h = norms.rmsnorm(params["ln"], x)
    y, state = recurrent.slstm_apply(params["slstm"], h, slstm_cfg(cfg),
                                     state=state, backend=backend)
    return x + y, state


def slstm_block_state(cfg: ArchCfg, batch: int):
    d = cfg.d_model
    return {"h": jnp.zeros((batch, d), jnp.float32),
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.ones((batch, d), jnp.float32),
            "m": jnp.full((batch, d), -1e30, jnp.float32)}


# --------------------------------------------------------------------------
# RG-LRU hybrid blocks (RecurrentGemma): rec/rec/attn pattern, each with MLP
# --------------------------------------------------------------------------

def rglru_cfg(cfg: ArchCfg) -> recurrent.RGLRUCfg:
    return recurrent.RGLRUCfg(d_model=cfg.d_model, d_rnn=cfg.d_rnn)


def rec_block_init(key, cfg: ArchCfg):
    ks = jax.random.split(key, 2)
    dt = _dtype(cfg)
    return {
        "ln1": norms.rmsnorm_init(cfg.d_model, dt),
        "rglru": recurrent.rglru_init(ks[0], rglru_cfg(cfg), dt),
        "ln2": norms.rmsnorm_init(cfg.d_model, dt),
        "mlp": mlp.init(ks[1], cfg.d_model, cfg.d_ff,
                        gated=cfg.gated_mlp, dtype=dt),
    }


def rec_block_apply(params, x, cfg, *, state=None, backend=None):
    h = norms.rmsnorm(params["ln1"], x)
    y, state = recurrent.rglru_apply(params["rglru"], h, rglru_cfg(cfg),
                                     state=state, backend=backend)
    x = x + y
    with jax.named_scope("mlp"):
        x = x + mlp.apply(params["mlp"], norms.rmsnorm(params["ln2"], x),
                          activation=cfg.mlp_activation, backend=backend)
    return x, state


def rec_block_state(cfg: ArchCfg, batch: int):
    r = rglru_cfg(cfg)
    return {"h": jnp.zeros((batch, r.d_rnn), jnp.float32),
            "conv": jnp.zeros((batch, r.conv_width - 1, r.d_rnn),
                              _dtype(cfg))}


def local_attn_block_init(key, cfg: ArchCfg):
    ks = jax.random.split(key, 2)
    dt = _dtype(cfg)
    return {
        "ln1": norms.rmsnorm_init(cfg.d_model, dt),
        "attn": attention.init(ks[0], attn_cfg(cfg), dt),
        "ln2": norms.rmsnorm_init(cfg.d_model, dt),
        "mlp": mlp.init(ks[1], cfg.d_model, cfg.d_ff,
                        gated=cfg.gated_mlp, dtype=dt),
    }


def local_attn_block_apply(params, x, cfg, *, mode="train", cache=None,
                           pos=0, backend=None):
    acfg = attn_cfg(cfg)
    with jax.named_scope("attention"):
        h = norms.rmsnorm(params["ln1"], x)
        if mode == "train":
            x = x + attention.apply(params["attn"], h, acfg, mode="train",
                                    backend=backend)
            new_cache = cache
        elif mode == "decode":
            # ring-buffer cache of size window
            y, new_cache = _ring_decode(params["attn"], h, acfg, cache, pos,
                                        backend)
            x = x + y
        else:  # prefill
            y = attention.apply(params["attn"], h, acfg, mode="train",
                                backend=backend)
            new_cache = _ring_from_prefill(params["attn"], h, acfg, cache,
                                           backend)
            x = x + y
    with jax.named_scope("mlp"):
        x = x + mlp.apply(params["mlp"], norms.rmsnorm(params["ln2"], x),
                          activation=cfg.mlp_activation, backend=backend)
    return x, new_cache


def _ring_decode(attn_params, h, acfg, cache, pos, backend):
    from repro.kernels.flash_attention.ref import mha_ref
    from repro.core import brgemm
    w = cache["k"].shape[2]
    positions = jnp.full((h.shape[1],), pos)
    q, k, v = attention._gqa_qkv(attn_params, h, acfg, positions, backend)
    slot = pos % w
    cache = dict(cache)
    with jax.named_scope("kv_write"):
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
    kv_len = jnp.minimum(pos + 1, w)
    with jax.named_scope("core"):
        o = mha_ref(q, cache["k"], cache["v"], causal=False, kv_len=kv_len)
    y = brgemm.matmul(attention._merge_heads(o), attn_params["wo"],
                      backend=backend)
    return y, cache


def _ring_from_prefill(attn_params, h, acfg, cache, backend):
    """Build the decode ring buffer from the last `window` prefill keys."""
    w = cache["k"].shape[2]
    t = h.shape[1]
    positions = jnp.arange(t)
    _, k, v = attention._gqa_qkv(attn_params, h, acfg, positions, backend)
    with jax.named_scope("kv_write"):
        if t >= w:
            k_last, v_last = k[:, :, -w:], v[:, :, -w:]
            shift = (t - w) % w
            k_last = jnp.roll(k_last, shift, axis=2)
            v_last = jnp.roll(v_last, shift, axis=2)
            return {"k": k_last.astype(cache["k"].dtype),
                    "v": v_last.astype(cache["v"].dtype)}
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        return cache


def local_attn_block_cache(cfg: ArchCfg, batch: int, max_len: int):
    acfg = attn_cfg(cfg)
    length = min(max_len, cfg.window or max_len)
    return attention.init_cache(acfg, batch, length, _dtype(cfg))
