"""Unified decoder-only LM covering the assigned architecture families.

Layer stacks are ``lax.scan``s over stacked parameters (compact HLO, bounded
compile time at 512 devices) with optional per-layer remat.  Heterogeneous
stacks scan over *groups* with a fixed per-step structure:

  * dense / moe:      scan over L identical decoder blocks
  * mla_moe:          3 leading dense blocks (scan) + scan over MoE blocks
  * xlstm:            scan over G groups of (slstm_every-1 mLSTM + 1 sLSTM)
  * rglru_hybrid:     scan over G groups of (rec, rec, attn) + trailing recs

Serve modes (prefill/decode) scan over (params, caches) pairs and emit the
updated caches as scan outputs; the in-place paged decode of dense stacks
scans over params alone, reads the pool as a loop constant and emits only
each layer's new K/V rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchCfg
from repro.layers import attention, embeddings, norms
from repro.core import brgemm
from repro.models import blocks
from repro.sharding.annotate import constrain

MTP_WEIGHT = 0.3
LB_WEIGHT = 0.01
Z_WEIGHT = 1e-4


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _stack_init(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _stack_tree(tree, n: int):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


# ==========================================================================
# init
# ==========================================================================

def init_params(key, cfg: ArchCfg):
    ks = jax.random.split(key, 8)
    dt = _dt(cfg)
    p = {
        "embed": embeddings.init(ks[0], cfg.vocab, cfg.d_model, dtype=dt),
        "final_ln": norms.rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        p["head"] = {
            "w": (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab),
                                    jnp.float32)
                  * cfg.d_model ** -0.5).astype(dt)}

    if cfg.block in ("dense", "moe"):
        use_moe = cfg.block == "moe"
        p["blocks"] = _stack_init(
            ks[2], cfg.n_layers,
            lambda k: blocks.decoder_block_init(k, cfg, use_moe=use_moe))
    elif cfg.block == "mla_moe":
        nd = cfg.n_dense_layers
        p["dense_blocks"] = _stack_init(
            ks[2], nd,
            lambda k: blocks.decoder_block_init(k, cfg, use_moe=False))
        p["moe_blocks"] = _stack_init(
            ks[3], cfg.n_layers - nd,
            lambda k: blocks.decoder_block_init(k, cfg, use_moe=True))
        if cfg.mtp:
            p["mtp_block"] = blocks.decoder_block_init(
                ks[4], cfg, use_moe=False)
    elif cfg.block == "xlstm":
        se = cfg.slstm_every or cfg.n_layers + 1
        if cfg.n_layers % se == 0:
            g, per = cfg.n_layers // se, se - 1
            p["mlstm_groups"] = _stack_init(
                ks[2], g,
                lambda k: _stack_init(
                    k, per, lambda k2: blocks.mlstm_block_init(k2, cfg)))
            p["slstm_groups"] = _stack_init(
                ks[3], g, lambda k: blocks.slstm_block_init(k, cfg))
        else:
            p["mlstm_groups"] = _stack_init(
                ks[2], 1,
                lambda k: _stack_init(
                    k, cfg.n_layers,
                    lambda k2: blocks.mlstm_block_init(k2, cfg)))
    elif cfg.block == "rglru_hybrid":
        n_pat = len(cfg.pattern)
        g = cfg.n_layers // n_pat
        tail = cfg.n_layers - g * n_pat
        n_rec = cfg.pattern.count("rec")
        p["groups"] = {
            "rec": _stack_init(
                ks[2], g,
                lambda k: _stack_init(
                    k, n_rec, lambda k2: blocks.rec_block_init(k2, cfg))),
            "attn": _stack_init(
                ks[3], g, lambda k: blocks.local_attn_block_init(k, cfg)),
        }
        if tail:
            p["tail_rec"] = _stack_init(
                ks[4], tail, lambda k: blocks.rec_block_init(k, cfg))
    else:
        raise ValueError(cfg.block)

    if cfg.n_patches:
        d = cfg.d_model
        p["vision_proj"] = {
            "w1": (jax.random.normal(ks[5], (d, d), jnp.float32)
                   * d ** -0.5).astype(dt),
            "b1": jnp.zeros((d,), dt),
            "w2": (jax.random.normal(ks[6], (d, d), jnp.float32)
                   * d ** -0.5).astype(dt),
            "b2": jnp.zeros((d,), dt),
        }
    return p


# ==========================================================================
# stack runners
# ==========================================================================

def _aux0():
    return {"load_balance_loss": jnp.float32(0),
            "router_z_loss": jnp.float32(0),
            "dropped_fraction": jnp.float32(0)}


def _acc(a, b):
    return jax.tree.map(jnp.add, a, b)


def _scan_train(stacked, x, apply_fn, remat, unroll=False):
    """apply_fn(p, x) -> (x, aux)."""

    def body(carry, p):
        x, aux = carry
        x, aux_i = apply_fn(p, x)
        return (x, _acc(aux, aux_i)), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, _aux0()), stacked, unroll=unroll)
    return x, aux


def _scan_serve(stacked, caches, x, apply_fn, unroll=False):
    """apply_fn(p, x, cache) -> (x, cache)."""

    def body(x, xs):
        p, c = xs
        x, c_new = apply_fn(p, x, c)
        return x, c_new

    return jax.lax.scan(body, x, (stacked, caches), unroll=unroll)


def _run_stacks(params, x, cfg: ArchCfg, *, mode, caches, pos, backend):
    """Returns (x, aux, new_caches)."""
    aux = _aux0()

    if cfg.block in ("dense", "moe"):
        if mode == "train":
            x, aux = _scan_train(
                params["blocks"], x,
                lambda p, h: blocks.decoder_block_apply(
                    p, h, cfg, mode="train", backend=backend)[::2],
                cfg.remat, cfg.scan_unroll)
            return x, aux, caches
        x, new_c = _scan_serve(
            params["blocks"], caches["blocks"], x,
            lambda p, h, c: blocks.decoder_block_apply(
                p, h, cfg, mode=mode, cache=c, pos=pos, backend=backend)[:2],
            cfg.scan_unroll)
        return x, aux, {"blocks": new_c}

    if cfg.block == "mla_moe":
        def dense_fn(p, h):
            h, _, a = blocks.decoder_block_apply(p, h, cfg, mode="train",
                                                 backend=backend)
            return h, a

        def moe_fn(p, h):
            h, _, a = blocks.decoder_block_apply(p, h, cfg, mode="train",
                                                 backend=backend)
            return h, a

        if mode == "train":
            x, a1 = _scan_train(params["dense_blocks"], x, dense_fn,
                                cfg.remat, cfg.scan_unroll)
            x, a2 = _scan_train(params["moe_blocks"], x, moe_fn, cfg.remat,
                                cfg.scan_unroll)
            return x, _acc(a1, a2), caches
        x, c1 = _scan_serve(
            params["dense_blocks"], caches["dense_blocks"], x,
            lambda p, h, c: blocks.decoder_block_apply(
                p, h, cfg, mode=mode, cache=c, pos=pos, backend=backend)[:2],
            cfg.scan_unroll)
        x, c2 = _scan_serve(
            params["moe_blocks"], caches["moe_blocks"], x,
            lambda p, h, c: blocks.decoder_block_apply(
                p, h, cfg, mode=mode, cache=c, pos=pos, backend=backend)[:2],
            cfg.scan_unroll)
        return x, aux, {"dense_blocks": c1, "moe_blocks": c2}

    if cfg.block == "xlstm":
        # states thread through both train (chunkwise) and serve modes
        has_slstm = "slstm_groups" in params
        mg = params["mlstm_groups"]
        sg = params.get("slstm_groups")
        mstates = caches["mlstm"]
        sstates = caches.get("slstm")

        def body(x, xs):
            if has_slstm:
                (mp, sp), (mst, sst) = xs
            else:
                (mp,), (mst,) = xs
                sp, sst = None, None

            def inner(x2, xs2):
                p, st = xs2
                x2, st = blocks.mlstm_block_apply(p, x2, cfg, state=st,
                                                  backend=backend)
                return x2, st

            if cfg.remat and mode == "train":
                inner = jax.checkpoint(inner)
            x, mst = jax.lax.scan(inner, x, (mp, mst),
                                  unroll=cfg.scan_unroll)
            if sp is not None:
                x, sst = blocks.slstm_block_apply(sp, x, cfg, state=sst,
                                                  backend=backend)
                return x, (mst, sst)
            return x, (mst,)

        if has_slstm:
            x, (mstates, sstates) = jax.lax.scan(
                body, x, ((mg, sg), (mstates, sstates)),
                unroll=cfg.scan_unroll)
            return x, aux, {"mlstm": mstates, "slstm": sstates}
        x, (mstates,) = jax.lax.scan(body, x, ((mg,), (mstates,)),
                                     unroll=cfg.scan_unroll)
        return x, aux, {"mlstm": mstates}

    if cfg.block == "rglru_hybrid":
        def group_body(x, xs):
            (rp, ap), (rst, acache) = xs

            def rec_inner(x2, xs2):
                p, st = xs2
                x2, st = blocks.rec_block_apply(p, x2, cfg, state=st,
                                                backend=backend)
                return x2, st

            if cfg.remat and mode == "train":
                rec_inner = jax.checkpoint(rec_inner)
            x, rst = jax.lax.scan(rec_inner, x, (rp, rst),
                                  unroll=cfg.scan_unroll)
            x, acache = blocks.local_attn_block_apply(
                ap, x, cfg, mode=mode, cache=acache, pos=pos,
                backend=backend)
            return x, (rst, acache)

        g = params["groups"]
        x, (rstates, acaches) = jax.lax.scan(
            group_body, x,
            ((g["rec"], g["attn"]),
             (caches["groups_rec"], caches["groups_attn"])),
            unroll=cfg.scan_unroll)
        new_caches = {"groups_rec": rstates, "groups_attn": acaches}
        if "tail_rec" in params:
            def rec_inner(x2, xs2):
                p, st = xs2
                x2, st = blocks.rec_block_apply(p, x2, cfg, state=st,
                                                backend=backend)
                return x2, st
            if cfg.remat and mode == "train":
                rec_inner = jax.checkpoint(rec_inner)
            x, tst = jax.lax.scan(rec_inner, x, (params["tail_rec"],
                                                 caches["tail_rec"]),
                                  unroll=cfg.scan_unroll)
            new_caches["tail_rec"] = tst
        return x, aux, new_caches

    raise ValueError(cfg.block)


# ==========================================================================
# caches / states
# ==========================================================================

def init_cache(cfg: ArchCfg, batch: int, max_len: int):
    if cfg.block in ("dense", "moe"):
        return {"blocks": _stack_tree(
            blocks.decoder_block_cache(cfg, batch, max_len), cfg.n_layers)}
    if cfg.block == "mla_moe":
        c = blocks.decoder_block_cache(cfg, batch, max_len)
        return {"dense_blocks": _stack_tree(c, cfg.n_dense_layers),
                "moe_blocks": _stack_tree(
                    c, cfg.n_layers - cfg.n_dense_layers)}
    if cfg.block == "xlstm":
        se = cfg.slstm_every or cfg.n_layers + 1
        if cfg.n_layers % se == 0:
            g, per = cfg.n_layers // se, se - 1
            return {
                "mlstm": _stack_tree(
                    _stack_tree(blocks.mlstm_block_state(cfg, batch), per),
                    g),
                "slstm": _stack_tree(blocks.slstm_block_state(cfg, batch),
                                     g),
            }
        return {"mlstm": _stack_tree(
            _stack_tree(blocks.mlstm_block_state(cfg, batch),
                        cfg.n_layers), 1)}
    if cfg.block == "rglru_hybrid":
        n_pat = len(cfg.pattern)
        g = cfg.n_layers // n_pat
        tail = cfg.n_layers - g * n_pat
        n_rec = cfg.pattern.count("rec")
        caches = {
            "groups_rec": _stack_tree(
                _stack_tree(blocks.rec_block_state(cfg, batch), n_rec), g),
            "groups_attn": _stack_tree(
                blocks.local_attn_block_cache(cfg, batch, max_len), g),
        }
        if tail:
            caches["tail_rec"] = _stack_tree(
                blocks.rec_block_state(cfg, batch), tail)
        return caches
    raise ValueError(cfg.block)


# `train` mode for recurrent archs still needs state threading; give zeros.
def _train_states(cfg: ArchCfg, batch: int):
    if cfg.block in ("xlstm", "rglru_hybrid"):
        return init_cache(cfg, batch, max_len=1)
    return None


# ==========================================================================
# forward / loss / serve
# ==========================================================================

@jax.named_scope("embed")
def _embed_inputs(params, batch, cfg: ArchCfg):
    h = embeddings.encode(params["embed"], batch["tokens"]).astype(_dt(cfg))
    if cfg.n_patches:
        v = batch["patch_embeds"].astype(_dt(cfg))
        vp = params["vision_proj"]
        v = brgemm.matmul(v, vp["w1"], vp["b1"], activation="gelu")
        v = brgemm.matmul(v, vp["w2"], vp["b2"])
        h = jnp.concatenate([v, h], axis=1)
    return constrain(h, "activation")


@jax.named_scope("head")
def _head(params, h, cfg: ArchCfg):
    h = norms.rmsnorm(params["final_ln"], h)
    if cfg.tie_embeddings:
        logits = embeddings.decode(params["embed"], h)
    else:
        logits = brgemm.matmul(h, params["head"]["w"],
                               out_dtype=jnp.float32)
    return constrain(logits, "logits")


def forward(params, batch, cfg: ArchCfg, *, backend=None):
    """Train-mode forward. Returns (logits_fp32, aux)."""
    h = _embed_inputs(params, batch, cfg)
    caches = _train_states(cfg, h.shape[0])
    h, aux, _ = _run_stacks(params, h, cfg, mode="train", caches=caches,
                            pos=0, backend=backend)
    if cfg.n_patches:
        h = h[:, cfg.n_patches:]
    logits = _head(params, h, cfg)
    if cfg.mtp and "mtp_block" in params:
        h2, _, _ = blocks.decoder_block_apply(
            params["mtp_block"], h, cfg, mode="train", backend=backend)
        aux = dict(aux)
        aux["mtp_logits"] = _head(params, h2, cfg)
    return logits, aux


@jax.named_scope("loss")
def _xent(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


def loss_fn(params, batch, cfg: ArchCfg, *, backend=None):
    logits, aux = forward(params, batch, cfg, backend=backend)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    loss = _xent(logits, labels, mask)
    metrics = {"ce_loss": loss}
    if "mtp_logits" in aux:
        # MTP: predict token t+2 (labels shifted one more step)
        mtp_loss = _xent(aux["mtp_logits"][:, :-1], labels[:, 1:],
                         mask[:, 1:])
        loss = loss + MTP_WEIGHT * mtp_loss
        metrics["mtp_loss"] = mtp_loss
    if cfg.block in ("moe", "mla_moe"):
        loss = (loss + LB_WEIGHT * aux["load_balance_loss"]
                + Z_WEIGHT * aux["router_z_loss"])
        metrics["load_balance_loss"] = aux["load_balance_loss"]
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, batch, cfg: ArchCfg, cache, *, backend=None,
            logit_pos=None):
    """Returns (last-token logits, updated cache).

    ``logit_pos`` (traced int, index into the hidden sequence including any
    patch prefix) selects which position's logits to return instead of the
    last one — used by bucketed prefill, where prompts are right-padded and
    the true last token sits before the pad.
    """
    h = _embed_inputs(params, batch, cfg)
    h, _, cache = _run_stacks(params, h, cfg, mode="prefill", caches=cache,
                              pos=0, backend=backend)
    if logit_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, logit_pos, 1, axis=1)
    logits = _head(params, h_last, cfg)
    return logits[:, 0], cache


def prefill_chunk(params, batch, cfg: ArchCfg, cache, pos, *, length=None,
                  backend=None):
    """One chunk of a longer prompt: tokens at positions ``pos..pos+C-1``.

    The chunk attends causally to everything already written into
    ``cache`` (earlier chunks) plus itself, and appends its own KV at
    ``pos``.  ``length`` (traced int <= C) marks the valid prefix of a
    right-padded final chunk: logits are returned for chunk-local index
    ``length - 1``; pad positions still write KV, but they land beyond the
    prompt and every later mask (``kv_len = pos + 1``) excludes them
    exactly.  Chaining chunks therefore reproduces one-shot ``prefill``.
    Fixed chunk width => one compilation per chunk budget.
    """
    h = _embed_inputs(params, batch, cfg)
    h, _, cache = _run_stacks(params, h, cfg, mode="prefill_chunk",
                              caches=cache, pos=pos, backend=backend)
    idx = h.shape[1] - 1 if length is None else length - 1
    h_last = jax.lax.dynamic_slice_in_dim(h, idx, 1, axis=1)
    logits = _head(params, h_last, cfg)
    return logits[:, 0], cache


def decode_step(params, tokens, cfg: ArchCfg, cache, pos, *, backend=None):
    """tokens: (B, 1); pos: traced int. Returns (logits (B, V), cache)."""
    with jax.named_scope("embed"):
        h = embeddings.encode(params["embed"], tokens).astype(_dt(cfg))
        h = constrain(h, "activation")
    h, _, cache = _run_stacks(params, h, cfg, mode="decode", caches=cache,
                              pos=pos, backend=backend)
    logits = _head(params, h, cfg)
    return logits[:, 0], cache


def decode_step_in_place(params, tokens, cfg: ArchCfg, kv, page_tables,
                         lengths, *, backend=None):
    """One token per slot against a key-major paged pool read in place
    (dense stacks only).

    tokens: (S, 1); kv: {"k", "v"} pool leaves (L, n_pages, Hkv, d,
    page_size), read but not written; page_tables: (S, P); lengths: (S,)
    keys each slot holds, i.e. its token's position.  The pool enters the
    layer scan as a constant, never as scanned inputs or outputs (which
    would restack it).  Returns (logits (S, V), {"k", "v"}: the new rows
    (L, S, Hkv, d)) for the caller to write into the pool.
    """
    with jax.named_scope("embed"):
        h = embeddings.encode(params["embed"], tokens).astype(_dt(cfg))
        h = constrain(h, "activation")

    def body(h, xs):
        p, layer = xs
        pages = attention.PagedKV(kv["k"], kv["v"], layer, page_tables,
                                  lengths)
        h, rows, _ = blocks.decoder_block_apply(
            p, h, cfg, mode="decode_paged", cache=pages, backend=backend)
        return h, rows

    h, rows = jax.lax.scan(body, h, (params["blocks"],
                                     jnp.arange(cfg.n_layers)),
                           unroll=cfg.scan_unroll)
    logits = _head(params, h, cfg)
    return logits[:, 0], rows
