"""Plain float32 reference of the Llama-style dense decoder, and its weights.

This module is the yardstick for every configuration whose file says
``"model_type": "llama"``.  It imports nothing of the program under test.
It follows the published Hugging Face ``LlamaForCausalLM`` description:

  x = embed[tokens]
  per layer:  x += W_o attn(rope(W_q n1(x)), rope(W_k n1(x)), W_v n1(x))
              x += W_down (silu(W_gate n2(x)) * W_up n2(x))
  logits = head(norm(x))        (head = embed^T when tied)

with RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``, grouped-query causal
softmax attention scaled by ``head_dim ** -0.5``, and rotary embeddings in
the ``rotate_half`` layout (feature ``i`` pairs with ``i + head_dim/2``).

Weights are made here from the seed, in the layout above and in the
dtype the configuration serves (``torch_dtype``); :func:`to_program`
re-lays them out for the program, as a checkpoint converter would.  All
arithmetic of the reference is float32 at ``highest`` matmul precision;
``quant="fp8"`` instead rounds every matmul operand to float8 e4m3 with a
per-tensor scale (the lower-precision control; gradients pass straight
through the rounding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0   # largest finite float8_e4m3fn


def dims(conf: dict) -> dict:
    h = conf["num_attention_heads"]
    d = conf["hidden_size"]
    return dict(d=d, h=h, kv=conf["num_key_value_heads"],
                dh=conf.get("head_dim") or d // h,
                ff=conf["intermediate_size"], v=conf["vocab_size"],
                layers=conf["num_hidden_layers"],
                eps=float(conf["rms_norm_eps"]),
                theta=float(conf["rope_theta"]),
                tied=bool(conf["tie_word_embeddings"]),
                dtype=jnp.dtype(conf["torch_dtype"]))


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def weight_shapes(conf: dict) -> dict:
    m = dims(conf)
    d, L = m["d"], m["layers"]
    layer = {"ln1": (L, d), "wq": (L, d, m["h"] * m["dh"]),
             "wk": (L, d, m["kv"] * m["dh"]), "wv": (L, d, m["kv"] * m["dh"]),
             "wo": (L, m["h"] * m["dh"], d), "ln2": (L, d),
             "wg": (L, d, m["ff"]), "wu": (L, d, m["ff"]),
             "wd": (L, m["ff"], d)}
    shapes = {"embed": (m["v"], d), "norm": (d,), "layers": layer}
    if not m["tied"]:
        shapes["head"] = (d, m["v"])
    return shapes


def _init_leaf(key, name, shape, dtype):
    if name in ("ln1", "ln2", "norm"):
        # scales near 1, not exactly 1, so that a dropped scale shows
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = shape[-1] if name == "embed" else shape[-2]
        w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(dtype)


def make_weights(conf: dict, seed: int, *, layout=None):
    """All weights, on the default device, from one jitted call; with
    ``layout`` (such as :func:`to_program`), in the tree it makes of
    them."""
    shapes = weight_shapes(conf)
    dtype = dims(conf)["dtype"]
    flat = sorted(jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0],
        key=lambda kv: jax.tree_util.keystr(kv[0]))

    def build(key):
        out = {}
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            leaf = _init_leaf(jax.random.fold_in(key, i), name, shape, dtype)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p.key, {})
            node[name] = leaf
        return out

    if layout is None:
        return jax.jit(build)(seed_key(seed))
    return jax.jit(lambda key: layout(conf, build(key)))(seed_key(seed))


def _pairs_interleaved(w, dh: int):
    """Columns of each head from ``rotate_half`` order (i, i + dh/2) to
    interleaved order (2i, 2i + 1): the same rotary map on the same
    weights, for a program that rotates adjacent pairs."""
    lead, n = w.shape[:-1], w.shape[-1]
    w = w.reshape(lead + (n // dh, 2, dh // 2))
    return jnp.swapaxes(w, -1, -2).reshape(lead + (n,))


def to_program(conf: dict, w: dict):
    """The program's parameter tree (``models/transformer.py`` layout).

    Also maps any tree of the same structure (gradients, updates);
    per-leaf norms are unchanged by the column reordering."""
    dh = dims(conf)["dh"]
    lay = w["layers"]

    def qk(x):
        return _pairs_interleaved(x, dh)

    p = {"embed": {"table": w["embed"]},
         "final_ln": {"scale": w["norm"]},
         "blocks": {"ln1": {"scale": lay["ln1"]},
                    "attn": {"wq": qk(lay["wq"]), "wk": qk(lay["wk"]),
                             "wv": lay["wv"], "wo": lay["wo"]},
                    "ln2": {"scale": lay["ln2"]},
                    "mlp": {"w_gate": lay["wg"], "w_up": lay["wu"],
                            "w_down": lay["wd"]}}}
    if "head" in w:
        p["head"] = {"w": w["head"]}
    return p


# names of the reference leaves at the program's tree paths
PROGRAM_PATHS = {
    "embed": ("embed", "table"), "norm": ("final_ln", "scale"),
    "head": ("head", "w"),
    "layers.ln1": ("blocks", "ln1", "scale"),
    "layers.wq": ("blocks", "attn", "wq"),
    "layers.wk": ("blocks", "attn", "wk"),
    "layers.wv": ("blocks", "attn", "wv"),
    "layers.wo": ("blocks", "attn", "wo"),
    "layers.ln2": ("blocks", "ln2", "scale"),
    "layers.wg": ("blocks", "mlp", "w_gate"),
    "layers.wu": ("blocks", "mlp", "w_up"),
    "layers.wd": ("blocks", "mlp", "w_down"),
}


def leaf_norms(tree, *, program: bool, minus=None, scale: float = 1.0):
    """Per-leaf L2 norms of ``scale * (tree - minus)`` (float32), stacked
    layer leaves split per layer: ``{"layers.wq.3": norm, "embed": norm,
    ...}``; a jitted reduction on the device, returned to the host.
    ``program`` says whether the trees are in the program's layout."""
    def pick(t, path):
        for p in path:
            t = t[p]
        return t

    names, leaves, subs = [], [], []
    for name, path in PROGRAM_PATHS.items():
        path = path if program else tuple(name.split("."))
        try:
            leaves.append(pick(tree, path))
        except KeyError:
            continue
        names.append(name)
        subs.append(None if minus is None else pick(minus, path))

    def norms(leaves, subs):
        out = []
        for n, x, y in zip(names, leaves, subs):
            x = x.astype(jnp.float32)
            if y is not None:
                x = x - y.astype(jnp.float32)
            x = x * scale
            if n.startswith("layers."):
                out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                    range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x))[None])
        return out

    vals = [np.asarray(v) for v in jax.jit(norms)(leaves, subs)]
    res = {}
    for n, v in zip(names, vals):
        if n.startswith("layers."):
            res.update({f"{n}.{i}": float(x) for i, x in enumerate(v)})
        else:
            res[n] = float(v[0])
    return res


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def _fq(x):
    """Per-tensor scaled float8 e4m3 rounding, straight-through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant, spec="...k,kn->...n"):
    if quant == "fp8":
        a, b = _fq(a), _fq(b)
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (T, heads, dh), rotate_half layout."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(p, x, m, *, quant=None, q_block=512):
    """One decoder layer over a whole sequence; x: (T, d) float32 with T a
    multiple of ``q_block``; p: this layer's weights (any float dtype)."""
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    t = x.shape[0]
    h, kv, dh = m["h"], m["kv"], m["dh"]
    y = _rms(x, p["ln1"], m["eps"])
    pos = jnp.arange(t)
    q = _rope(_mm(y, p["wq"], quant).reshape(t, h, dh), pos, m["theta"])
    k = _rope(_mm(y, p["wk"], quant).reshape(t, kv, dh), pos, m["theta"])
    v = _mm(y, p["wv"], quant).reshape(t, kv, dh)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    qb = min(q_block, t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)       # (qb, h, dh)
        s = _mm(qi, k, quant, "qhd,khd->hqk") * dh ** -0.5
        causal = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return _mm(a, v, quant, "hqk,khd->qhd")

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * dh)
    x = x + _mm(o, p["wo"], quant)
    y = _rms(x, p["ln2"], m["eps"])
    g = _mm(y, p["wg"], quant)
    x = x + _mm(jax.nn.silu(g) * _mm(y, p["wu"], quant), p["wd"], quant)
    return x


def _head_w(w):
    return w["head"] if "head" in w else w["embed"].T


def _logits(w, x, m, quant):
    y = _rms(x, w["norm"].astype(jnp.float32), m["eps"])
    return _mm(y, _head_w(w).astype(jnp.float32), quant)


def _pad_to(n: int, q: int) -> int:
    return -(-n // q) * q


@functools.lru_cache(maxsize=None)
def _serve_fns(conf_key, quant, q_block):
    m = dims(dict(conf_key))
    embed = jax.jit(lambda table, tok: table[tok].astype(jnp.float32))
    lay = jax.jit(lambda p, x: layer(p, x, m, quant=quant, q_block=q_block))
    head = jax.jit(lambda w, x, rows: _logits(w, x[rows], m, quant))
    return embed, lay, head


def logits_at(conf: dict, w: dict, tokens, rows, *, quant=None,
              q_block: int = 512, pad: int = 1024):
    """Reference logits (float32, on the device) at ``rows`` of one
    sequence, run layer by layer so that only one layer's weights are
    widened to float32 at a time.  The sequence is right-padded to a
    multiple of ``pad``: causal attention keeps padding out of every real
    row, and few padded lengths mean few compiles."""
    with jax.default_matmul_precision("highest"):
        embed, lay, head = _serve_fns(_conf_key(conf), quant, q_block)
        tokens = np.asarray(tokens, np.int32)
        t = _pad_to(len(tokens), max(pad, q_block))
        tok = np.zeros(t, np.int32)
        tok[:len(tokens)] = tokens
        x = embed(w["embed"], jnp.asarray(tok))
        for i in range(dims(conf)["layers"]):
            x = lay(jax.tree.map(lambda a: a[i], w["layers"]), x)
        return head(w, x, jnp.asarray(np.asarray(rows, np.int32)))


# --------------------------------------------------------------------------
# training: loss, gradients and AdamW
# --------------------------------------------------------------------------

def _seq_loss(w, tokens, labels, m, quant):
    x = w["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (layer(p, x, m, quant=quant), None))
    x, _ = jax.lax.scan(body, x, w["layers"])
    logits = _logits(w, x, m, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - ll)


def _conf_key(conf: dict):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _loss_grads_fn(conf_key, quant):
    m = dims(dict(conf_key))

    def run(w32, tokens, labels):
        def one(acc, tl):
            l, g = jax.value_and_grad(_seq_loss)(w32, tl[0], tl[1], m, quant)
            return jax.tree.map(jnp.add, acc, (l, g)), None

        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w32))
        (l, g), _ = jax.lax.scan(one, zero, (tokens, labels))
        n = tokens.size
        return l / n, jax.tree.map(lambda x: x / n, g)

    return jax.jit(run)


def loss_and_grads(conf: dict, w32: dict, tokens, labels, *, quant=None):
    """Mean token cross-entropy over a (B, T) batch and its gradient,
    a sequence at a time (float32, ``highest``)."""
    with jax.default_matmul_precision("highest"):
        return _loss_grads_fn(_conf_key(conf), quant)(
            w32, jnp.asarray(tokens), jnp.asarray(labels))


# The learning-rate schedule the program's train step applies (linear
# warm-up over 1,000 steps, then a cosine to a tenth at step 100,000): a
# fixed part of the program, not of the mix, held here as the reference's
# own copy.
SCHEDULE = {"warmup": 1000, "total": 100_000, "min_ratio": 0.1}


def lr_scale(step: int, sched: dict = SCHEDULE) -> float:
    """Linear warm-up, then cosine to ``min_ratio`` (step counts from 0)."""
    warm = min(step / max(sched["warmup"], 1), 1.0)
    t = min(max((step - sched["warmup"])
                / max(sched["total"] - sched["warmup"], 1), 0.0), 1.0)
    r = sched["min_ratio"]
    return warm * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * t)))


@functools.lru_cache(maxsize=None)
def _adamw_fn(opt_key):
    opt = dict(opt_key)

    def run(w32, m, v, g, lr, c1, c2):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        m = jax.tree.map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b,
                         m, g)
        v = jax.tree.map(
            lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * b * b, v, g)
        w32 = jax.tree.map(
            lambda w, a, b: w - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                  + opt["eps"])
                                      + opt["weight_decay"] * w),
            w32, m, v)
        return w32, m, v, g

    return jax.jit(run)


def adamw(w32, m, v, g, step: int, opt: dict):
    """One AdamW update with global-norm clipping; ``step`` counts from 0.
    Returns (weights, m, v, clipped gradient)."""
    lr = opt["lr"] * lr_scale(step)
    return _adamw_fn(_conf_key(opt))(
        w32, m, v, g, jnp.float32(lr),
        jnp.float32(1 - opt["b1"] ** (step + 1)),
        jnp.float32(1 - opt["b2"] ** (step + 1)))
