"""Mixture-of-Experts layer: top-k routing + capacity-based dispatch.

Expert FFNs execute as *batched* GEMMs over the expert dimension through the
batch-reduce building block (`batched_matmul`), so EP sharding of the expert
axis turns the dispatch scatter into an all-to-all under pjit.

Dispatch is GShard-style with capacity + token dropping (overflow tokens fall
into a discard slot); the combine re-gathers with the (renormalized) router
gates.  Aux outputs: load-balance loss and router z-loss.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import brgemm, dispatch
from repro.layers import mlp as mlp_layer
from repro.sharding.annotate import constrain


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    d_ff: int                 # per-expert hidden size
    n_experts: int
    top_k: int
    n_shared: int = 0         # DeepSeek-style always-on shared experts
    capacity_factor: float = 1.25
    activation: str = "silu"
    renormalize: bool = True
    # GShard-style grouped dispatch: one routing group per batch row, so
    # the dispatch buffers/scatters/expert-GEMM slots shard over the DP
    # axis instead of being redundantly computed on every DP shard.
    # (§Perf iteration 1: 16x expert-FLOP reduction on the 16x16 mesh.)
    grouped: bool = True


def init(key, cfg: MoECfg, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in, s_out = (1.0 / d) ** 0.5, (1.0 / f) ** 0.5
    params = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * s_in
                   ).astype(dtype),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out
                   ).astype(dtype),
    }
    if cfg.n_shared:
        params["shared"] = mlp_layer.init(
            ks[4], d, f * cfg.n_shared, gated=True, dtype=dtype)
    return params


def capacity(cfg: MoECfg, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    c = max(8, ((c + 3) // 4) * 4)
    # an expert can receive at most one assignment per token
    return min(c, ((n_tokens + 3) // 4) * 4)


def _shmap_over_dp(fn, g_: int):
    """Run fn shard_map'ed over the dp axes of the installed mesh (first
    arg dims sharded on dp); identity wrapper when no mesh is active."""
    from repro.sharding.annotate import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return fn
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if not dp or size <= 1 or g_ % size != 0:
        return fn
    from jax.sharding import PartitionSpec as P

    spec = P(dp)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)


def _route(params, xg, cfg: MoECfg, cap: int, backend):
    """Shared routing math. xg: (G, N, D) -> dispatch indices + gates.

    Capacity is enforced per group; with one group per batch row the
    position cumsum, scatter and combine all stay local to a DP shard.
    """
    g_, n, d = xg.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = brgemm.matmul(
        xg, params["router"], out_dtype=jnp.float32, backend=backend)
    probs = jax.nn.softmax(logits, axis=-1)            # (G, N, E)
    gate_vals, ids = jax.lax.top_k(probs, k)           # (G, N, k)
    if cfg.renormalize:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    flat_ids = ids.reshape(g_, n * k)
    onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # (G, N*k, E)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap)               # overflow -> discard
    return logits, probs, gate_vals, flat_ids, keep, safe_pos


def apply(params, x, cfg: MoECfg, *, backend: str | None = None):
    """x: (B, T, D) -> (y, aux). Routed experts + optional shared expert."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if cfg.grouped and t > 1:
        g_, n = b, t                                   # group = batch row
        xg = x
    else:
        # decode (t == 1): per-row groups would pad capacity ~e/k-fold;
        # a single global group keeps the buffer at ~n_tokens (§Perf 1d)
        g_, n = 1, b * t
        xg = x.reshape(1, b * t, d)
    cap = capacity(cfg, n)

    logits, probs, gate_vals, flat_ids, keep, safe_pos = _route(
        params, xg, cfg, cap, backend)
    xg = constrain(xg, "activation")

    # Dispatch scatter, *local per dp shard*.  GSPMD cannot shard a scatter
    # whose leading dim is addressed by explicit index arrays (it gathered
    # 240 GB/dev of activations — §Perf iteration 1c), so the scatter runs
    # under shard_map over the dp axes; the subsequent constrain to
    # (dp, model-on-E) is the canonical MoE dispatch all-to-all.
    x_rep = jnp.repeat(xg, k, axis=1)                  # (G, N*k, D)
    slot = flat_ids * (cap + 1) + safe_pos             # (G, N*k)

    def _local_scatter(xr, sl):
        gi = jnp.broadcast_to(jnp.arange(xr.shape[0])[:, None], sl.shape)
        b_ = jnp.zeros((xr.shape[0], e * (cap + 1), d), xr.dtype)
        return b_.at[gi, sl].set(xr)

    def _local_gather(of, sl):
        gi = jnp.broadcast_to(jnp.arange(of.shape[0])[:, None], sl.shape)
        return of[gi, sl]

    buf = _shmap_over_dp(_local_scatter, g_)(x_rep, slot)
    buf = constrain(buf.reshape(g_, e, cap + 1, d), "moe_dispatch")
    expert_in = buf[:, :, :cap]                        # (G, E, cap, D)

    # expert FFN as batched GEMMs over (G, E).  Keeping the 4-D form (no
    # transpose/reshape across the dp-sharded group dim!) lets GSPMD keep
    # groups on dp and experts on model with no re-layout all-gathers
    # (§Perf iteration 1b).  On the Pallas path this is vmap-over-groups of
    # the batched brgemm; the XLA path writes the same contraction directly.
    def expert_gemm(lhs, w, activation="none"):
        if dispatch.resolve("batched_matmul", backend) == "xla":
            out = jnp.einsum("gecd,edf->gecf", lhs, w,
                             preferred_element_type=jnp.float32)
            from repro.core import fusion
            return fusion.apply(activation, out).astype(lhs.dtype)
        return jax.vmap(
            lambda l: brgemm.batched_matmul(
                l, w, activation=activation, backend=backend))(lhs)

    gt = expert_gemm(expert_in, params["w_gate"], cfg.activation)
    u = expert_gemm(expert_in, params["w_up"])
    out = expert_gemm(constrain(gt * u, "moe_dispatch"), params["w_down"])

    # combine all-to-all: bring expert outputs back to dp-local layout so
    # the gather below never crosses the model axis
    out_pad = jnp.pad(out, ((0, 0), (0, 0), (0, 1), (0, 0)))
    out_flat = constrain(out_pad.reshape(g_, e * (cap + 1), d),
                         "activation")
    y_tok = _shmap_over_dp(_local_gather, g_)(out_flat, slot)  # (G, N*k, D)
    w = (gate_vals.reshape(g_, n * k) * keep).astype(x.dtype)
    y = (y_tok * w[..., None]).reshape(g_, n, k, d).sum(axis=2)

    if cfg.n_shared:
        y = y + mlp_layer.apply(params["shared"], xg,
                                activation=cfg.activation, backend=backend)

    # aux losses (GShard load-balance + z-loss)
    me = probs.reshape(-1, e).mean(axis=0)             # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[flat_ids.reshape(-1)].add(
        1.0) / (g_ * n * k)
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
        "dropped_fraction": 1.0 - keep.mean(),
    }
    return y.reshape(b, t, d), aux
