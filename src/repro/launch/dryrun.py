"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the shape's entry point (train_step / prefill /
decode_step) against abstract inputs (ShapeDtypeStruct — no allocation) with
production shardings, compiles it, and records:

  * memory analysis (bytes per device; proves it fits),
  * cost analysis (FLOPs / bytes for the roofline),
  * collective bytes parsed from the partitioned HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute), per collective kind,
  * the per-shard block choices mesh-aware dispatch resolves for the
    cell's hot GEMM/attention problems, next to the global-shape picks
    (``resolved_blocks``: tiles tuned for the 16-row shard a device runs,
    not the 8192-row global problem).

The host-device-count XLA flag is set from :func:`main` (or the
``REPRO_DRYRUN_DEVICES`` env var), **never at import time**, so importing
this module for tests does not clobber ``XLA_FLAGS`` for the whole
process.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all            # every cell, subprocesses
  python -m repro.launch.dryrun --all --multi-pod
  python -m repro.launch.dryrun --blocks-smoke --devices 8   # CI smoke
"""
import argparse
import json
import os
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, applicable
from repro.core import dispatch
from repro.core.blocking import blocks_to_dict
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import api
from repro.sharding import local as shlocal
from repro.sharding import rules
from repro.sharding.annotate import use_rules
from repro.train import optimizer as opt
from repro.train import train_step as ts

ART = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"

DEVICES_ENV = "REPRO_DRYRUN_DEVICES"
DEFAULT_HOST_DEVICES = 512


def force_host_device_count(n: int | None = None) -> None:
    """Arrange for ``n`` fake host devices (default 512, or
    ``REPRO_DRYRUN_DEVICES``).  Must run before jax initializes its
    backends; a pre-existing device-count flag in ``XLA_FLAGS`` wins."""
    n = n or int(os.environ.get(DEVICES_ENV, DEFAULT_HOST_DEVICES))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the partitioned HLO."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        kind = m.group(1)
        lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split(
            m.group(1))[0]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
        out["_count_" + kind] = out.get("_count_" + kind, 0) + 1
    out["total"] = sum(v for k, v in out.items()
                       if not k.startswith("_count_") and k != "total")
    return out


def cell_problems(cfg, shape):
    """The cell's hot canonical tuning problems, with the axis assignment
    the sharding rules induce on each triple.

    One row per projection family: column-parallel GEMMs (qkv / mlp-up)
    shard rows on the DP axes and the out dim on the model axis;
    row-parallel GEMMs (attn-out / mlp-down) shard the *contraction* dim
    on the model axis instead; attention's triple stays head-sharded
    (local == global).  Returns ``(name, op, (m, n, k), axis_spec)``.
    """
    dp = ("pod", "data")  # shlocal.shard_count skips axes absent from mesh
    model = "model" if cfg.tp else None
    decode = shape.kind == "decode"
    rows = shape.global_batch * (1 if decode else shape.seq_len)
    d, dh = cfg.d_model, cfg.dh
    n_q = cfg.n_heads * dh
    probs = [
        ("attn_qkv", "matmul", (rows, n_q, d), (dp, model, None)),
        ("attn_out", "matmul", (rows, d, n_q), (dp, None, model)),
    ]
    if cfg.d_ff:
        probs += [
            ("mlp_up", "matmul", (rows, cfg.d_ff, d), (dp, model, None)),
            ("mlp_down", "matmul", (rows, d, cfg.d_ff), (dp, None, model)),
        ]
    if cfg.moe_d_ff:
        probs.append(("moe_up", "brgemm",
                      (rows, cfg.moe_d_ff, d), (dp, model, None)))
    tq = 1 if decode else shape.seq_len
    probs.append(("attention", "flash_attention",
                  (tq, shape.seq_len, dh), (None, None, None)))
    return probs


def block_choices(cfg, shape, mesh, dtype=None):
    """Per-shard vs global-shape block resolution for one cell.

    For each hot problem this resolves the tile twice — once against the
    global shape (meshless context) and once through mesh-aware dispatch
    (``use(mesh=..., axis_specs=...)``, which localizes the triple before
    tuning) — and records both with the local problem, so the dry-run
    artifact shows exactly where global-shape tuning would have picked
    tiles for a problem no device runs.  Heuristic policy: cheap enough to
    run per cell; a persisted ``REPRO_TUNING_CACHE`` upgrade is the
    measured follow-up.
    """
    dtype = jnp.dtype(dtype or cfg.dtype)
    out = []
    for name, op, (m, n, k), spec in cell_problems(cfg, shape):
        blk_global = dispatch.resolve_blocks(op, m, n, k, dtype,
                                             backend="pallas")
        with dispatch.use(mesh=mesh, axis_specs={op: spec}):
            local = shlocal.local_problem(op, m, n, k, mesh,
                                          axis_specs={op: spec})
            blk_local = dispatch.resolve_blocks(op, m, n, k, dtype,
                                                backend="pallas")
        out.append({
            "name": name, "op": op, "dtype": dtype.name,
            "global": [m, n, k], "local": list(local),
            "blocks_global": blocks_to_dict(blk_global),
            "blocks_local": blocks_to_dict(blk_local),
            "differs": blk_local != blk_global,
        })
    return out


def _bytes_per_device(tree_specs, shardings, mesh) -> float:
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(tree_specs),
                        jax.tree.leaves(shardings, is_leaf=lambda x:
                                        isinstance(x, NamedSharding))):
        n = 1
        for d in leaf.shape:
            n *= d
        shards = 1
        for ax in sh.spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shards *= mesh.shape[a]
        total += n * jnp.dtype(leaf.dtype).itemsize / shards
    return total


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               moment_dtype: str | None = None, extra_tag: str = "",
               cfg_overrides: dict | None = None):
    import dataclasses as _dc
    cfg = configs.get(arch_name)
    if cfg_overrides:
        # Cost-accounting mode (launch/costs.py): small unrolled stacks so
        # XLA cost analysis counts every layer (while-loop bodies are
        # otherwise counted once); full-model costs are extrapolated from
        # two layer counts.  The compile-proof sweep uses rolled scans.
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    # bf16 moments for the largest models keep optimizer HBM in budget
    if moment_dtype is None:
        total, _ = cfg.param_counts()
        moment_dtype = "bfloat16" if total > 1e11 else "float32"
    ocfg = opt.AdamWCfg(moment_dtype=moment_dtype)

    t0 = time.time()
    with mesh, use_rules(rules.activation_rules(mesh), mesh):
        if shape.kind == "train":
            state = ts.abstract_state(cfg, ocfg)
            batch = api.input_specs(cfg, shape)
            state_sh = rules.param_shardings(state, mesh, fsdp=cfg.fsdp, tp=cfg.tp)
            batch_sh = rules.batch_shardings(batch, mesh)
            step = ts.make_train_step(cfg, ocfg)
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh)).lower(state, batch)
            arg_bytes = _bytes_per_device(state, state_sh, mesh)
        elif shape.kind == "prefill":
            params = api.params_specs(None, cfg)
            batch = api.input_specs(cfg, shape)
            cache = api.cache_specs(cfg, shape)
            p_sh = rules.param_shardings(params, mesh, fsdp=cfg.fsdp, tp=cfg.tp)
            b_sh = rules.batch_shardings(batch, mesh)
            c_sh = rules.cache_shardings(cache, mesh)

            def prefill_fn(params, batch, cache):
                return api.prefill(params, batch, cfg, cache)

            lowered = jax.jit(
                prefill_fn, in_shardings=(p_sh, b_sh, c_sh)).lower(
                    params, batch, cache)
            arg_bytes = (_bytes_per_device(params, p_sh, mesh)
                         + _bytes_per_device(cache, c_sh, mesh))
        else:  # decode
            params = api.params_specs(None, cfg)
            batch = api.input_specs(cfg, shape)
            cache = api.cache_specs(cfg, shape)
            p_sh = rules.param_shardings(params, mesh, fsdp=cfg.fsdp, tp=cfg.tp)
            b_sh = rules.batch_shardings(batch, mesh)
            c_sh = rules.cache_shardings(cache, mesh)
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            pos_sh = NamedSharding(mesh, P())

            def decode_fn(params, tokens, cache, pos):
                return api.decode_step(params, tokens, cfg, cache, pos)

            lowered = jax.jit(
                decode_fn,
                in_shardings=(p_sh, b_sh["tokens"], c_sh, pos_sh)).lower(
                    params, batch["tokens"], cache, pos_spec)
            arg_bytes = (_bytes_per_device(params, p_sh, mesh)
                         + _bytes_per_device(cache, c_sh, mesh))

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not support it
        mem_info = {"error": str(e)}

    coll = collective_bytes(compiled.as_text())
    total_p, active_p = cfg.param_counts()
    rec = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_axes": {str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        "status": "ok", "tag": extra_tag,
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops"),
        "bytes_accessed_per_device": cost.get("bytes accessed"),
        "collective_bytes_per_device": coll,
        "state_bytes_per_device": arg_bytes,
        "params_total": total_p, "params_active": active_p,
        "moment_dtype": moment_dtype,
        # outside the `with mesh` block on purpose: the meshless baseline
        # resolution must not see a dispatch mesh context
        "resolved_blocks": block_choices(cfg, shape, mesh),
    }
    return rec


def _cell_path(arch, shape, multi_pod, tag=""):
    mesh = "multi" if multi_pod else "single"
    suffix = f"_{tag}" if tag else ""
    return ART / f"dryrun_{arch}_{shape}_{mesh}{suffix}.json"


def run_all(multi_pod: bool, force: bool = False):
    ART.mkdir(parents=True, exist_ok=True)
    results = []
    for arch in configs.ARCH_NAMES:
        for shape in SHAPES:
            out = _cell_path(arch, shape, multi_pod)
            if out.exists() and not force:
                results.append(json.loads(out.read_text()))
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if multi_pod:
                cmd.append("--multi-pod")
            print(f"[dryrun] {arch} x {shape} "
                  f"({'multi' if multi_pod else 'single'})", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            if r.returncode != 0:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if multi_pod else "single",
                       "status": "error",
                       "stderr": r.stderr[-4000:]}
                out.write_text(json.dumps(rec, indent=1))
                print(f"  ERROR: {r.stderr[-500:]}", flush=True)
                results.append(rec)
            else:
                results.append(json.loads(out.read_text()))
                print("  ok", flush=True)
    return results


def blocks_smoke(arch: str, shape_name: str) -> int:
    """CI smoke: one (arch x shape x host-mesh) cell through mesh-aware
    dispatch.  Prints the ``resolved_blocks`` record and fails unless at
    least one per-shard choice differs from the global-shape choice."""
    mesh = make_host_mesh()
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh_axes": {str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        "n_devices": mesh.size,
        "resolved_blocks": block_choices(cfg, shape, mesh),
    }
    print(json.dumps(rec, indent=1))
    n_diff = sum(r["differs"] for r in rec["resolved_blocks"])
    print(f"[dryrun-smoke] problems={len(rec['resolved_blocks'])} "
          f"per_shard_differs={n_diff}")
    return 0 if n_diff else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="forced host device count (default "
                         f"{DEFAULT_HOST_DEVICES}, or {DEVICES_ENV})")
    ap.add_argument("--blocks-smoke", action="store_true",
                    help="resolve one cell's blocks per-shard on a host "
                         "mesh and assert they differ from the global "
                         "choice (CI)")
    args = ap.parse_args()
    force_host_device_count(args.devices)

    if args.blocks_smoke:
        sys.exit(blocks_smoke(args.arch or "smollm-135m",
                              args.shape or "decode_32k"))

    if args.all:
        res = run_all(args.multi_pod, args.force)
        n_ok = sum(r["status"] == "ok" for r in res)
        n_skip = sum(r["status"] == "skipped" for r in res)
        n_err = sum(r["status"] == "error" for r in res)
        print(f"[dryrun] ok={n_ok} skipped={n_skip} error={n_err}")
        sys.exit(1 if n_err else 0)

    assert args.arch and args.shape
    rec = lower_cell(args.arch, args.shape, multi_pod=args.multi_pod)
    ART.mkdir(parents=True, exist_ok=True)
    out = _cell_path(args.arch, args.shape, args.multi_pod)
    out.write_text(json.dumps(rec, indent=1))
    print(json.dumps(
        {k: v for k, v in rec.items() if k != "stderr"}, indent=1))


if __name__ == "__main__":
    main()
