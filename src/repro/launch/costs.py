"""Exact HLO cost accounting via layer-count extrapolation.

XLA's cost analysis counts a ``lax.scan`` (while-loop) body ONCE regardless
of trip count, so the rolled-scan dry-run under-reports FLOPs/bytes/
collective bytes by ~n_layers.  Full unrolling of 60-90 layer models at 512
devices is compile-prohibitive.  Instead: lower each cell at two (or three)
SMALL layer counts with the stacks UNROLLED — per-layer HLO is identical
across layers, so costs are exactly affine in the layer/group count — and
extrapolate to the real depth:

    cost(L) = base + per_layer * L
    per_layer = (cost(L2) - cost(L1)) / (L2 - L1)

Per block type the sample points respect the arch's grouping constraints
(xlstm groups of `slstm_every`, rglru (rec,rec,attn) groups + tail, enc/dec
stacks separately).  Writes ``cost_<arch>_<shape>_<mesh>.json`` artifacts
consumed by launch/roofline.py.

Usage:
  python -m repro.launch.costs --arch grok-1-314b --shape train_4k
  python -m repro.launch.costs --all
"""
import argparse
import json
import os
import subprocess
import sys

from repro import configs
from repro.configs.shapes import SHAPES, applicable
from repro.launch.dryrun import ART, force_host_device_count, lower_cell

_FIELDS = ("flops_per_device", "bytes_accessed_per_device")


def _extract(rec):
    out = {f: rec[f] or 0.0 for f in _FIELDS}
    coll = rec["collective_bytes_per_device"]
    for k, v in coll.items():
        if not k.startswith("_count_"):
            out[f"coll_{k}"] = v
    return out


def _combine(base, slope_pairs):
    """base: costs dict; slope_pairs: list of (per_unit_costs, extra_units)."""
    out = dict(base)
    for per, n in slope_pairs:
        for k in set(out) | set(per):
            out[k] = out.get(k, 0.0) + per.get(k, 0.0) * n
    return out


def _diff(a, b, denom=1.0):
    return {k: (a.get(k, 0.0) - b.get(k, 0.0)) / denom
            for k in set(a) | set(b)}


def cost_cell(arch: str, shape: str, *, multi_pod: bool = False,
              extra_overrides: dict | None = None, tag: str = ""):
    cfg = configs.get(arch)
    ok, why = applicable(cfg, SHAPES[shape])
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skipped",
                "reason": why}
    eo = extra_overrides or {}
    if cfg.block == "xlstm" and SHAPES[shape].seq_len > 8192:
        # unrolling 32k/256 = 128 chunk steps per layer is compile-
        # prohibitive; use a 2048 chunk (16 steps).  CAVEAT (EXPERIMENTS.md
        # §Method): overstates the intra-chunk quadratic term ~8x vs the
        # production chunk=256 — a conservative upper bound.
        eo.setdefault("mlstm_chunk", 2048)

    def lower(**ov):
        rec = lower_cell(arch, shape, multi_pod=multi_pod,
                         cfg_overrides={"scan_unroll": True, **eo, **ov})
        assert rec["status"] == "ok", rec
        return _extract(rec), rec

    if cfg.block in ("dense", "moe"):
        c2, _ = lower(n_layers=2)
        c4, rec = lower(n_layers=4)
        per = _diff(c4, c2, 2)
        full = _combine(c2, [(per, cfg.n_layers - 2)])
    elif cfg.block == "mla_moe":
        nd = cfg.n_dense_layers
        c1, _ = lower(n_layers=nd + 1)
        c2, rec = lower(n_layers=nd + 2)
        per = _diff(c2, c1, 1)
        full = _combine(c1, [(per, cfg.n_layers - nd - 1)])
    elif cfg.block == "xlstm":
        se = cfg.slstm_every
        c1, _ = lower(n_layers=se)          # 1 group
        c2, rec = lower(n_layers=2 * se)    # 2 groups
        per = _diff(c2, c1, 1)
        full = _combine(c1, [(per, cfg.n_layers // se - 1)])
    elif cfg.block == "rglru_hybrid":
        np_ = len(cfg.pattern)
        g_real = cfg.n_layers // np_
        tail = cfg.n_layers - g_real * np_
        c1, _ = lower(n_layers=np_)         # 1 group, no tail
        c2, rec = lower(n_layers=2 * np_)   # 2 groups
        per_group = _diff(c2, c1, 1)
        parts = [(per_group, g_real - 1)]
        if tail:
            c_tail, _ = lower(n_layers=np_ + tail)  # 1 group + tail
            parts.append((_diff(c_tail, c1, 1), 1.0))
        full = _combine(c1, parts)
    elif cfg.block == "encdec":
        c22, _ = lower(n_layers=2, n_enc_layers=2)
        c42, _ = lower(n_layers=2, n_enc_layers=4)
        c24, rec = lower(n_layers=4, n_enc_layers=2)
        per_enc = _diff(c42, c22, 2)
        per_dec = _diff(c24, c22, 2)
        full = _combine(c22, [(per_enc, cfg.n_enc_layers - 2),
                              (per_dec, cfg.n_layers - 2)])
    else:
        raise ValueError(cfg.block)

    total_p, active_p = cfg.param_counts()
    out = {
        "arch": arch, "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "tag": tag,
        "n_devices": rec["n_devices"],
        "method": "unrolled-2pt-extrapolation",
        "flops_per_device": full["flops_per_device"],
        "bytes_accessed_per_device": full["bytes_accessed_per_device"],
        "collective_bytes_per_device": {
            **{k[5:]: v for k, v in full.items() if k.startswith("coll_")},
            "total": full.get("coll_total", 0.0),
        },
        "state_bytes_per_device": rec["state_bytes_per_device"],
        "params_total": total_p, "params_active": active_p,
    }
    return out


def _out_path(arch, shape, multi_pod, tag=""):
    mesh = "multi" if multi_pod else "single"
    suffix = f"_{tag}" if tag else ""
    return ART / f"cost_{arch}_{shape}_{mesh}{suffix}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for A/B runs")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    help="ArchCfg override, e.g. --set attention_impl=chunked")
    args = ap.parse_args()
    force_host_device_count()
    ART.mkdir(parents=True, exist_ok=True)

    overrides = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        elif v.isdigit():
            v = int(v)
        overrides[k] = v

    if args.all:
        n_err = 0
        for arch in configs.ARCH_NAMES:
            for shape in SHAPES:
                out = _out_path(arch, shape, args.multi_pod)
                if out.exists() and not args.force:
                    continue
                cmd = [sys.executable, "-m", "repro.launch.costs",
                       "--arch", arch, "--shape", shape]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                print(f"[costs] {arch} x {shape}", flush=True)
                r = subprocess.run(
                    cmd, capture_output=True, text=True,
                    env={**os.environ, "JAX_PLATFORMS": "cpu"})
                if r.returncode != 0:
                    n_err += 1
                    print(f"  ERROR: {r.stderr[-400:]}", flush=True)
                else:
                    print("  ok", flush=True)
        sys.exit(1 if n_err else 0)

    rec = cost_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                    extra_overrides=overrides, tag=args.tag)
    _out_path(args.arch, args.shape, args.multi_pod, args.tag).write_text(
        json.dumps(rec, indent=1))
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
