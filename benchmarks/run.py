"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Mapping to the paper:
  fig1_*   Figure 1  convolution implementation strategies
  sec2_*   Section 2 batch-reduce vs batched vs looped GEMM
  fig6_*   Figure 6  LSTM cell fwd / bwd+upd
  fig7/8_* Figures 7-8 + Table 2: ResNet-50 convolutions
  fig9_*   Figure 9  fully-connected layers
  fig10_*  Figure 10 distributed-scaling proxy (collective footprint)
  tune_*   heuristic vs measured-autotune tiles (``--compare-policies``)
  serve_*  continuous-batching vs static-batching serving throughput
  quant_*  bf16 vs int8 quantized GEMM + int8-decode serving throughput
  obs_*    roofline accounting (achieved GFLOP/s vs arithmetic
           intensity per op) + traced autotune span counts

``--json out.json`` additionally persists every record (plus platform /
dispatch metadata) so the BENCH_*.json perf trajectory can be diffed
across commits.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default=None, choices=("xla", "pallas"),
                    help="force a dispatch backend for every benchmark "
                         "(overridden by per-benchmark explicit choices)")
    ap.add_argument("--blocks-policy", default=None,
                    choices=("heuristic", "autotune"),
                    help="block-selection policy for every benchmark")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write all records as JSON to this path")
    ap.add_argument("--compare-policies", action="store_true",
                    help="run the heuristic-vs-autotune tile comparison "
                         "(pays a measured search per op/shape)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="with --compare-policies: also compare global-"
                         "shape vs per-shard (local-shape) tuning under a "
                         "device-free mesh of this shape (e.g. 2x4)")
    ap.add_argument("--only", default=None, metavar="SUBSTR[,SUBSTR...]",
                    help="run only benchmark modules whose name contains "
                         "one of these comma-separated substrings "
                         "(e.g. --only attention, --only quant,serving)")
    args = ap.parse_args()

    import jax

    import repro
    from repro.runtime.compile_cache import enable_compile_cache
    from benchmarks import (bench_attention, bench_autotune, bench_brgemm,
                            bench_conv_resnet50, bench_conv_strategies,
                            bench_distributed_proxy, bench_fc, bench_lstm,
                            bench_obs, bench_quant, bench_serving, common)

    mods = [bench_brgemm, bench_conv_strategies, bench_lstm, bench_fc,
            bench_conv_resnet50, bench_attention, bench_distributed_proxy,
            bench_serving, bench_quant, bench_obs]
    if args.compare_policies:
        mods.append(bench_autotune)
    elif args.mesh:
        ap.error("--mesh requires --compare-policies")
    if args.only:
        wanted = [s for s in args.only.split(",") if s]
        mods = [m for m in mods
                if any(s in m.__name__ for s in wanted)]
        if not mods:
            ap.error(f"--only {args.only!r} matches no benchmark module")
        if args.mesh and bench_autotune not in mods:
            ap.error(f"--mesh runs inside bench_autotune, which --only "
                     f"{args.only!r} filtered out (use --only autotune)")

    enable_compile_cache()
    print("name,us_per_call,derived")
    ok = True
    # use(backend=None, ...) leaves every field unset — a no-op context.
    with repro.use(backend=args.backend, blocks_policy=args.blocks_policy):
        for mod in mods:
            try:
                if mod is bench_autotune and args.mesh:
                    mod.run(mesh=args.mesh)
                else:
                    mod.run()
            except Exception:
                ok = False
                print(f"# ERROR in {mod.__name__}", file=sys.stderr)
                traceback.print_exc()

    if args.json:
        payload = {
            "platform": jax.default_backend(),
            "backend": args.backend,
            "blocks_policy": args.blocks_policy,
            "ok": ok,
            "records": common.RECORDS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records to {args.json}",
              file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
