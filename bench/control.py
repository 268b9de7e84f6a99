"""Read a cell's compared numbers beside its control's, on several seeds.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 40

One process runs the cell once per seed, as ``bench/run.py`` does, and
after each run reads the control too: for a serving cell the program's
own int8 GEMM path over the same prompts and served tokens, for a
training cell the reference in float8, the reference on half of each
batch (the half-batch fault) and the reference computing every step
with its first weights (the stale-weights fault).  Each goes through the
same comparison, with the cell's own limits, as the program's run, and
should come out not correct.  Each seed prints one line ``CONTROL
{json}``.  The benchmark's own runs never read the control; these
readings are what the limits in ``bench/cells/<workload>.json`` were set
from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = bench_run.context(args.workload, seed, args.seconds, False,
                                control=True)
        ctx.t_process = time.perf_counter()
        res = bench_run.execute(ctx)
        print("CONTROL " + json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "checks": res["checks"],
            "control": res["control"], "e2e": res["e2e"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
