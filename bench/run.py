"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything the run needs is found by name from ``BENCHMARK.json``: the
cell's configuration file, its traffic mix (``bench/traffic/<traffic>
.json``), the limits of its checks (``bench/cells/<workload>.json``) and,
with ``--trace 1``, one reader per per-layer metric
(``bench/metrics/<name>.py``, else ``bench/metrics/<prefix>.py`` for a
name ``<prefix>.<part>``).  The traffic file's ``driver`` names the
module under ``bench/harness`` that drives the program.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown`` of device time and idle gaps, and last
``checks``: each number compared with its limit.  The same numbers end
standard error.  Without an accelerator, or with fewer chips than the
cell asks for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import common  # noqa: E402


def spec(root: str = ROOT) -> dict:
    return common.load_json(os.path.join(root, "BENCHMARK.json"))


def context(workload: str, seed: int, seconds: float, trace: bool, *,
            require_chip: bool = True, control: bool = False,
            root: str = ROOT) -> common.Context:
    b = spec(root)
    cell = next((w for w in b["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{[w['name'] for w in b['workloads']]}")
    conf_entry = next(c for c in b["configs"] if c["name"] == cell["config"])
    return common.Context(
        workload=workload,
        conf=common.load_json(os.path.join(root, conf_entry["file"])),
        traffic=common.load_json(os.path.join(
            BENCH, "traffic", f"{cell['traffic']}.json")),
        cell=common.load_json(os.path.join(BENCH, "cells",
                                           f"{workload}.json")),
        seed=seed, seconds=seconds, trace=trace, chips=cell["chips"],
        t_process=T_PROCESS, require_chip=require_chip, control=control)


def execute(ctx: common.Context) -> dict:
    driver = importlib.import_module(f"harness.{ctx.traffic['driver']}")
    return driver.run(ctx)


def _reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return common.load_file_module(path, f"bench_metric_{stem}")
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def cell_metrics(b: dict, workload: str, e2e: dict, observed: dict,
                 trace: bool) -> dict:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    metrics, each ``{"value", "unit"}``; a reader that finds nothing is
    left out."""
    reported = {m["name"] for m in b["end_to_end"]
                if workload in m.get("workloads", [workload])}
    out = {}
    if not trace:
        for m in b["end_to_end"]:
            if m["name"] in reported:
                out[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        return out
    for m in b["per_layer"]:
        wanted = (workload in m["workloads"] if "workloads" in m
                  else m["moves"] in reported)
        if not wanted:
            continue
        value = _reader(m["name"]).read(observed)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(b: dict, ctx: common.Context, res: dict) -> dict:
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": cell_metrics(b, ctx.workload, res["e2e"],
                                    res["observed"], ctx.trace),
            "device": dict(res["device"])}
    tr = res["observed"].get("trace")
    if ctx.trace and tr is not None:
        line["device"]["busy_s"] = tr["busy_s"]
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx = context(args.workload, args.seed, args.seconds, bool(args.trace))
    res = execute(ctx)
    line = result_line(spec(), ctx, res)
    for name, row in line["checks"].items():
        common.stderr(f"check {name}: {row['value']!r} (limit "
                      f"{row['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
