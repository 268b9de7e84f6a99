"""What every cell's driver shares: files, device, caches, compiles, checks."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file_module(path: str, name: str):
    """Import a Python file of the benchmark by its path (the names of
    per-metric readers hold dots, so they cannot be imported by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(conf: dict):
    """The plain reference of the configuration's ``model_type``."""
    return load_file_module(
        os.path.join(BENCH, "models", f"{conf['model_type']}.py"),
        f"bench_model_{conf['model_type']}")


def program_cfg(conf: dict):
    """The program's ``ArchCfg`` for a configuration file: the program's
    own entry for ``program.arch`` with ``program.replace`` applied."""
    from repro import configs

    prog = conf["program"]
    return dataclasses.replace(configs.get(prog["arch"]),
                               **prog.get("replace", {}))


@dataclasses.dataclass
class Context:
    """One run of one cell."""
    workload: str
    conf: dict          # configuration file
    traffic: dict       # traffic mix file
    cell: dict          # the cell's limits file
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_process: float    # perf_counter when the process started
    require_chip: bool = True
    control: bool = False   # also read the control and faults (control.py)


class NoChip(SystemExit):
    pass


def devices(ctx: Context):
    """The devices the cell runs on: refuses a run without an accelerator
    or with fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if ctx.require_chip:
        if devs[0].platform == "cpu":
            raise NoChip(f"no accelerator: JAX platform is "
                         f"{devs[0].platform!r}")
        if len(devs) < ctx.chips:
            raise NoChip(f"the cell needs {ctx.chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:ctx.chips]


def device_info(devs) -> dict:
    d = devs[0]
    peak_mem = 0
    for dv in devs:
        stats = dv.memory_stats() or {}
        peak_mem = max(peak_mem, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak_mem}


def enable_caches() -> str:
    """JAX's persistent compilation cache (the program's own switch: the
    directory JAX_COMPILATION_CACHE_DIR names, else a fixed one in the
    checkout), holding every program however quick its compile, so that
    only a cell's first run in a checkout compiles."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts jit traces and backend compiles while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100); ``inf`` entries are
    requests that missed, and sort last."""
    v = sorted(values)
    if not v:
        return math.inf
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


class Checks:
    """The numbers compared, each beside its limit."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = {}

    def add(self, name: str, value: float) -> None:
        self.rows[name] = {"value": float(value),
                           "limit": float(self.limits[name])}

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(r["value"]) and r["value"] <= r["limit"]
            for r in self.rows.values())


def log(*args) -> None:
    print(*args, flush=True)


def stderr(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
