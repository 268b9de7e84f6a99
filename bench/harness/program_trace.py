"""From a profiler trace to device time by jitted program and named scope,
and idle device time by program span.

The program names its work in two ways that reach the trace.  Every op
of a jitted program carries the ``jax.named_scope`` path it was traced
under; on a TPU the trace keeps it as the ``tf_op`` stat of the op's
event metadata, which ``jax.profiler.ProfileData`` does not expose, so
:func:`op_paths` reads it from the serialized trace itself.  And while
the program's ``repro.obs`` tracer is on, each of its spans is also a
host annotation ``repro.<name>`` on the profiler's clock.

Device seconds go to (program, innermost listed scope), or to
``unscoped`` where an op sits under none of them; an op's program is
the ``XLA Modules`` event it runs in.  Transform wrappers are unwrapped
first: the backward of an attention op reads
``transpose(jvp(attention))/core/...``, a vmapped gather
``vmap(kv_gather)/...``.  Idle device time in the traced window is
charged as ``trace.reduce`` charges it to the harness's spans, piece by
piece to the innermost program span over it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

from harness import trace

SPAN_PREFIX = "repro."
UNSCOPED = "unscoped"
NO_SPAN = "no program span"
MODULES_LINE = "XLA Modules"
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"
# the model's and the serving stack's scopes; a nested one is named by
# its path under the scope that holds it
SCOPES = ("embed", "attention", "attention/core", "attention/kv_write",
          "mlp", "head", "loss", "kv_gather", "kv_scatter", "kv_insert",
          "optimizer")
_NESTED = {s.split("/")[1]: s.split("/")[0] for s in SCOPES if "/" in s}
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")
_MODULE = re.compile(r"(.*)\((\d+)\)$")   # "jit__decode(1807...)"


def unwrap(part: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    m = _WRAPPED.fullmatch(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.fullmatch(part)
    return part


def scope_of(path: str) -> str:
    """The innermost listed scope of an ``op_name`` path, or
    :data:`UNSCOPED`."""
    found = UNSCOPED
    for part in path.split("/"):
        part = unwrap(part)
        if part in SCOPES:
            found = part
        elif _NESTED.get(part) == found:
            found = f"{found}/{part}"
    return found


# ---- the serialized trace (XSpace protocol buffer), read in part -------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: int | None = None):
    """(field number, value) of one message: an int for a varint or fixed
    field, a (start, end) pair for a length-delimited one."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entry):
    return [v for f, v in _fields(buf, *entry) if f == 2]


def op_paths(raw: bytes) -> dict:
    """``{(program id, op name): op_name path}`` of every device plane's
    ops, from their event metadata (XPlane fields ``name`` 2,
    ``event_metadata`` 4, ``stat_metadata`` 5; XEventMetadata ``name`` 2,
    ``stats`` 5; XStat ``metadata_id`` 1, ``uint64_value`` 3,
    ``int64_value`` 4, ``str_value`` 5, ``ref_value`` 7)."""
    buf = memoryview(raw)
    out = {}
    for f, plane in _fields(buf):
        if f != 1:                                   # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 4:
                events += _map_values(buf, v)
            elif pf == 5:
                for value in _map_values(buf, v):
                    meta = dict(_fields(buf, *value))
                    stat_names[meta.get(1)] = _text(buf, meta.get(2, (0, 0)))
        if not name.startswith(trace.DEVICE_PLANE):
            continue
        for span in events:
            op, stats = "", {}
            for ef, ev in _fields(buf, *span):
                if ef == 2:
                    op = _text(buf, ev)
                elif ef == 5:
                    stat = dict(_fields(buf, *ev))
                    if 5 in stat:
                        val = _text(buf, stat[5])
                    elif 7 in stat:
                        val = stat_names.get(stat[7])
                    else:
                        val = stat.get(3, stat.get(4))
                    stats[stat_names.get(stat.get(1))] = val
            if stats.get(PATH_STAT):
                out[(stats.get(PROGRAM_STAT), op)] = (
                    stats[PATH_STAT].rstrip(":"))
    return out


def events_of(profile, paths: dict):
    """(device ops, host spans).  Device ops: ``{plane: [(name, start_ns,
    end_ns, program, op_name path)]}``, loops and calls left out for the
    ops they hold as in ``trace.events_of``; host spans: the program's
    spans and the traced window, ``[(name, start_ns, end_ns)]``."""
    dev, host = {}, []
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            for line in plane.lines:
                for e in line.events:
                    if (e.name.startswith(SPAN_PREFIX)
                            or e.name == trace.WINDOW):
                        host.append((e.name, e.start_ns, e.end_ns))
            continue
        lines = {line.name: line for line in plane.lines}
        modules = []
        for e in (lines[MODULES_LINE].events if MODULES_LINE in lines
                  else ()):
            m = _MODULE.match(e.name)
            modules.append((e.start_ns, e.end_ns,
                            m.group(1) if m else e.name,
                            int(m.group(2)) if m else None))
        modules.sort()
        starts = [m[0] for m in modules]
        ops = []
        for e in (lines[trace.OPS_LINE].events if trace.OPS_LINE in lines
                  else ()):
            if trace._CONTAINER.search(e.name):
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog, pid = (modules[i][2:] if i >= 0
                         and e.start_ns < modules[i][1] else ("", None))
            ops.append((e.name, e.start_ns, e.end_ns, prog,
                        paths.get((pid, e.name), "")))
        dev[plane.name] = ops
    return dev, host


def load(log_dir: str):
    """(device ops, host spans) of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(max(files, key=os.path.getmtime), "rb") as f:
        raw = f.read()
    return events_of(ProfileData.from_serialized_xspace(raw), op_paths(raw))


def reduce(dev: dict, host: list) -> dict:
    """Over the traced window: ``{"window_s", "busy_s", "scopes":
    {(program, scope): s}, "unscoped_kinds": {program: [[op kind, s]]},
    "idle": {span: s}}``.  Device seconds are summed over devices; busy
    and idle seconds averaged over the devices that ran any operation,
    as ``trace.reduce`` has busy time, so the idle seconds sum to window
    less busy."""
    windows = [(s, e) for n, s, e in host if n == trace.WINDOW]
    if not windows:
        raise ValueError(f"trace has no {trace.WINDOW!r} span")
    w0, w1 = windows[0]
    spans = sorted((s, e, n) for n, s, e in host if n != trace.WINDOW)
    starts = [s for s, _, _ in spans]
    scopes = collections.defaultdict(float)
    kinds = collections.defaultdict(lambda: collections.defaultdict(float))
    idle = collections.defaultdict(float)
    busy, n_dev = 0.0, 0
    for ops in dev.values():
        clipped = [(max(s, w0), min(e, w1), n, prog, path)
                   for n, s, e, prog, path in ops if e > w0 and s < w1]
        if not clipped:
            continue
        n_dev += 1
        for s, e, n, prog, path in clipped:
            scope = scope_of(path)
            scopes[(prog, scope)] += (e - s) * 1e-9
            if scope == UNSCOPED:
                kinds[prog][trace.kind(n)] += (e - s) * 1e-9
        merged = trace._union((s, e) for s, e, *_ in clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = collections.defaultdict(float)
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                trace._charge(spans, starts, g0, g1, gaps)
        for k, v in gaps.items():
            idle[NO_SPAN if k == "no bench span" else k] += v
    n_dev = max(n_dev, 1)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy / n_dev,
            "scopes": dict(scopes),
            "unscoped_kinds": {
                p: [[k, s] for k, s in sorted(row.items(),
                                              key=lambda kv: -kv[1])[:5]]
                for p, row in kinds.items()},
            "idle": {k: v / n_dev for k, v in idle.items()}}


def seconds_in(scopes: dict, program: str, names) -> float:
    """Device seconds of ``program`` under any of the scopes ``names``."""
    return sum(s for (p, sc), s in scopes.items()
               if p == program and sc in names)


def lines(red: dict) -> list[str]:
    """Log lines: each program's device seconds by scope, largest first,
    with its share under a listed scope and what its unscoped time is;
    then idle seconds by program span against the window's idle time."""
    by_prog = collections.defaultdict(dict)
    for (p, sc), s in red["scopes"].items():
        by_prog[p or "?"][sc] = s
    out = []
    for p, row in sorted(by_prog.items(),
                         key=lambda kv: -sum(kv[1].values())):
        total = sum(row.values())
        scoped = total - row.get(UNSCOPED, 0.0)
        cells = ", ".join(f"{sc} {s:.6f}" for sc, s in
                          sorted(row.items(), key=lambda kv: -kv[1]))
        out.append(f"device s by scope, {p}: {total:.6f} s, "
                   f"{100 * scoped / total:.2f}% scoped: {cells}")
        if red["unscoped_kinds"].get(p):
            out.append(f"  unscoped in {p} by op kind: " + ", ".join(
                f"{k} {s:.6f}" for k, s in red["unscoped_kinds"][p]))
    idle = red["idle"]
    cells = ", ".join(f"{n} {s:.6f}" for n, s in
                      sorted(idle.items(), key=lambda kv: -kv[1]))
    out.append(f"idle s by program span: {sum(idle.values()):.6f} of "
               f"{red['window_s'] - red['busy_s']:.6f} idle: {cells}")
    return out
