"""From a profiler trace to device busy time, idle gaps and op times.

The traced window is the host span named :data:`WINDOW`, which the
harness opens around the part of the run it traces; device operations
are clipped to it.  Busy time is the union of the intervals in which an
operation ran on a device (line ``XLA Ops`` of each ``/device:TPU:<n>``
plane), averaged over the devices that ran any.  Idle time is charged,
piece by piece, to the innermost host span of the harness (names
starting ``bench.``) over it, so the breakdown says what the host was
doing while the chip waited.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
# an op of the ops line is named by its HLO text; a loop, conditional or
# call there spans the ops of its body, which the line also holds
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
_KIND = re.compile(r"^%?([^ .]+)")


def kind(op_name: str) -> str:
    """An op's kind: its HLO name without ``%`` and numeric suffixes
    (``%matmul_pallas.48 = ...`` -> ``matmul_pallas``)."""
    m = _KIND.match(op_name)
    return m.group(1) if m else op_name


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed code into ``log_dir`` (no Python call tracing:
    it would slow the host by far more than the harness's spans)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_events(log_dir: str):
    """(device ops, host spans) of the newest trace under ``log_dir``.

    Device ops: ``{plane: [(name, start_ns, end_ns, stats)]}``, loops and
    calls left out for the ops they hold; host spans: ``[(name,
    start_ns, end_ns)]`` of the harness's own spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return events_of(ProfileData.from_file(max(paths, key=os.path.getmtime)))


def events_of(profile):
    dev, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if _CONTAINER.search(e.name):
                        continue
                    stats = {k: v for k, v in e.stats}
                    ops.append((e.name, e.start_ns, e.end_ns, stats))
            dev[plane.name] = ops
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns, e.end_ns))
    return dev, host


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _charge(spans, starts, g0, g1, gaps, depth: int = 16) -> None:
    """Charge the idle interval [g0, g1) to the harness spans over it,
    each piece to the innermost (latest-starting) span that covers it.
    Harness spans run one after another and nest only a few deep."""
    lo = bisect.bisect_right(starts, g0)
    hi = bisect.bisect_left(starts, g1)
    cand = [sp for sp in spans[max(0, lo - depth):hi]
            if sp[0] < g1 and sp[1] > g0]
    cuts = sorted({g0, g1} | {x for s, e, _ in cand for x in (s, e)
                              if g0 < x < g1})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(s, n) for s, e, n in cand if s <= mid < e]
        gaps[max(cover)[1] if cover else "no bench span"] += (b - a) * 1e-9


def reduce(dev: dict, host: list, *, top: int = 10) -> dict:
    """Busy and idle time in the traced window, op times, idle gaps.

    Returns ``{"window_s", "busy_s", "ops": [(name, stats, seconds)],
    "device_ops": [[kind, s]], "idle_gaps": [[host span, s]]}``, op times
    clipped to the window and summed over devices (``device_ops`` by op
    kind); ``busy_s`` is the mean over the devices that ran any
    operation."""
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    per_op = collections.defaultdict(float)
    stats_of = {}
    busy = []
    gaps = collections.defaultdict(float)
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    starts = [s for s, _, _ in spans]
    for ops in dev.values():
        clipped = [(max(s, w0), min(e, w1), n, st) for n, s, e, st in ops
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for s, e, n, st in clipped:
            per_op[n] += (e - s) * 1e-9
            stats_of.setdefault(n, st)
        merged = _union((s, e) for s, e, _, _ in clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                _charge(spans, starts, g0, g1, gaps)
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])
    by_kind = collections.defaultdict(float)
    for n, s in ranked:
        by_kind[kind(n)] += s
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "ops": [(n, stats_of[n], s) for n, s in ranked],
        "device_ops": [[n, s] for n, s in
                       sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
