"""From a profiler trace to the numbers the per-layer metrics read.

A trace is first normalised to plain data::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

so that the reduction below can be tested on a small recorded trace
without JAX.  Device planes are ``/device:TPU:<n>``; the operations that
run on a device are the events of its ``XLA Ops`` line.  Host spans are
the benchmark's own ``jax.profiler.TraceAnnotation`` events, all named
``bench.<what>``; the one named ``bench.window`` bounds the measured
window, and device time is counted inside it only.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """Normalise an ``.xplane.pb`` file (needs JAX)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: dict) -> list[dict]:
    return [p for p in tr["planes"] if DEVICE_PLANE.match(p["name"])]


def host_spans(tr: dict) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every ``bench.*`` span on the host."""
    out = []
    for p in tr["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name, s, s + d))
    return out


def window_bounds(tr: dict) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in host_spans(tr) if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def ops(plane: dict, lo: float, hi: float) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the plane's device operations, clipped
    to [lo, hi]; operations wholly outside are dropped."""
    out = []
    for ln in plane["lines"]:
        if ln["name"] != OPS_LINE:
            continue
        for name, s, d in ln["events"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append((name, a, b))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(plane: dict, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge((s, e) for _, s, e in
                                       ops(plane, lo, hi)))


def op_time_ns(plane: dict, lo: float, hi: float, pattern: str) -> float:
    """Device time of the operations whose name holds ``pattern``."""
    rx = re.compile(pattern)
    return sum(e - s for n, s, e in ops(plane, lo, hi) if rx.search(n))


def top_ops(planes, lo, hi, n: int = 10) -> list[list]:
    """[name, seconds] of the ``n`` operations that took most device time
    in the window, summed over their calls and over the planes."""
    tot: dict[str, float] = {}
    for p in planes:
        for name, s, e in ops(p, lo, hi):
            tot[name] = tot.get(name, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def gaps(plane: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of one device inside [lo, hi]."""
    out, t = [], lo
    for a, b in merge((s, e) for _, s, e in ops(plane, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def spans_at(spans, times) -> list[str]:
    """Innermost (shortest) host span that covers each of ``times``, in
    one sweep over the spans by start: ``times`` ascending."""
    order = sorted(spans, key=lambda sp: sp[1])
    active: list = []
    out, i = [], 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[2] >= t]
        best = min(active, key=lambda sp: sp[2] - sp[1], default=None)
        out.append("no bench span" if best is None else best[0])
    return out


def idle_by_span(tr: dict, planes, lo, hi, n: int = 10) -> list[list]:
    """[label, seconds]: the device's idle time in the window, summed by
    the host span that covered each idle gap's midpoint, largest first.
    The label carries the number of gaps, as ``<span> x<count>``."""
    spans = host_spans(tr)
    tot: dict[str, list] = {}
    for p in planes:
        idle = gaps(p, lo, hi)
        labels = spans_at(spans, [0.5 * (a + b) for a, b in idle])
        for (a, b), lab in zip(idle, labels):
            slot = tot.setdefault(lab, [0.0, 0])
            slot[0] += b - a
            slot[1] += 1
    best = sorted(tot.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{k} x{c}", v * 1e-9] for k, (v, c) in best]


def summarize(tr: dict, kernels: dict[str, str] | None = None) -> dict:
    """What the per-layer readers take from a trace: the window's length,
    the device busy time averaged over the planes that ran anything,
    the time of each named kernel (``kernels`` maps a key to a regular
    expression over operation names), and the breakdown."""
    lo, hi = window_bounds(tr)
    planes = [p for p in device_planes(tr) if ops(p, lo, hi)]
    if not planes:
        raise ValueError("no device operation ran inside the window")
    busy = sum(busy_ns(p, lo, hi) for p in planes) / len(planes)
    ktime = {k: sum(op_time_ns(p, lo, hi, rx) for p in planes) * 1e-9
             for k, rx in (kernels or {}).items()}
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "devices": len(planes), "kernel_s": ktime,
            "breakdown": {"device_ops": top_ops(planes, lo, hi),
                          "idle_gaps": idle_by_span(tr, planes, lo, hi)}}
