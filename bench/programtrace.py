"""The program's own spans and executables in a profiler trace: what
splits the device's idle time by cause.

``tracereduce`` reads the device's ``XLA Ops`` line and the benchmark's
``bench.*`` spans.  This module reads two more things from the same
trace, on the same clock:

- each device plane's ``XLA Modules`` line: one event per run of an
  executable, named ``jit_<function>(<fingerprint>)`` (a TPU v5e trace
  with JAX 0.9: ``jit_engine_seg_4_4(...)``, ``jit_dynamic_slice(...)``;
  the plane's other lines are ``XLA Ops``, ``Async XLA Ops``, ``Scalar
  Unit 0`` and ``TC Overlay 0``).  The staged engine names its
  executables ``engine_seg_<start>_<length>`` and ``engine_unit_<i>``;
  the other modules in the window are the eager operations of chunk
  assembly (``jit_dynamic_slice``, ``jit_squeeze``, ``jit_concatenate``,
  ``jit_gather``, ``jit_broadcast_in_dim``, ...);
- the program's spans, ``repro.*`` (``src/repro/obs.py``), on the host
  thread that calls the objective.  A span's metadata is kept in its
  name as ``<name>#<key>=<value>,...#``, as the profiler's own encoding
  writes it; ``repro.engine.stack`` carries ``path=gather`` or
  ``path=stack``.

The device's idle time in the window (the window less the union of its
operations) splits in two: idle while no module runs
(``idle_between_s``, the host has not yet enqueued the next program),
labelled by the innermost ``repro.*`` span over each gap's midpoint,
and idle between the operations of one running module (``inside a
program``).  A gap while a ``repro.engine.recompute`` span is open is
labelled with that span, whatever is nested in it: an eviction recompute
dispatches through the same dispatch, call and stack spans as a wave.
The labels fall into the five groups of ``GROUPS``, which partition the
between-program idle, one per-layer metric each.  A trace of a program
without these spans or names reads as having none of them.

The per-layer readers find the run's trace as the newest one under
``.bench_trace/`` and read it a second time (about 10 s for a 40 s
window on a TPU v5e), checked against the window the harness reduced.
"""
from __future__ import annotations

import bisect
import os
import re
import sys
import time

from bench import tracereduce

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "repro."
ENGINE_MODULE = re.compile(r"engine_(seg|unit)_\d+")
INSIDE = "inside a program"
NO_SPAN = "no program span"
RECOMPUTE = PROGRAM_PREFIX + "engine.recompute"
# between-program idle by gap label: one group per metric
GROUPS = {
    "dispatch": ("repro.engine.dispatch", "repro.engine.call"),
    "stack": ("repro.engine.stack",),
    "recompute": (RECOMPUTE,),
    "bookkeeping": ("repro.engine.plan", "repro.engine.store",
                    "repro.engine.gather"),
    "outside_engine": ("repro.objective", NO_SPAN),
}
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_trace")


def load(path: str) -> dict:
    """Normalise an ``.xplane.pb`` file as ``tracereduce.load_xplane``
    does, keeping only what the two reductions read: the device planes'
    ``XLA Ops`` and ``XLA Modules`` lines and the host's ``bench.*`` and
    ``repro.*`` spans, metadata in the name (needs JAX)."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = bool(tracereduce.DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            if device:
                if ln.name not in (tracereduce.OPS_LINE, MODULES_LINE):
                    continue
                events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                          for ev in ln.events]
            else:
                events = []
                for ev in ln.events:
                    name = ev.name
                    if name.startswith(PROGRAM_PREFIX):
                        name = encode(name, ev.stats)
                    elif not name.startswith(tracereduce.SPAN_PREFIX):
                        continue
                    events.append([name, float(ev.start_ns),
                                   float(ev.duration_ns)])
            if events:
                lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def encode(name: str, stats) -> str:
    stats = list(stats)
    if not stats:
        return name
    return name + "#" + ",".join(f"{k}={v}" for k, v in stats) + "#"


def decode(name: str) -> tuple[str, dict]:
    """A span's name and metadata from ``<name>#<k>=<v>,...#``."""
    base, _, rest = name.partition("#")
    meta = dict(kv.split("=", 1) for kv in rest.rstrip("#").split(",")
                if "=" in kv)
    return base, meta


def program_spans(tr: dict) -> list[tuple[str, float, float, dict]]:
    """(name, start_ns, end_ns, metadata) of every ``repro.*`` span."""
    out = []
    for p in tr["planes"]:
        if tracereduce.DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith(PROGRAM_PREFIX):
                    base, meta = decode(name)
                    out.append((base, s, s + d, meta))
    return out


def modules(plane: dict, lo: float, hi: float
            ) -> list[tuple[str, float, float]]:
    """(module, start_ns, end_ns) of the plane's module runs, clipped to
    [lo, hi]; the program id after the module's name is dropped."""
    out = []
    for ln in plane["lines"]:
        if ln["name"] != MODULES_LINE:
            continue
        for name, s, d in ln["events"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append((name.split("(", 1)[0], a, b))
    return out


def _gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(tr: dict) -> dict:
    """The new keys: ``module_s`` (device seconds per module, summed over
    the planes), ``idle_between_s`` (idle while no module runs, averaged
    over the planes, like ``busy_s``), ``idle_by_program_span``
    (``{label: [seconds, gaps]}`` summed over the planes: between-program
    idle by innermost ``repro.*`` span, or by the open recompute span,
    and ``inside a program``),
    ``stack_paths`` (``repro.engine.stack`` spans begun in the window, by
    path), ``program_spans`` (``repro.*`` spans in the trace),
    ``engine_modules`` (distinct engine executables that ran in the
    window) and ``planes`` (device planes that ran anything)."""
    lo, hi = tracereduce.window_bounds(tr)
    planes = [p for p in tracereduce.device_planes(tr)
              if tracereduce.ops(p, lo, hi)]
    spans = program_spans(tr)
    leaf = [(n, s, e) for n, s, e, _ in spans]
    recomputes = tracereduce.merge((s, e) for n, s, e, _ in spans
                                   if n == RECOMPUTE)
    starts = [a for a, _ in recomputes]
    module_s: dict[str, float] = {}
    by_span: dict[str, list] = {}
    between = 0.0
    for p in planes:
        ops = [(s, e) for _, s, e in tracereduce.ops(p, lo, hi)]
        mods = modules(p, lo, hi)
        for name, a, b in mods:
            module_s[name] = module_s.get(name, 0.0) + (b - a) * 1e-9
        busy = tracereduce.merge(ops)
        gaps = _gaps(tracereduce.merge(ops + [(a, b) for _, a, b in mods]),
                     lo, hi)
        between += sum(b - a for a, b in gaps)
        mids = [0.5 * (a + b) for a, b in gaps]
        for (a, b), t, lab in zip(gaps, mids,
                                  tracereduce.spans_at(leaf, mids)):
            k = bisect.bisect_right(starts, t) - 1
            if k >= 0 and t <= recomputes[k][1]:
                lab = RECOMPUTE
            slot = by_span.setdefault(
                NO_SPAN if lab == "no bench span" else lab, [0.0, 0])
            slot[0] += (b - a) * 1e-9
            slot[1] += 1
        inside = _intersect(_gaps(busy, lo, hi), tracereduce.merge(
            (a, b) for _, a, b in mods))
        if inside:
            slot = by_span.setdefault(INSIDE, [0.0, 0])
            slot[0] += sum(b - a for a, b in inside) * 1e-9
            slot[1] += len(inside)
    paths: dict[str, int] = {}
    for name, s, _, meta in spans:
        if name == PROGRAM_PREFIX + "engine.stack" and lo <= s <= hi:
            key = meta.get("path", "?")
            paths[key] = paths.get(key, 0) + 1
    return {"module_s": module_s,
            "idle_between_s": between * 1e-9 / max(len(planes), 1),
            "idle_by_program_span": by_span,
            "stack_paths": paths,
            "program_spans": len(spans),
            "engine_modules": sum(1 for k in module_s
                                  if ENGINE_MODULE.search(k)),
            "planes": len(planes)}


def idle_share(ctx: dict, group: str) -> float | None:
    """Between-program idle under the labels of ``GROUPS[group]``, as a
    share (%) of the window, averaged over the planes; ``None`` for a
    program without ``repro.*`` spans."""
    red = of(ctx)
    if red is None or not red["program_spans"]:
        return None
    s = sum(red["idle_by_program_span"].get(k, [0.0])[0]
            for k in GROUPS[group])
    return 100.0 * s / max(red["planes"], 1) / ctx["trace"]["window_s"]


def breakdown(red: dict) -> list[list]:
    """``idle_gaps_program``: [``<label> x<count>``, seconds], largest
    first, in the form of ``tracereduce``'s ``idle_gaps``."""
    best = sorted(red["idle_by_program_span"].items(),
                  key=lambda kv: -kv[1][0])
    return [[f"{k} x{c}", v] for k, (v, c) in best]


def of(ctx: dict) -> dict | None:
    """What this module reads from the run's own trace, for the
    per-layer readers: read once, kept in ``ctx``.  ``None`` when no
    trace of this window is found (its window must be the one the
    harness reduced)."""
    if "program" not in ctx:
        ctx["program"] = _read(ctx)
    return ctx["program"]


def _read(ctx: dict) -> dict | None:
    try:
        path = tracereduce.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    t = time.monotonic()
    tr = load(path)
    lo, hi = tracereduce.window_bounds(tr)
    if abs((hi - lo) * 1e-9 - ctx["trace"]["window_s"]) > 1e-6:
        return None
    red = reduce(tr)
    print(f"program trace read in {time.monotonic() - t:.1f} s: "
          f"{red['program_spans']} program spans, "
          f"{len(red['module_s'])} modules, "
          f"stack paths {red['stack_paths']}, "
          f"idle_gaps_program {breakdown(red)}", file=sys.stderr,
          flush=True)
    return red
