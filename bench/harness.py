"""Finds a cell's pieces by name, runs it once, and builds its result.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
The configuration's file is named in ``configs``; the traffic mix is
``traffic/<mix>.json`` and names the driver (``drivers/<driver>.py``)
that turns it into work; each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and entries; nothing here names one.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(Exception):
    """The cell cannot be run as asked: a missing piece or device."""


def load_spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def _load_data(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


def load_cell(spec: dict, name: str, root: str = ROOT) -> Cell:
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise BenchError(f"no workload {name!r}; known: "
                         f"{sorted(w['name'] for w in spec['workloads'])}")
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg_path = os.path.join(root, cfg["file"])
    if not os.path.isfile(cfg_path):
        raise BenchError(f"configuration file {cfg['file']} is missing")
    path = os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")
    if not os.path.isfile(path):
        raise BenchError(f"no traffic file for mix {wl['traffic']!r}")
    return Cell(name=name, chips=int(wl["chips"]),
                config=_load_data(cfg_path), traffic=_load_data(path),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def _load_file(path: str, modname: str):
    if not os.path.isfile(path):
        raise BenchError(f"{os.path.relpath(path, ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT):
    """``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
    mod = _load_file(os.path.join(root, "bench", "metrics", metric + ".py"),
                     "bench_metric_" + re.sub(r"\W", "_", metric))
    return mod.read


def load_reference(config: dict, root: str = ROOT):
    """The plain reference module named by the configuration, kept
    beside its file under ``configs/``."""
    name = config["reference"]
    return _load_file(os.path.join(root, "bench", "configs", name + ".py"),
                      "bench_ref_" + re.sub(r"\W", "_", name))


def load_driver(name: str):
    try:
        return importlib.import_module(f"bench.drivers.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.drivers.{name}":
            raise BenchError(f"no driver {name!r} under bench/drivers")
        raise


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------
def require_devices(chips: int):
    """The cell's chips, or :class:`BenchError`: the benchmark measures a
    TPU and never falls back to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r} "
                         "devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    from bench.peaks import UnknownDevice, peaks_for
    try:
        peaks_for(devs[0].device_kind)
    except UnknownDevice as e:
        raise BenchError(str(e)) from None
    return devs


def device_info(used: int) -> dict:
    """The device as JAX reports it, and the peak memory of the fullest
    of the ``used`` devices."""
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts backend compilations (persistent-cache loads included),
    their seconds, and the persistent cache's hits, while it is
    entered."""

    def __init__(self):
        self.names: list[str] = []
        self.seconds = 0.0
        self.cache_hits = 0

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.names.append(str(kwargs.get("fun_name", "?")))
            self.seconds += duration

    def _hit(self, event: str, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._hit)


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, trace_dir: str, root: str = ROOT,
             controls: bool = False) -> dict:
    """Set the cell up, measure one window, check what it produced, and
    return the result line's object.  ``t0`` is the process's start on
    ``time.monotonic``.  The caller has checked the devices.  With
    ``controls`` the driver's controls are read too, after the check
    (``controls`` in the result: how the limits were set)."""
    import gc

    import jax

    from bench import tracereduce
    from bench.peaks import peaks_for

    mod = load_driver(cell.traffic["driver"])
    drv = mod.Run(cell.config, cell.traffic, seed,
                  load_reference(cell.config, root), seconds)
    with CompileCounter() as cc:
        drv.setup()
        setup_s = time.monotonic() - t0
        n0 = cc.count
        setup_compile = {"setup_compiles": n0,
                         "setup_compile_s": cc.seconds,
                         "setup_cache_hits": cc.cache_hits}
        print(f"set-up {setup_s:.1f} s: {setup_compile}", file=sys.stderr,
              flush=True)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        try:
            with span("window"):
                win = drv.window(seconds)
        finally:
            if trace:
                t = time.monotonic()
                jax.profiler.stop_trace()
                print(f"trace written in {time.monotonic() - t:.1f} s",
                      file=sys.stderr, flush=True)
        compiles = cc.count - n0
        compiled = sorted(set(cc.names[n0:]))
        drv.drain()
    device = device_info(cell.chips)
    summary = None
    if trace:
        t = time.monotonic()
        summary = tracereduce.summarize(
            tracereduce.load_xplane(tracereduce.find_xplane(trace_dir)),
            getattr(drv, "KERNELS", {}))
        print(f"trace read in {time.monotonic() - t:.1f} s",
              file=sys.stderr, flush=True)
    drv.release()
    gc.collect()
    checks = drv.check()

    if trace:
        ctx = {"window": win, "trace": summary, "compiles": compiles,
               "peaks": peaks_for(device["kind"]), "config": cell.config,
               "traffic": cell.traffic}
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    else:
        vals = dict(win["metrics"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in vals:
                raise BenchError(f"the {cell.traffic['driver']} driver "
                                 f"reports no {m['name']}")
            metrics[m["name"]] = {"value": vals[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks),
           "attempted": win["attempted"], "failed": win["failed"],
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary["breakdown"]
    out["notes"] = dict(win.get("notes", {}), setup_s=setup_s,
                        **setup_compile,
                        compiles_in_window=compiles,
                        compiled_in_window=compiled[:20])
    if controls:
        out["controls"] = drv.controls()
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out
