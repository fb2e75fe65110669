#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip and print its result.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (weights and inputs from the seed,
compilation or compile-cache loads, warm-up) runs first; then the window
is measured for ``--seconds``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics.  Either way the
outputs of the window are checked against the configuration's plain
reference once the window has closed.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; ``checks`` comes last, each compared number with its
limit).  The last lines of standard error repeat the checks.  Without a
TPU, with fewer chips than the cell needs, or outside a checkout that
holds ``src/repro``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0,
                    help="also read the controls the limits were set "
                         "against (not part of a benchmark run)")
    args = ap.parse_args(argv)

    # libtpu logs to a fixed /tmp path unless told otherwise; the run
    # writes nothing outside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from bench import harness

    try:
        cell = harness.load_cell(harness.load_spec(ROOT), args.workload,
                                 ROOT)
        src = os.path.join(ROOT, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise harness.BenchError(f"no repro package under {src}; run "
                                     "from a checkout of the repository")
        sys.path.insert(0, src)
        harness.require_devices(cell.chips)
    except harness.BenchError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2

    import jax

    # the checkout's own cache at a fixed path, whatever the environment
    # names, holding every executable however quick its compile and
    # however large: only the first run of a cell in a checkout compiles
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t0=T0,
                           trace_dir=trace_dir, root=ROOT,
                           controls=bool(args.controls))
    for name, readings in out.get("controls", {}).items():
        print(f"control {name}: {readings}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
