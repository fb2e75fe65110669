"""Device time in XLA modules other than the engine's own executables
(``engine_seg_*``, ``engine_unit_*``), as a share of the device's busy
time in the traced window: the eager gathers, stacks, slices and copies
of chunk assembly.  Nothing is read where no module carries an engine
name."""
from bench import programtrace


def read(ctx):
    p = programtrace.of(ctx)
    if p is None or not p["engine_modules"]:
        return None
    other = sum(v for k, v in p["module_s"].items()
                if not programtrace.ENGINE_MODULE.search(k))
    return 100.0 * other / p["planes"] / ctx["trace"]["busy_s"]
