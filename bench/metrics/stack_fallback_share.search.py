"""Share of the chunk assemblies in the traced window that took the
per-row path (each parent sliced out, then stacked) rather than one
gather: the ``repro.engine.stack`` spans by their ``path``.  Nothing is
read without such spans."""
from bench import programtrace


def read(ctx):
    p = programtrace.of(ctx)
    n = sum(p["stack_paths"].values()) if p else 0
    if not n:
        return None
    return 100.0 * p["stack_paths"].get("stack", 0) / n
