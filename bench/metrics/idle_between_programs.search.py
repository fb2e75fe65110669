"""Share of the traced window in which the device ran no XLA module:
idle time between programs, before the host enqueued the next one.
``device_idle_share.search`` less this is the idle time between the
operations of a running program.  Nothing is read from a trace without
the device's module line."""
from bench import programtrace


def read(ctx):
    p = programtrace.of(ctx)
    if p is None or not p["module_s"]:
        return None
    return 100.0 * p["idle_between_s"] / ctx["trace"]["window_s"]
