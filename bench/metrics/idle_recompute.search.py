"""Share of the traced window in which the device ran no XLA module
while a ``repro.engine.recompute`` span was open, whatever is nested in
it (the recompute's own dispatch, call and assembly): the host redoing
evicted parents.  Nothing is read from a program without ``repro.*``
spans."""
from bench import programtrace


def read(ctx):
    return programtrace.idle_share(ctx, "recompute")
