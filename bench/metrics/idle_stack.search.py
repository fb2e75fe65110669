"""Share of the traced window in which the device ran no XLA module
while the innermost program span was ``repro.engine.stack``, outside any
eviction recompute: the host assembling a chunk's parent activations.
Nothing is read from a program without ``repro.*`` spans."""
from bench import programtrace


def read(ctx):
    return programtrace.idle_share(ctx, "stack")
