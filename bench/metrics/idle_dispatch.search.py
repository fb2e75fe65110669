"""Share of the traced window in which the device ran no XLA module
while the innermost program span was ``repro.engine.dispatch`` or
``repro.engine.call``, outside any eviction recompute: host dispatch of
the engine's chunks (gene padding and upload, landing the previous call,
the jitted call itself).  Nothing is read from a program without
``repro.*`` spans."""
from bench import programtrace


def read(ctx):
    return programtrace.idle_share(ctx, "dispatch")
