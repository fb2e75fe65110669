"""Share of the traced window in which the device ran no XLA module
while the innermost program span was ``repro.engine.plan``,
``repro.engine.store`` or ``repro.engine.gather``, outside any eviction
recompute: the engine planning a wave's segments, storing its outputs
and fetching the ΔAcc values to the host.  Nothing is read from a
program without ``repro.*`` spans."""
from bench import programtrace


def read(ctx):
    return programtrace.idle_share(ctx, "bookkeeping")
