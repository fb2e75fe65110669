"""Share of the traced window in which the device ran no XLA module
while no engine span was open: under ``repro.objective`` alone (the cost
model and the objective's own work) or under no program span.  With
``idle_dispatch``, ``idle_stack``, ``idle_recompute`` and
``idle_bookkeeping`` it adds up to ``idle_between_programs``.  Nothing
is read from a program without ``repro.*`` spans."""
from bench import programtrace


def read(ctx):
    return programtrace.idle_share(ctx, "outside_engine")
