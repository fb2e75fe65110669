"""Unit runs the staged engine performed per candidate row the objective
returned in the window (delta of ``PrefixEvalEngine.stats()["unit_runs"]``,
eviction recomputes included)."""


def read(ctx):
    w = ctx["window"]["layer"]
    return w["unit_runs"] / w["candidates"]
