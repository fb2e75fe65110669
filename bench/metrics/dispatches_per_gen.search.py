"""Engine dispatches per generation in the window (delta of
``PrefixEvalEngine.stats()["dispatches"]``)."""


def read(ctx):
    w = ctx["window"]["layer"]
    return w["dispatches"] / w["generations"]
