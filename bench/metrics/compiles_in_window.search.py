"""Backend compilations (persistent-cache loads included) inside the
window; set-up should have made them all."""


def read(ctx):
    return ctx["compiles"]
