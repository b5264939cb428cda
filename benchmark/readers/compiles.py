"""Programs JAX compiled (or fetched from its persistent cache) inside the
measured window: the warm-up missed a shape if this is not 0."""


def read(ctx):
    return ctx["compiles_in_window"]
