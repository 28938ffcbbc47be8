"""Backend compiles inside the window (JAX's compile monitoring event); the
serving path should compile nothing once warm."""


def read(ctx):
    return ctx["compiles"]
