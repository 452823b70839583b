"""Programs JAX lowered inside the windows, untraced and traced (each a
compile or a load from the compile cache; JAX's monitoring events).
Set-up warms every shape, so this reads 0."""


def read(ctx):
    return ctx.lowered_in_window
