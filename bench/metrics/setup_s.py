"""Set-up seconds on the host clock, from the process's start to the
window's: imports, weights made from the seed, the host expert store
filled, every program compiled or loaded from the compile cache, and
the server served until every client is past its prompt and every
expert cache is full."""


def read(ctx):
    return ctx.setup_s
