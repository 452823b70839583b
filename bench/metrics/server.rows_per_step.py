"""Active rows (decode rows and prompt-chunk rows) per server step,
averaged over the window's steps (the program's routing trace)."""


def read(ctx):
    rows = [n for _, _, n in ctx.steps]
    return sum(rows) / len(rows) if rows else None
