"""The window's seconds over the engine iterations it made, in ms."""


def read(ctx):
    n = ctx.facts.get("iterations")
    if not n:
        return None
    return 1e3 * ctx.facts["window_s"] / n
