"""One minus the union of the device's operation intervals over the traced
slice, in percent."""


def read(ctx):
    r = ctx.reduced
    if not r or not r.get("window_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
