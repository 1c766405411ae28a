"""Device time of one compiled module's executions over the traced slice, in
percent."""


def read(ctx, module):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if not mod or not r.get("window_s"):
        return None
    return 100.0 * mod["seconds"] / r["window_s"]
