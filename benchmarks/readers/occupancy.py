"""Decode slots occupied over the engine's ``max_batch``, sampled after every
step of the window, mean, in percent."""


def read(ctx):
    samples = ctx.facts.get("samples")
    if not samples:
        return None
    live = sum(n for n, _ in samples) / len(samples)
    return 100.0 * live / ctx.mix["engine"]["max_batch"]
