"""Device time under the named scope ``repro:cost`` (the engine's
simulated-microsecond accounting, which does no transaction work) over
device busy time."""


def read(ctx):
    tr = ctx.get("trace")
    s = tr and tr["scope_s"].get("repro:cost")
    if not s or tr["busy_s"] <= 0:
        return None
    return 100.0 * s / tr["busy_s"]
