"""Device time under the named scope ``repro:validate`` (the concurrency-
control mechanism and the backend operations it calls) over device busy
time."""


def read(ctx):
    tr = ctx.get("trace")
    s = tr and tr["scope_s"].get("repro:validate")
    if not s or tr["busy_s"] <= 0:
        return None
    return 100.0 * s / tr["busy_s"]
