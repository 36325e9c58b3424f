"""Device time under the named scope ``repro:gen`` (the program's own
workload generator, run on the device every wave) over device busy
time."""


def read(ctx):
    tr = ctx.get("trace")
    s = tr and tr["scope_s"].get("repro:gen")
    if not s or tr["busy_s"] <= 0:
        return None
    return 100.0 * s / tr["busy_s"]
