"""Device time under the named scope ``repro:relayout`` (the table and
per-op row conversions around every Pallas call, ``kernels/rows.py``) over
device busy time.  The scope nests inside the phase that calls the kernel,
so this time is also part of that phase's share."""


def read(ctx):
    tr = ctx.get("trace")
    s = tr and tr["scope_s"].get("repro:relayout")
    if not s or tr["busy_s"] <= 0:
        return None
    return 100.0 * s / tr["busy_s"]
