"""The least time the chip's HBM needs for the bytes the traced waves'
concurrency control must move (bench/roofline.py), over the device time
under ``repro:validate``."""


def read(ctx):
    tr = ctx.get("trace")
    b = ctx.get("validate_bytes")
    s = tr and tr["scope_s"].get("repro:validate")
    if not s or not b:
        return None
    return 100.0 * (b / ctx["hbm_peak"]) / s
