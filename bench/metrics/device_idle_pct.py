"""Device idle share: 1 - (union of device operation intervals) / traced
window, averaged over the chips the cell uses."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
