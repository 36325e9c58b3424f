"""Share of the traced window in which an ``all-to-all`` runs on a chip
and nothing else does, averaged over the chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["has_collective"] or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
