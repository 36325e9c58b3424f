"""Aborted attempts over attempts in the window, from the engine's own
verdict counters."""


def read(ctx):
    c = ctx.get("counters")
    if not c or not c["attempts"]:
        return None
    return 100.0 * c["aborts"] / c["attempts"]
