"""Device busy time under none of the program's layer scopes, over busy
time.

Each device op of the program's wave runs under exactly one outermost
``repro:<layer>`` scope of its driver's wave (``OUTER``; the program's
tests hold it to that list), and a chip runs its ops one at a time, so
the outermost scopes' times add up to the scoped part of busy time.  The
rest is device time that no layer of the program claims: the harness's
own ops (on four chips its traffic generator), the scan's loop
bookkeeping, and fusions whose root carries no scope.  An op of one
outermost scope that overlaps an op of another (an ``all-to-all`` beside
compute) is counted twice and lowers the reading."""

#: The outermost scopes of each driver's wave; the others (``relayout``,
#: ``wave_commit``, ...) nest inside them.
OUTER = {
    "engine": ("repro:gen", "repro:schedule", "repro:validate",
               "repro:cost", "repro:account"),
    "sharded": ("repro:schedule", "repro:route", "repro:exchange",
                "repro:claim", "repro:commit", "repro:install",
                "repro:account"),
}


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    outer = OUTER[ctx["cell"].config["driver"]]
    scoped = sum(tr["scope_s"].get(s, 0.0) for s in outer)
    return 100.0 * (tr["busy_s"] - scoped) / tr["busy_s"]
