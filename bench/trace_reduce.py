"""From a profiler trace to the per-layer numbers.

The reduction works on a plain form of the trace, which ``load_xplane``
makes from the profiler's ``.xplane.pb`` and which a test can write by
hand:

    {"window": [start_ns, end_ns],            # the traced window
     "devices": {"0": [[op, start_ns, dur_ns], ...], ...},
     "host": [[span, start_ns, dur_ns], ...], # the harness's own spans
     "scopes": {op: "jit(chunk)/while/body/repro:validate/..."}}

``devices`` holds each chip's device operations, named as the compiled
program names them; ``scopes`` maps those names to the op metadata of the
compiled HLO, where JAX writes the named scopes (``repro:*``) an
operation was traced under.
"""
from __future__ import annotations

import re

_HLO_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')
#: Control-flow ops whose trace event spans the ops they run.
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> op_name metadata, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def merge(intervals, lo: int, hi: int) -> list:
    """Union of [start, end) intervals clipped to [lo, hi), sorted."""
    iv = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                if min(e, hi) > max(s, lo))
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> int:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    out, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def _is_exchange(name: str) -> bool:
    """XLA names the collective ``all-to-all`` and the ops it lowers
    ``jax.lax.all_to_all`` into around it ``all_to_all``."""
    return "all-to-all" in name.replace("_", "-")


def reduce(trace: dict) -> dict:
    """Busy and idle time, time per named scope and exposed exchange
    time of a plain trace; times in seconds, per device and averaged."""
    lo, hi = trace["window"]
    scopes = trace.get("scopes", {})
    devs = sorted(trace["devices"])
    busy, scope_s, exposed = {}, {}, {}
    for d in devs:
        ev = trace["devices"][d]
        allm = merge(_spans(ev), lo, hi)
        busy[d] = total(allm) * 1e-9
        by_scope: dict = {}
        for name, s, dur in ev:
            path = scopes.get(name, "")
            for part in set(re.findall(r"repro:[\w\-]+", path)):
                by_scope.setdefault(part, []).append((s, s + dur))
        scope_s[d] = {k: total(merge(v, lo, hi)) * 1e-9
                      for k, v in by_scope.items()}
        coll = merge([(s, s + dur) for name, s, dur in ev
                      if _is_exchange(name)], lo, hi)
        rest = merge([(s, s + dur) for name, s, dur in ev
                      if not _is_exchange(name)], lo, hi)
        exposed[d] = subtract(coll, rest) * 1e-9
    n = max(len(devs), 1)
    names = {k for d in devs for k in scope_s[d]}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy.values()) / n,
        "scope_s": {k: sum(scope_s[d].get(k, 0.0) for d in devs) / n
                    for k in names},
        "exposed_collective_s": sum(exposed.values()) / n,
        "has_collective": any(_is_exchange(name) for d in devs
                              for name, _, _ in trace["devices"][d]),
        "devices": len(devs),
    }


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (seconds, mean over the
    devices) and the longest idle gaps of the first device, each named by
    the harness span the host was in when the gap began."""
    lo, hi = trace["window"]
    devs = sorted(trace["devices"])
    per_op: dict = {}
    for d in devs:
        for name, s, dur in trace["devices"][d]:
            ov = min(s + dur, hi) - max(s, lo)
            if ov > 0:
                per_op[name] = per_op.get(name, 0) + ov
    n = max(len(devs), 1)
    ops = sorted(((k, v * 1e-9 / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = []
    if devs:
        busy = merge(_spans(trace["devices"][devs[0]]), lo, hi)
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        host = sorted(trace.get("host", []), key=lambda h: h[1])
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_host_at(host, g0), (g1 - g0) * 1e-9))
    gaps.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def _host_at(host, t: int) -> str:
    """The innermost harness span that holds instant ``t``."""
    best, start = "no harness span", None
    for name, s, d in host:
        if s > t:
            break
        if s <= t < s + d and (start is None or s >= start):
            best, start = name, s
    return best


def op_name(event_name: str) -> str:
    """A TPU trace names an op by its HLO text, ``%fusion.3 = f32[...] ...``;
    the instruction name is the part before `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str, hlo_text: str, window_span: str) -> dict:
    """The plain form of a profiler ``.xplane.pb``: every TPU's "XLA Ops"
    line without the control-flow ops (a ``while`` event spans the whole
    loop it runs), the host's ``bench:`` spans, and the traced window as
    the extent of the host span ``window_span``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ev = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = op_name(e.name)
                        if not _CONTAINER.match(name):
                            ev.append([name, int(e.start_ns),
                                       int(e.duration_ns)])
            devices[plane.name.rsplit(":", 1)[1]] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if e.name.startswith("bench:")]
    win = [h for h in host if h[0] == window_span]
    if not win or not devices:
        raise ValueError(f"{path}: no {window_span!r} span or no TPU plane")
    s, d = win[0][1], win[0][2]
    return {"window": [s, s + d], "devices": devices, "host": host,
            "scopes": hlo_scopes(hlo_text)}
