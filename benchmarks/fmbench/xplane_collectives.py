"""Device seconds of the collective operations in a profiler trace, per
chip: what the exchange between chips costs on the device's own clock.

Takes ``fmbench.xplane.load``'s neutral planes.  A collective is an event
of a chip's ``XLA Ops`` line whose operation's name starts with one of
``KINDS``.  An asynchronous collective shows as a ``-start`` and a
``-done`` event; the pair is counted ONCE, as the one interval from the
start's beginning to the done's end (the transfer is in flight all that
while, whatever else the chip runs under it), so that a share of the
interconnect's peak over this time cannot be flattered by what overlaps.
A chip's seconds are the union of its intervals inside the window (the
harness's ``bench:window`` span where the trace has one).
"""

from __future__ import annotations

from fmbench import xplane

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def kind_of(name: str):
    """(kind, phase) of an event's name -- phase "start", "done" or ""
    -- or None where the operation is no collective."""
    short = xplane._short(name)
    for kind in KINDS:
        if short.startswith(kind):
            rest = short[len(kind):]
            for phase in ("start", "done"):
                if rest.startswith("-" + phase):
                    return kind, phase
            return kind, ""
    return None


def intervals(events: list) -> list:
    """``(kind, start_ns, end_ns)`` per collective of one chip's ops
    line, a start / done pair joined (a start pairs with the next done of
    its kind; an unpaired half stands alone)."""
    out, waiting = [], {}
    for name, s, d in sorted(events, key=lambda e: e[1]):
        hit = kind_of(name)
        if hit is None:
            continue
        kind, phase = hit
        if phase == "start":
            waiting.setdefault(kind, []).append((s, s + d))
        elif phase == "done" and waiting.get(kind):
            s0, _ = waiting[kind].pop(0)
            out.append((kind, s0, s + d))
        else:
            out.append((kind, s, s + d))
    for kind, rest in waiting.items():
        out.extend((kind, s, e) for s, e in rest)
    return out


def reduce(planes: list) -> dict | None:
    """``{"chips", "seconds", "by_kind", "count"}``: the mean over the
    chips of the union of a chip's collective intervals inside the
    window, the same per kind, and the collectives a chip ran.  None
    where the trace holds no device plane."""
    devices = [ln for p in planes
               if p["name"].startswith(xplane.DEVICE_PLANE_PREFIX)
               for ln in p["lines"] if ln["name"] == xplane.OPS_LINE]
    if not devices:
        return None
    win = [(s, s + d) for p in planes
           if p["name"].startswith(xplane.HOST_PLANE_PREFIX)
           for ln in p["lines"] for name, s, d in ln["events"]
           if name == xplane.WINDOW_SPAN]
    lo = min((s for s, _ in win), default=float("-inf"))
    hi = max((e for _, e in win), default=float("inf"))
    total, by_kind, count = 0.0, {}, 0
    for ln in devices:
        found = intervals(ln["events"])
        inside = xplane._clip([(s, e) for _, s, e in found], lo, hi)
        count += len(inside)
        total += sum(e - s for s, e in xplane._union(inside))
        for kind in KINDS:
            iv = xplane._clip([(s, e) for k, s, e in found if k == kind],
                              lo, hi)
            if iv:
                by_kind[kind] = by_kind.get(kind, 0.0) + sum(
                    e - s for s, e in xplane._union(iv))
    n = len(devices)
    return {"chips": n, "seconds": total / n / 1e9,
            "by_kind": {k: v / n / 1e9 for k, v in by_kind.items()},
            "count": count / n}
