"""Reduce a profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, device time per jitted
program, the top device operations, and the idle gaps by what the host
was doing in them.

``load`` turns the file into a neutral form -- a list of planes, each
``{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}``
-- so that ``reduce`` can be checked against a hand-built trace without
a chip (benchmarks/tests/test_yardstick.py).

What a TPU trace looks like (looked at by hand, PR 25): one plane per
chip named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per
executed HLO operation and whose ``XLA Modules`` line holds one event per
run of a jitted program; host threads are lines of the ``/host:CPU``
plane, where ``jax.profiler.TraceAnnotation`` spans appear by name.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
WINDOW_SPAN = "bench:window"
# Host spans that an idle gap may be attributed to: the program's own
# TraceAnnotations and the harness's.
SPAN_PREFIXES = ("tffm:", "bench:")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events],
            })
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _gaps(busy: list, lo: float, hi: float) -> list:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _short(name: str) -> str:
    # "%fusion.12 = f32[...] fusion(...)" -> "fusion.12"
    name = name.split(" = ")[0].strip()
    return name[1:] if name.startswith("%") else name


def reduce(planes: list, top: int = 10) -> dict | None:
    """None when the trace holds no device plane (a CPU rehearsal).

    ``busy_s`` is the union of the device-operation intervals inside the
    window, averaged over the chips; the window is the harness's
    ``bench:window`` span where the trace has one, else first to last
    device event.
    """
    devices = [p for p in planes
               if p["name"].startswith(DEVICE_PLANE_PREFIX)
               and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if not devices:
        return None
    spans = []  # named host spans: (name, start, end)
    for p in planes:
        if not p["name"].startswith(HOST_PLANE_PREFIX):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith(SPAN_PREFIXES):
                    spans.append((name, s, s + d))
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    all_ops = [(s, s + d) for p in devices for ln in p["lines"]
               if ln["name"] == OPS_LINE for _, s, d in ln["events"]]
    if not all_ops:
        return None
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo, hi = min(s for s, _ in all_ops), max(e for _, e in all_ops)
    busy_ns, op_ns, programs, gap_ns = [], {}, {}, {}
    for p in devices:
        ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE][0]
        iv = _clip([(s, s + d) for _, s, d in ops["events"]], lo, hi)
        busy = _union(iv)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in ops["events"]:
            if s + d > lo and s < hi:
                key = _short(name)
                op_ns[key] = op_ns.get(key, 0.0) + (min(s + d, hi)
                                                     - max(s, lo))
        for ln in p["lines"]:
            if ln["name"] != MODULES_LINE:
                continue
            for name, s, d in ln["events"]:
                if s + d > lo and s < hi:
                    key = name.split("(")[0]
                    rec = programs.setdefault(key, {"seconds": 0.0,
                                                    "runs": 0})
                    rec["seconds"] += d / 1e9
                    rec["runs"] += 1
        for gs, ge in _gaps(busy, lo, hi):
            # The host span (but the window itself) that covers most
            # of the gap names it.
            best, best_ov = "unattributed", 0.0
            for name, s, e in spans:
                # (a span the window cuts -- the dispatch in which the
                # harness closes it -- has no end of its own)
                if name == WINDOW_SPAN or s < lo or e > hi:
                    continue
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = name, ov
            gap_ns[best] = gap_ns.get(best, 0.0) + (ge - gs)
    n = len(devices)

    def rank(ns_by_name: dict) -> list:
        return [[k, v / 1e9 / n] for k, v in sorted(
            ns_by_name.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "programs": {k: {"seconds": v["seconds"] / n, "runs": v["runs"] / n}
                     for k, v in programs.items()},
        "device_ops": rank(op_ns),
        "idle_gaps": rank(gap_ns),
    }
