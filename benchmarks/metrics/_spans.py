"""Shared by the readers that take their number from the program's own
request phases: the ``tffm:serve.<phase>`` host spans of the run's trace
(fast_tffm_tpu/serve; OBSERVABILITY.md "xprof" lists them), with the
stats each carries.

``load`` keeps what ``fmbench.xplane.load`` drops, the events' stats,
and only the host planes' named spans; its neutral form -- planes of
lines of ``(name, start_ns, dur_ns, stats)`` -- lets ``reduce`` be
checked against a hand-built trace (benchmarks/tests/test_spans.py).
A program without such spans, a run without a trace and a trace that is
no longer on the disk all read as None.
"""

from __future__ import annotations

import os

from fmbench import harness, xplane

PREFIX = "tffm:serve."
# The dispatcher thread's work; ``coalesce`` is its own deliberate wait.
WORK_PHASES = ("fill", "launch", "readback", "deliver", "quality")
_cache: dict = {}


def load(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.HOST_PLANE_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns),
                       dict(e.stats)) for e in line.events
                      if e.name.startswith(xplane.SPAN_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce(planes: list) -> dict | None:
    """Per phase, over the spans that lie inside the ``bench:window``
    span (one the window cuts has no end of its own): count, seconds,
    the longest, and the sum of each stat; and the seconds per phase on
    the dispatcher's line (the one that holds ``coalesce``)."""
    lines = [ln["events"] for p in planes
             if p["name"].startswith(xplane.HOST_PLANE_PREFIX)
             for ln in p["lines"]]
    win = [(s, s + d) for ev in lines for name, s, d, _ in ev
           if name == xplane.WINDOW_SPAN]
    if not win:
        return None
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    phases, dispatcher = {}, {}
    for events in lines:
        mine = {}
        for name, s, d, stats in events:
            if not name.startswith(PREFIX) or s < lo or s + d > hi:
                continue
            phase = name[len(PREFIX):]
            rec = phases.setdefault(phase, {"count": 0, "seconds": 0.0,
                                            "longest_s": 0.0, "stats": {}})
            rec["count"] += 1
            rec["seconds"] += d / 1e9
            rec["longest_s"] = max(rec["longest_s"], d / 1e9)
            for k, v in stats.items():
                rec["stats"][k] = rec["stats"].get(k, 0) + v
            mine[phase] = mine.get(phase, 0.0) + d / 1e9
        if "coalesce" in mine:
            for phase, sec in mine.items():
                dispatcher[phase] = dispatcher.get(phase, 0.0) + sec
    if not phases:
        return None
    return {"window_s": (hi - lo) / 1e9, "phases": phases,
            "dispatcher": dispatcher}


def for_run(run: dict) -> dict | None:
    """The reduced spans of this run's own trace, read once a process."""
    if not run.get("trace"):
        return None
    try:
        path = xplane.find_xplane(os.path.join(
            harness.WORK_ROOT, run["workload"], "trace"))
    except FileNotFoundError:
        return None
    if path not in _cache:
        _cache[path] = reduce(load(path))
    return _cache[path]


def mean_ms(run: dict, phase: str) -> float | None:
    """Mean milliseconds of one phase's spans in the window."""
    spans = for_run(run)
    rec = spans and spans["phases"].get(phase)
    if not rec:
        return None
    return 1e3 * rec["seconds"] / rec["count"]


def dispatcher_busy_pct(run: dict) -> float | None:
    """The dispatcher thread's work phases over the window, in percent:
    near 100, the one dispatcher is the cap."""
    spans = for_run(run)
    if not spans or not spans["dispatcher"]:
        return None
    work = sum(spans["dispatcher"].get(p, 0.0) for p in WORK_PHASES)
    return 100.0 * work / spans["window_s"]
