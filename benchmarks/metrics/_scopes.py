"""Shared by the readers that take their number from the train step's own
scopes: device milliseconds a run of the step program, by the
``jax.named_scope`` (``tffm.*``) each operation of the compiled step was
written under.

A device trace names an operation by its instruction (``fusion.22``) and
carries no scope.  The program writes the join itself: the newest
``record: compile`` of the largest ``k`` in its own records
(``run["records"]``) holds the compiled step's table -- ``scopes``
(scope -> the instructions that carry it), ``moved`` (scope -> what the
compiler made and gave the scope of the data it moves), ``mixed`` (a
fusion whose members carry more than one scope -> those scopes) and
``containers`` (``while`` / ``conditional`` / ``call``) --
fast_tffm_tpu/obs/scopes.py.

``reduce`` takes, on each chip, the ``XLA Ops`` events that lie inside a
run (an ``XLA Modules`` event) of the program named by
``counters["step_program_prefix"]`` that overlaps ``bench:window``, and
counts each event's SELF time: its duration less the union of the same
line's events nested inside it (a container holds the operations it
runs).  Self time is summed by the table: a scope (``moved`` time
included, and also apart as ``moved``), ``mixed``, ``containers``, or
``unnamed`` (an instruction in no list); the sum of the four is the step
program's summed self time.  Chips are averaged.

A run without a trace file, a program without the table and a CPU trace
(no device plane) read as None.  A trace and a table with not one event
between them raises: the naming broke, and a None would hide it; so does
a traced run whose compile record says the table could not be made
(``scope_table_error``).  The
trace is read through ``fmbench.xplane.load``, whose neutral form lets
``reduce`` be checked against a hand-built trace
(benchmarks/tests/test_scopes.py).
"""

from __future__ import annotations

import bisect
import os

from fmbench import xplane

INTERACTION = ("tffm.fm_interaction_fwd", "tffm.fm_interaction_bwd",
               "tffm.ffm_interaction_fwd", "tffm.ffm_interaction_bwd")
EXCHANGE = ("tffm.exchange", "tffm.exchange_merge")
_cache: dict = {}


def table(records: list) -> dict | None:
    """The scope table of the newest ``record: compile`` of the largest
    ``k``, or None; ``{"error": ...}`` where that compile's table failed."""
    best = None
    for rec in records or ():
        if rec.get("record") != "compile" or not (
                "scopes" in rec or "scope_table_error" in rec):
            continue
        if best is None or rec.get("k", 0) >= best.get("k", 0):
            best = rec
    if best is None:
        return None
    if "scope_table_error" in best:
        return {"error": best["scope_table_error"]}
    return {"scopes": best["scopes"], "moved": best.get("moved", {}),
            "mixed": best.get("mixed", {}),
            "containers": best.get("containers", [])}


def _self_times(events: list) -> list:
    """``(name, start, self_ns)`` of each event of one line: its
    duration less the union of the events nested inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    kids = [[] for _ in order]
    stack = []  # indices of events that may still hold later ones
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        for j in reversed(stack):  # the innermost event that holds it
            if order[j][1] + order[j][2] >= s + d:
                kids[j].append((s, s + d))
                break
        stack.append(i)
    out = []
    for (name, s, d), inner in zip(order, kids):
        covered = sum(e - b for b, e in xplane._union(inner))
        out.append((name, s, d - covered))
    return out


def reduce(planes: list, tab: dict, prefix: str) -> dict | None:
    """Per chip (averaged): the step program's runs in the window, its
    summed self time, and the self time by scope (``moved`` time in it),
    ``moved`` by scope, ``mixed``, ``containers`` and ``unnamed``;
    ``ops`` has each instruction's category and seconds.  None without a
    device plane."""
    devices = [p for p in planes
               if p["name"].startswith(xplane.DEVICE_PLANE_PREFIX)
               and any(ln["name"] == xplane.OPS_LINE for ln in p["lines"])]
    if not devices:
        return None
    win = [(s, s + d) for p in planes
           if p["name"].startswith(xplane.HOST_PLANE_PREFIX)
           for ln in p["lines"] for name, s, d in ln["events"]
           if name == xplane.WINDOW_SPAN]
    lo = min((s for s, _ in win), default=float("-inf"))
    hi = max((e for _, e in win), default=float("inf"))
    where = {name: scope for part in ("scopes", "moved")
             for scope, names in tab.get(part, {}).items()
             for name in names}
    moved = {name for names in tab.get("moved", {}).values()
             for name in names}
    where.update((name, "mixed") for name in tab["mixed"])
    where.update((name, "containers") for name in tab["containers"])
    seconds: dict = {}
    moved_s: dict = {}
    ops: dict = {}
    runs = joined = 0
    for p in devices:
        spans = sorted(
            (s, s + d) for ln in p["lines"]
            if ln["name"] == xplane.MODULES_LINE
            for name, s, d in ln["events"]
            if name.split("(")[0].startswith(prefix)
            and s + d > lo and s < hi)
        runs += len(spans)
        starts = [s for s, _ in spans]
        for ln in p["lines"]:
            if ln["name"] != xplane.OPS_LINE:
                continue
            for name, s, self_ns in _self_times(ln["events"]):
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= spans[i][1]:
                    continue  # another program's, or outside the window
                key = xplane._short(name)
                cat = where.get(key, "unnamed")
                joined += cat != "unnamed"
                seconds[cat] = seconds.get(cat, 0.0) + self_ns / 1e9
                if key in moved:
                    moved_s[cat] = moved_s.get(cat, 0.0) + self_ns / 1e9
                rec = ops.setdefault(key, [cat, 0.0])
                rec[1] += self_ns / 1e9
    if not joined:
        raise RuntimeError(
            f"the trace's {prefix!r} operations and the compiled step's "
            "scope table have no instruction in common: the naming broke")
    n = len(devices)
    return {"runs": runs / n,
            "total_s": sum(seconds.values()) / n,
            "scopes": {k: v / n for k, v in seconds.items()
                       if k not in ("mixed", "containers", "unnamed")},
            "moved": {k: v / n for k, v in moved_s.items()},
            "moved_s": sum(moved_s.values()) / n,
            "mixed_s": seconds.get("mixed", 0.0) / n,
            "containers_s": seconds.get("containers", 0.0) / n,
            "unnamed_s": seconds.get("unnamed", 0.0) / n,
            "ops": {k: [c, v / n] for k, (c, v) in sorted(
                ops.items(), key=lambda kv: -kv[1][1])}}


def for_run(run: dict) -> dict | None:
    """The reduction of this run's own trace by its own table, read
    once a process, with the table's scopes as ``listed``; None where
    either is missing."""
    path = run.get("trace_file")
    prefix = (run.get("counters") or {}).get("step_program_prefix")
    if not path or not os.path.isfile(path) or not prefix:
        return None
    tab = table(run.get("records"))
    if tab is None:
        return None
    if "error" in tab:
        raise RuntimeError(
            f"the compiled step's scope table was not made: {tab['error']}")
    if path not in _cache:
        red = reduce(xplane.load(path), tab, prefix)
        if red is not None:
            red["listed"] = set(tab["scopes"]).union(tab["moved"])
        _cache[path] = red
    return _cache[path]


def ms(run: dict, scopes) -> float | None:
    """Device milliseconds a run of the step program in ``scopes``; None
    where the table names none of them."""
    red = for_run(run)
    if not red or not red["runs"]:
        return None
    if not red["listed"].intersection(scopes):
        return None
    seconds = sum(red["scopes"].get(s, 0.0) for s in scopes)
    return 1e3 * seconds / red["runs"]


def pct(run: dict, part: str) -> float | None:
    """``unnamed``, ``mixed`` or ``moved`` self time over the step's, in
    percent."""
    red = for_run(run)
    if not red or not red["total_s"]:
        return None
    return 100.0 * red[part + "_s"] / red["total_s"]
