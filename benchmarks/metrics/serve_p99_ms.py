"""99th percentile of all the window's requests, timed from when each
was due (a failed one counts as slower than any).  It stands here and
not among the end-to-end metrics because it swings by a quarter from run
to run on unchanged code (PERF.md section 2): no bound could hold it."""


def read(run):
    lat = run["counters"].get("latency_ms")
    return lat["p99"] if lat else None
