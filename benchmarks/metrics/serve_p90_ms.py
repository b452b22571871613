"""90th percentile of all the window's requests, timed from when each
was due: the steadier neighbour of ``serve_p99_ms``."""


def read(run):
    lat = run["counters"].get("latency_ms")
    return lat["p90"] if lat else None
