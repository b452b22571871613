"""Shared by the readers that take their number from the device trace."""


def program(run, prefix_key):
    """Seconds and runs of the jitted program whose name starts with the
    driver's ``counters[prefix_key]``, or None."""
    trace, prefix = run.get("trace"), run["counters"].get(prefix_key)
    if not trace or not prefix:
        return None
    hits = [v for k, v in trace["programs"].items() if k.startswith(prefix)]
    if not hits:
        return None
    return {"seconds": sum(h["seconds"] for h in hits),
            "runs": sum(h["runs"] for h in hits)}


def idle_pct(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
