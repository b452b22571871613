"""Mean host milliseconds per dispatch in the window: the program's
``train.dispatch`` timer (enqueue plus any block on the device)."""


def read(run):
    c = run["counters"]
    if not c.get("dispatch_count"):
        return None
    return 1e3 * c["dispatch_s"] / c["dispatch_count"]
