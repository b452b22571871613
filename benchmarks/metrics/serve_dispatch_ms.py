"""Mean milliseconds of the scorer's ``serve.dispatch`` timer in the
window: one rung call and the blocking read of its scores."""


def read(run):
    c = run["counters"]
    if not c.get("serve_dispatch_count"):
        return None
    return 1e3 * c["serve_dispatch_s"] / c["serve_dispatch_count"]
