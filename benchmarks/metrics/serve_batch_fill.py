"""Filled over dispatched rung slots in the window, in percent: the
server's ``serve.examples`` counter over the slots its ``serve.batch_fill``
gauge implies."""


def read(run):
    c = run["counters"]
    if not c.get("serve_slots"):
        return None
    return 100.0 * c["serve_examples"] / c["serve_slots"]
