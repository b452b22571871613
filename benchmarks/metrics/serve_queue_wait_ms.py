"""Mean milliseconds a request of the window waited in the batcher's
queue, submit to picked: the ``qwait_us`` stat over the ``reqs`` stat of
the ``tffm:serve.fill`` spans."""

import _spans


def read(run):
    spans = _spans.for_run(run)
    fill = spans and spans["phases"].get("fill")
    if not fill or not fill["stats"].get("reqs"):
        return None
    return 1e-3 * fill["stats"].get("qwait_us", 0) / fill["stats"]["reqs"]
