"""The dispatcher thread's work phases (fill, launch, readback, deliver,
quality; not its own wait, coalesce) over the traced part of a serving
window, in percent."""

import _spans


def read(run):
    return _spans.dispatcher_busy_pct(run)
