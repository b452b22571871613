"""1 - union of the device-operation intervals over the traced part of a
serving window, in percent."""

import _trace


def read(run):
    return _trace.idle_pct(run)
