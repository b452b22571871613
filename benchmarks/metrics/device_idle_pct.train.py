"""1 - union of the device-operation intervals over the traced window of
a training cell, in percent."""

import _trace


def read(run):
    return _trace.idle_pct(run)
