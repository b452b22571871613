"""Mean milliseconds of a ``tffm:serve.readback`` span: the blocking
read of a rung's scores (the rung itself and its D2H)."""

import _spans


def read(run):
    return _spans.mean_ms(run, "readback")
