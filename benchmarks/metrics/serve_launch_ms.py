"""Mean milliseconds of a ``tffm:serve.launch`` span: the compiled rung
called until it returns (implicit H2D of the numpy arguments, enqueue)."""

import _spans


def read(run):
    return _spans.mean_ms(run, "launch")
