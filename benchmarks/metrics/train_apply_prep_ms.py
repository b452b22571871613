"""Device milliseconds a run of the train-step program spends preparing
the one-device apply (scope ``tffm.apply_prep``: the occurrence stream,
sort, payload, K1), self time by the compiled step's own scope table
(_scopes.py)."""

import _scopes


def read(run):
    return _scopes.ms(run, ("tffm.apply_prep",))
