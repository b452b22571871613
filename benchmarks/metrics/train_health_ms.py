"""Device milliseconds a run of the train-step program spends on the
training-health monitors (scope ``tffm.health``: the ``rows_touched``
scatter, the counters, the gradient norm and its non-finite count), self
time by the compiled step's own scope table (_scopes.py)."""

import _scopes


def read(run):
    return _scopes.ms(run, ("tffm.health",))
