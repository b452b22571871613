"""Device milliseconds a run of the train-step program spends writing
the touched rows (scope ``tffm.apply_write``: whichever writer runs, the
stream writer's Mosaic call where kernels compile), self time by the
compiled step's own scope table (_scopes.py)."""

import _scopes


def read(run):
    return _scopes.ms(run, ("tffm.apply_write",))
