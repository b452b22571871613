"""Device milliseconds a run of the train-step program spends on the
entries exchange's own work (scopes ``tffm.exchange``: first-stage
dedup, the predicate, the rung's gathers; ``tffm.exchange_merge``: the
second sort, payload and K1 of the merge), collectives included, self
time by the compiled step's own scope table (_scopes.py).  None where
the step has no exchange."""

import _scopes


def read(run):
    return _scopes.ms(run, _scopes.EXCHANGE)
