"""Share of the train-step program's self time in operations its own
scope table does not name (_scopes.py), in percent: what the program's
scopes miss."""

import _scopes


def read(run):
    return _scopes.pct(run, "unnamed")
