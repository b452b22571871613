"""Device milliseconds a run of the train-step program spends in the
forward row gather (scope ``tffm.row_gather``: ``params.table[ids]`` on
one chip, the masked ``table_l[lids]`` of a model shard), self time of
the operations the compiled step's own scope table names (_scopes.py)."""

import _scopes


def read(run):
    return _scopes.ms(run, ("tffm.row_gather",))
