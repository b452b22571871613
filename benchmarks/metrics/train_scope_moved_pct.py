"""Share of the train-step program's self time in what the compiler made
itself (layout and asynchronous copies, the loops that move buffers),
which the scope table gives the scope of the data it moves (the table's
``moved``, _scopes.py), in percent: the time the scope metrics hold by
inference, not by the program's own names.  With
``train_scope_unnamed_pct`` it is what would be unnamed without that
rule."""

import _scopes


def read(run):
    return _scopes.pct(run, "moved")
