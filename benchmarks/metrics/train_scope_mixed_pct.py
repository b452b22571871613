"""Share of the train-step program's self time in fusions whose members
carry more than one scope (the table's ``mixed``; _scopes.py), in
percent: time no single scope's metric can claim."""

import _scopes


def read(run):
    return _scopes.pct(run, "mixed")
