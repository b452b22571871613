"""Device milliseconds a run of the train-step program spends in the
interaction, forward and closed-form backward (scopes
``tffm.fm_interaction_fwd`` / ``_bwd``, ``tffm.ffm_interaction_fwd`` /
``_bwd``; on a mesh the partial terms and their model-axis psum), self
time by the compiled step's own scope table (_scopes.py)."""

import _scopes


def read(run):
    return _scopes.ms(run, _scopes.INTERACTION)
