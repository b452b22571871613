"""Device milliseconds of the train-step program per dispatch, from the
trace's per-program line."""

import _trace


def read(run):
    p = _trace.program(run, "step_program_prefix")
    if not p or not p["runs"]:
        return None
    return 1e3 * p["seconds"] / p["runs"]
