"""The writer's share of its roofline: the least time the chip could take
for the apply's own counted work -- every unique row's weights and
optimizer rows read and written once, the optimizer's update on each of
its coordinates (``counters["apply_needed_bytes"]`` /
``["apply_needed_flops"]``, the driver's count: fmbench/roofline_ftrl.py
in the FTRL cell) -- over the device milliseconds a run of the step
spends under ``tffm.apply_write`` (self time by the compiled step's own
scope table, _scopes.py), in percent.  None where the driver counts no
apply or the run has no scope table."""

import _scopes
from fmbench import peaks


def read(run):
    c = run["counters"]
    if not c.get("apply_needed_bytes"):
        return None
    ms = _scopes.ms(run, ("tffm.apply_write",))
    if not ms:
        return None
    least = peaks.least_seconds(c["apply_needed_flops"],
                                c["apply_needed_bytes"], run["peaks"])
    return 100.0 * least["seconds"] / (ms / 1e3)
