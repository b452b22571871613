"""The whole training step's share of the chip's peak: the least time the
chip could take for the work the ALGORITHM needs (fmbench/roofline.py:
unique rows gathered once, table and accumulator rows read and written
once, the batch read; interaction flops) over the step program's measured
device time, in percent.  Bound by bytes at these shapes."""

import _trace
from fmbench import peaks


def read(run):
    p = _trace.program(run, "step_program_prefix")
    c = run["counters"]
    if not p or not p["runs"] or not c.get("step_needed_bytes"):
        return None
    least = peaks.least_seconds(c["step_needed_flops"],
                                c["step_needed_bytes"], run["peaks"])
    return 100.0 * least["seconds"] / (p["seconds"] / p["runs"])
