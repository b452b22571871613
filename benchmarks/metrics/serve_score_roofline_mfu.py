"""The scoring program's share of the chip's peak: the least time the
chip could take for what scoring one request NEEDS (fmbench/roofline.py:
its unique rows read once, ids and values read, one score written; the
interaction's flops) over the rung program's measured device time per
run, in percent.  Read only where a request is one rung (the bulk mix)."""

import _trace
from fmbench import peaks


def read(run):
    p = _trace.program(run, "rung_program_prefix")
    c = run["counters"]
    if not p or not p["runs"] or not c.get("request_needed_bytes"):
        return None
    least = peaks.least_seconds(c["request_needed_flops"],
                                c["request_needed_bytes"], run["peaks"])
    return 100.0 * least["seconds"] / (p["seconds"] / p["runs"])
