"""The exchange's share of the chip's interconnect peak: the least time
the REAL merged entries of the other data shards (a row id and the two
sums a row, fmbench/roofline_mesh.py) need at the chip's published
inter-chip bandwidth, over the device time the chip spent in collective
operations that step, in percent.  The collectives also carry the
model axis's partial terms and the capacity's padding, so the share says
how far the exchange is from moving only what it must."""

from fmbench import roofline_mesh


def read(run):
    c = run["counters"]
    spent = c.get("collective_s_per_step")
    if not spent or c.get("exchange_needed_bytes") is None:
        return None
    least = roofline_mesh.least_exchange_seconds(
        c["exchange_needed_bytes"], c["device_kind"])
    return 100.0 * least / spent
