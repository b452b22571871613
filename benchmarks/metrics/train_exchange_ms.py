"""Device milliseconds a chip spends in collective operations per
training step (fmbench/xplane_collectives.py over the run's own trace:
all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute;
an asynchronous pair counted once, start to done), from the driver's
counters.  None where the program has no such step (one chip)."""


def read(run):
    c = run["counters"]
    if c.get("collective_s_per_step") is None:
        return None
    return 1e3 * c["collective_s_per_step"]
