"""Shared on-chip timing helpers for the tools/ scripts.

The ONE copy of the scalar-readback protocol: completion is forced by
fetching one scalar from every output leaf (``block_until_ready`` is an
equally valid barrier on a local chip).
"""

from __future__ import annotations

import time

import numpy as np


def drain(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        np.asarray(jax.device_get(
            leaf.reshape(-1)[:1] if hasattr(leaf, "reshape") else leaf
        ))


def bench(fn, *args, steps=20):
    for _ in range(2):
        drain(fn(*args))
    t0 = time.perf_counter()
    r = None
    for _ in range(steps):
        r = fn(*args)
    drain(r)
    return (time.perf_counter() - t0) * 1e3 / steps
