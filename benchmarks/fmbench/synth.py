"""Seeded synthetic CTR data: Zipf-skewed ids as libsvm text.

A COPY of ``fast_tffm_tpu/data/synth.py`` (PR 23), kept with the
benchmark so that a later PR may change the program's generator and
never the yardstick's.  numpy only.
"""

from __future__ import annotations

import numpy as np

# Planted-label signal strength: each id carries a +-1 linear weight (a
# pure function of the id), logit = PLANT_SCALE * sum_j w[id_j] * val_j.
PLANT_SCALE = 0.5


def zipf_ids(rng, shape, vocab: int) -> np.ndarray:
    """Zipf(1.1)-skewed ids spread over the bucket space: realistic CTR
    duplicate structure (a few very hot ids) without clustering the hot
    ids into adjacent buckets."""
    z = rng.zipf(1.1, size=shape).astype(np.uint64)
    return ((z * np.uint64(0x9E3779B97F4A7C15)) % np.uint64(vocab)).astype(
        np.int32
    )


def val4(rng, shape) -> np.ndarray:
    """Feature values as their four decimals: "0.%04d" in [0.1, 1.0)."""
    return rng.integers(1000, 10000, size=shape)


def planted_labels(rng, ids: np.ndarray, v4: np.ndarray) -> np.ndarray:
    sign = ((ids.astype(np.uint64) * np.uint64(0xD6E8FEB86659FD93))
            >> np.uint64(40)) & np.uint64(1)
    w = sign.astype(np.float64) * 2.0 - 1.0
    logit = PLANT_SCALE * (w * (v4 * 1e-4)).sum(axis=1)
    p = 1.0 / (1.0 + np.exp(-logit))
    return (rng.uniform(size=p.shape) < p).astype(np.int64)


def libsvm_lines(labels, ids: np.ndarray, v4: np.ndarray) -> np.ndarray:
    """``label id:0.dddd ...`` per row, as a numpy bytes array
    (vectorized: per-token Python formatting takes minutes at these
    sizes).  ``labels`` None writes the label 0."""
    n, f = ids.shape
    if labels is None:
        labels = np.zeros((n,), np.int64)
    cols = [labels.astype("S1")]
    for j in range(f):
        cols.append(np.char.add(
            np.char.add(b" ", np.char.add(ids[:, j].astype("S10"), b":0.")),
            v4[:, j].astype("S4"),
        ))
    while len(cols) > 1:  # log-depth reduce: a left fold copies quadratically
        nxt = [np.char.add(cols[i], cols[i + 1])
               for i in range(0, len(cols) - 1, 2)]
        if len(cols) % 2:
            nxt.append(cols[-1])
        cols = nxt
    return cols[0]


def write_libsvm(path: str, labels, ids, v4) -> None:
    with open(path, "wb") as f:
        f.write(b"\n".join(libsvm_lines(labels, ids, v4)))
        f.write(b"\n")
