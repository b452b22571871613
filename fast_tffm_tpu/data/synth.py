"""Seeded synthetic CTR data: Zipf-skewed hashed ids as libsvm text.

The generator behind ``chip_smoke.py``'s train/validation/predict
files — the chip machine has no network and
``examples/data/`` is git-ignored, so real-width inputs are made from a
seed, in seconds (numpy only; no jax import).
"""

from __future__ import annotations

import os

import numpy as np

# Planted-label signal strength: each id carries a +-1 linear weight
# (a pure function of the id), logit = PLANT_SCALE * sum_j w[id_j] *
# val_j.  At 39 features the logit's std is ~2, so a model that learns
# only the hot ids' weights already sits well below ln 2.
PLANT_SCALE = 0.5


def zipf_ids(rng, shape, vocab: int) -> np.ndarray:
    """Zipf(1.1)-skewed ids hash-spread over the bucket space: realistic
    CTR duplicate structure (a few very hot ids) without clustering the
    hot ids into adjacent buckets."""
    z = rng.zipf(1.1, size=shape).astype(np.uint64)
    return ((z * np.uint64(0x9E3779B97F4A7C15)) % np.uint64(vocab)).astype(
        np.int32
    )


def planted_labels(rng, ids: np.ndarray, val4: np.ndarray) -> np.ndarray:
    """Bernoulli labels from a planted linear model over the ids, so a
    few training steps visibly beat logloss ln 2 (random labels cannot).
    ``val4`` are the values' four decimals as written ("0.%04d")."""
    sign = ((ids.astype(np.uint64) * np.uint64(0xD6E8FEB86659FD93))
            >> np.uint64(40)) & np.uint64(1)
    w = sign.astype(np.float64) * 2.0 - 1.0
    logit = PLANT_SCALE * (w * (val4 * 1e-4)).sum(axis=1)
    p = 1.0 / (1.0 + np.exp(-logit))
    return (rng.uniform(size=p.shape) < p).astype(np.int64)


def gen_libsvm_files(tmpdir: str, rng, n_files: int, lines_per_file: int,
                     n_feat: int, vocab: int, prefix: str = "bench",
                     planted: bool = False) -> list[str]:
    """Vectorized libsvm text generation: numpy bytes ops, pairwise-reduced
    concatenation (a left-fold over 39 growing columns copies quadratically;
    pure-Python per-token formatting would take minutes at multi-chip
    batch sizes).  ``planted`` draws the labels from :func:`planted_labels`
    instead of a fair coin."""
    paths = []
    for fi in range(n_files):
        ids = zipf_ids(rng, (lines_per_file, n_feat), vocab)
        # vals in [0.1, 1.0) with 4 decimals, formatted as "0.%04d".
        val4 = rng.integers(1000, 10000, size=(lines_per_file, n_feat))
        if planted:
            labels = planted_labels(rng, ids, val4)
        else:
            labels = rng.integers(0, 2, size=(lines_per_file,))
        cols = [labels.astype("S1")]
        for j in range(n_feat):
            cols.append(np.char.add(
                np.char.add(b" ", np.char.add(ids[:, j].astype("S10"), b":0.")),
                val4[:, j].astype("S4"),
            ))
        while len(cols) > 1:  # log-depth reduce
            nxt = [np.char.add(cols[i], cols[i + 1])
                   for i in range(0, len(cols) - 1, 2)]
            if len(cols) % 2:
                nxt.append(cols[-1])
            cols = nxt
        path = os.path.join(tmpdir, f"{prefix}_{fi}.libsvm")
        with open(path, "wb") as f:
            f.write(b"\n".join(cols[0]))
            f.write(b"\n")
        paths.append(path)
    return paths
