"""What ONE CHIP of a (data x model) mesh needs for a step of the sharded
FM training job, from shapes and the batch's own ids -- never from how
the step is implemented (``fmbench/roofline.py`` counts the same step
for one device holding everything).

The table rests row-sharded over the model axis and replicated over the
data axis; the batch is split over the data axis.  A chip (d, m):

* gathers, once each, the rows of ITS model shard that ITS data shard's
  examples touch;
* reads and writes, table and accumulator, every row of its model shard
  that the GLOBAL batch touches (each data replica of a shard applies
  the whole batch's update to its own copy);
* reads its data shard of the batch;
* does its share of the interaction's arithmetic: a shard's partial
  terms over its data shard's examples, 1 / chips of the whole.

The exchange between chips is not HBM traffic.  What it needs is counted
apart: the entries of the OTHER data shards that land in the chip's model
shard, a row id and the two sums a row, over the chip's published
inter-chip interconnect.
"""

import numpy as np

from fmbench import roofline

# Google Cloud documentation, "TPU v5e": inter-chip interconnect (ICI)
# bandwidth 1,600 Gbit/s per chip -- the whole chip's figure, all links,
# so a share of it cannot pass 100% whichever links a collective uses.
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def shard_counts(ids: np.ndarray, vocab: int, data_shards: int,
                 model_shards: int) -> dict:
    """Per chip, from one step's hashed ``ids [B, F]`` (examples split
    over the data axis in order, rows over the model axis in order), the
    mean over the chips of: ``gather_unique`` (distinct rows of its model
    shard in its data shard's examples), ``apply_unique`` (distinct rows
    of its model shard in the whole batch), ``foreign_entries`` (distinct
    rows of its model shard in each OTHER data shard's examples, summed:
    what the exchange has to bring it)."""
    b = ids.shape[0]
    if b % data_shards or vocab % model_shards:
        raise ValueError("the batch and the table split evenly over the mesh")
    rows_local = vocab // model_shards
    per_data = [np.unique(part) for part in
                np.split(ids.reshape(b, -1), data_shards)]
    gather, apply_, foreign = [], [], []
    for m in range(model_shards):
        lo, hi = m * rows_local, (m + 1) * rows_local
        mine = [u[(u >= lo) & (u < hi)] for u in per_data]
        whole = len(np.unique(np.concatenate(mine)))
        for d in range(data_shards):
            gather.append(len(mine[d]))
            apply_.append(whole)
            foreign.append(sum(len(x) for j, x in enumerate(mine) if j != d))
    return {"gather_unique": float(np.mean(gather)),
            "apply_unique": float(np.mean(apply_)),
            "foreign_entries": float(np.mean(foreign))}


def train_step_needed(n: int, f: int, k: int, counts: dict,
                      data_shards: int, model_shards: int) -> dict:
    """One chip's HBM bytes and flops for one step on a global batch of
    ``n`` examples (``counts`` from :func:`shard_counts`)."""
    rb = roofline.row_bytes(k)
    n_local = n // data_shards
    gather = counts["gather_unique"] * rb
    apply_ = counts["apply_unique"] * rb * 4  # table r+w, accumulator r+w
    batch = n_local * f * (4 + roofline.F32) + n_local * 2 * roofline.F32
    flops = ((roofline.fm_forward_flops(n, f, k)
              + roofline.fm_backward_flops(n, f, k))
             / (data_shards * model_shards)
             + counts["apply_unique"] * (1 + k) * 6)
    return {"bytes": gather + apply_ + batch, "flops": flops}


def exchange_needed_bytes(counts: dict, k: int) -> float:
    """Bytes the exchange has to bring one chip a step: a row id and
    ``(sum g, sum g^2)`` for every entry of the other data shards in its
    model shard."""
    return counts["foreign_entries"] * (4 + 2 * roofline.row_bytes(k))


def least_exchange_seconds(nbytes: float, device_kind: str) -> float:
    try:
        return nbytes / ICI_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} has no interconnect peak in "
            f"roofline_mesh.ICI_BYTES_PER_S ({sorted(ICI_BYTES_PER_S)}); "
            "add it with its published source") from None
