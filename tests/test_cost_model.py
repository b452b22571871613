"""Per-step communication cost model, measured from the traced jaxpr.

The reference's PS design moved only the rows a batch touched
(IndexedSlices push, SURVEY.md §3.2), so its per-step network traffic
scaled with the batch, not the vocabulary.  These tests pin the same
property onto the rebuild: the shardmap step's collective bytes are
extracted by walking the actual jaxpr (not a hand-maintained formula),
so any regression that reintroduces a vocab-proportional exchange in
entries mode fails here on CPU — no hardware needed.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import shardmap_step, sparse as sparse_lib

_COLLECTIVES = ("psum", "all_gather", "reduce_scatter", "all_to_all",
                "ppermute")


def _walk_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from _walk_jaxprs(inner)
                elif hasattr(v, "eqns"):
                    yield from _walk_jaxprs(v)


def collective_bytes(fn, *args) -> dict:
    """Total operand bytes per collective primitive in fn's jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    out: dict = {}
    for j in _walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if any(name.startswith(c) for c in _COLLECTIVES):
                nbytes = sum(
                    int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                    for v in eqn.invars
                    if hasattr(v.aval, "shape")
                )
                out[name] = out.get(name, 0) + nbytes
    return out


def _step_bytes(vocab: int, exchange: str, mesh) -> int:
    cfg = FmConfig(
        vocabulary_size=vocab, factor_num=8, max_features=8, batch_size=64,
        optimizer="adagrad", learning_rate=0.05, lookup="shardmap",
        sparse_exchange=exchange,
    )
    rng = np.random.default_rng(0)
    batch = Batch(
        labels=rng.integers(0, 2, 64).astype(np.float32),
        ids=rng.integers(0, vocab, (64, 8)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, (64, 8)).astype(np.float32),
        fields=np.zeros((64, 8), np.int32),
        weights=np.ones((64,), np.float32),
    )
    batch = jax.tree.map(jnp.asarray, batch)
    params = fm.init_params(jax.random.PRNGKey(0), cfg)
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    per_prim = collective_bytes(
        lambda p, o, b: shardmap_step.sparse_step_shardmap(
            cfg, p, o, b, mesh
        ),
        params, opt, batch,
    )
    return sum(per_prim.values())


def _mesh(shape):
    devs = np.array(jax.devices()[:shape[0] * shape[1]]).reshape(shape)
    return Mesh(devs, (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))


def test_entries_comms_independent_of_vocab():
    """Entries mode: growing the vocabulary 16x must not change per-step
    collective bytes (batch-proportional).  Dense mode: grows ~16x."""
    mesh = _mesh((2, 4))
    v_small, v_big = 1 << 14, 1 << 18
    e_small = _step_bytes(v_small, "entries", mesh)
    e_big = _step_bytes(v_big, "entries", mesh)
    d_small = _step_bytes(v_small, "dense", mesh)
    d_big = _step_bytes(v_big, "dense", mesh)
    assert e_small == e_big, (e_small, e_big)
    # Dense delta dominates: bytes scale with vocab.
    assert d_big > 8 * d_small, (d_small, d_big)
    # At the large vocab the entries exchange is far cheaper.
    assert e_big * 4 < d_big, (e_big, d_big)


def test_auto_exchange_picks_by_bytes():
    """auto == dense at small vocab / large batch, entries at large
    vocab / small batch — whichever the ring-traffic model favors."""
    mesh = _mesh((2, 4))
    small = FmConfig(
        vocabulary_size=1 << 10, factor_num=8, max_features=8,
        batch_size=64, lookup="shardmap",
    )
    big = FmConfig(
        vocabulary_size=1 << 22, factor_num=8, max_features=8,
        batch_size=64, lookup="shardmap",
    )
    n_occ = 64 // 2 * 8  # per-device occurrences on the (2, 4) mesh
    assert sparse_lib.resolve_exchange(small, mesh, n_occ) == "dense"
    assert sparse_lib.resolve_exchange(big, mesh, n_occ) == "entries"
    forced = FmConfig(**{**small.__dict__, "sparse_exchange": "entries",
                         "train_files": [], "weight_files": [],
                         "validation_files": [], "predict_files": []})
    assert sparse_lib.resolve_exchange(forced, mesh, n_occ) == "entries"


def test_auto_exchange_allreduce_weighting():
    """Pin the corrected crossover (ADVICE r5): a ring all-reduce moves
    ~2x its buffer per device, so the dense side weighs double.  Shapes
    in the band between V*2D and 2*V*2D (where the old, unweighted
    comparison picked 'dense') must now resolve to 'entries'.

    S=2, vocab_local=1024, d=9, 512-entry cap:
      entries ring words (per (S-1)): S*cap*(2d+1)  = 2*512*19 = 19456
      old dense words:                V*2d          = 1024*18  = 18432
      corrected dense words:          2*V*2d        = 36864
    """
    assert sparse_apply.resolve_exchange(
        "auto", n_local_occ=512, vocab_local=1024, d=9, data_shards=2,
    ) == "entries"
    # Just past the corrected crossover (entries words > 2*V*2D) the pick
    # flips back to dense: same cap against a quarter of the vocab.
    assert sparse_apply.resolve_exchange(
        "auto", n_local_occ=512, vocab_local=256, d=9, data_shards=2,
    ) == "dense"


def test_entries_cap_is_batch_bounded():
    """The static exchange capacity scales with occurrences, not vocab."""
    c1 = sparse_apply.entries_cap(1000, 1 << 20)
    c2 = sparse_apply.entries_cap(1000, 1 << 28)
    assert c1 == c2  # vocab-independent once vocab > batch
    assert c1 <= -(-1000 // sparse_apply.CHUNK) * sparse_apply.CHUNK
    # Tiny vocab range bounds it the other way.
    assert sparse_apply.entries_cap(10_000, 512) <= max(
        512, sparse_apply.CHUNK
    )


def test_compact_k2_grid_scales_with_entries_not_vocab():
    """Compact K2's grid (== streamed table blocks) is bounded by the
    entry count: the streaming analogue of the comms property.  Verified
    from the traced pallas_call grid, not a formula."""

    def grid_of(vocab, n_ids):
        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, vocab, n_ids), np.int32
        )
        g = jnp.ones((n_ids, 9), jnp.float32)
        table = jnp.zeros((vocab, 9), jnp.float32)
        closed = jax.make_jaxpr(
            lambda t, i, gg: sparse_apply.sgd_apply(
                t, i, gg, lr=0.1, compact=True
            )
        )(table, ids, g)
        grids = []
        for j in _walk_jaxprs(closed.jaxpr):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    gm = eqn.params.get("grid_mapping")
                    if gm is not None and len(gm.grid) == 1:
                        grids.append(gm.grid[0])
        # K1 + K2 both present; K2 is the table-streaming one (max grid
        # in the full-stream case, but under compact it is the one whose
        # grid is NOT the K1 chunk grid).
        return grids

    # 200 ids -> n_pad 512 entries; V=2^21 has 1024 groups of 8x256 rows,
    # so compact must engage (t_max = 512 < 1024) and the K2 grid — the
    # number of table blocks streamed — is the ENTRY bound, not the
    # vocab bound.
    vocab = 1 << 21
    grids = grid_of(vocab, 200)
    group = sparse_apply._group_for(vocab // sparse_apply.TILE)
    n_groups = vocab // (sparse_apply.TILE * group)
    assert n_groups not in grids, (grids, n_groups)  # vocab bound gone
    assert 512 in grids, grids  # the entry-bounded K2 grid
    # Growing the vocab 4x leaves the K2 grid unchanged (entry-bounded).
    grids4 = grid_of(vocab * 4, 200)
    assert 512 in grids4, grids4
    n_groups4 = (vocab * 4) // (sparse_apply.TILE * sparse_apply._group_for(
        (vocab * 4) // sparse_apply.TILE))
    assert n_groups4 not in grids4, (grids4, n_groups4)


def test_auto_exchange_pure_model_parallel():
    """With one data shard nothing is exchanged either way; auto must
    pick entries (its fast path is the plain K1+K2 apply, strictly less
    work than a dense delta materialization)."""
    assert sparse_apply.resolve_exchange(
        "auto", n_local_occ=10_000, vocab_local=1 << 12, d=9,
        data_shards=1,
    ) == "entries"
    # Same shapes with real data sharding still favor dense.
    assert sparse_apply.resolve_exchange(
        "auto", n_local_occ=10_000, vocab_local=1 << 12, d=9,
        data_shards=4,
    ) == "dense"
