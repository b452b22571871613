"""Host-side sort metadata must match the device-side prep exactly.

native.sort_meta re-derives, in C++, everything ops/sparse_apply._prep
computes from the batch ids on device (stable sort permutation, unique
positions, chunk/tile boundary metadata).  Both sorts are stable, so
every integer output — and therefore the K1/K2 numerics downstream —
must agree BIT-EXACTLY, not approximately.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.data import native
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.ops import sparse_apply

V, D = 2048, 9


def _device_meta(ids, vocab):
    """The device-side quantities, via the same code sort_meta mirrors."""
    g = jnp.zeros((ids.shape[0], D), jnp.float32)
    payload, upos, starts, firsts, ends, sidx, n_pad = sparse_apply._prep(
        jnp.asarray(ids), g, vocab
    )
    tile_start = sparse_apply._tile_starts(
        sidx, upos,
        jnp.arange(0, vocab + 1, sparse_apply.TILE, dtype=sidx.dtype),
    )
    # perm is recoverable from payload only indirectly; recompute it the
    # way _prep does.
    n = ids.shape[0]
    ids_pad = np.concatenate(
        [ids, np.full((n_pad - n,), vocab, ids.dtype)]
    )
    _, perm = jax.lax.sort_key_val(
        jnp.asarray(ids_pad), jnp.arange(n_pad, dtype=jnp.int32)
    )
    lrow_last = payload[:, 2 * D]  # the metadata column, pre-128-pad slot
    return {
        "perm": np.asarray(perm),
        "upos": np.asarray(upos),
        "lrow_last": np.asarray(lrow_last),
        "starts": np.asarray(starts),
        "firsts": np.asarray(firsts),
        "ends": np.asarray(ends),
        "tile_start": np.asarray(tile_start),
    }


def _ids(seed, n, hot=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n,)).astype(np.int32)
    if hot:
        ids[:hot] = 7  # a hot id spanning chunks
    return ids


@pytest.mark.parametrize(
    "n,hot",
    [
        (1200, 0),        # padded tail (n not a CHUNK multiple)
        (1024, 600),      # hot id spanning chunks, exact CHUNK multiple
        (4096, 1500),     # multiple chunks, duplicates everywhere
        (64, 64),         # single-id batch, heavy padding
    ],
)
def test_sort_meta_matches_device_prep(n, hot):
    ids = _ids(3, n, hot)
    meta = native.sort_meta(ids, V, sparse_apply.CHUNK, sparse_apply.TILE)
    dev = _device_meta(ids, V)
    for name in dev:
        np.testing.assert_array_equal(
            np.asarray(getattr(meta, name)), dev[name], err_msg=name
        )


@pytest.mark.parametrize("vocab", [1 << 13, 1 << 24])
def test_sort_meta_matches_device_prep_large_vocab(vocab):
    """Large vocabularies exercise the per-bucket low-bit sort passes
    (vocab 2^13: one cache-hot pass; 2^24: two, covering the ping-pong
    buffer normalization) — the default V=2048 cases have lo_bits == 0
    and skip that code entirely."""
    rng = np.random.default_rng(5)
    ids = rng.integers(0, vocab, (3000,)).astype(np.int32)
    ids[:800] = 123  # a hot id spanning chunks
    meta = native.sort_meta(ids, vocab, sparse_apply.CHUNK,
                            sparse_apply.TILE)
    dev = _device_meta(ids, vocab)
    for name in dev:
        np.testing.assert_array_equal(
            np.asarray(getattr(meta, name)), dev[name], err_msg=name
        )


def test_sort_meta_is_stable_for_duplicates():
    ids = np.asarray([5, 3, 5, 5, 3, 7], np.int32)
    meta = native.sort_meta(ids, V, sparse_apply.CHUNK, sparse_apply.TILE)
    n = len(ids)
    # Sorted order: 3(idx1), 3(idx4), 5(idx0), 5(idx2), 5(idx3), 7(idx5),
    # then sentinel slots in position order.
    expect = [1, 4, 0, 2, 3, 5] + list(range(n, sparse_apply.CHUNK))
    np.testing.assert_array_equal(meta.perm, expect)


def test_apply_with_meta_bit_identical():
    """Same stable order -> the kernels see identical inputs, so the
    host-meta path must reproduce the device-sort path bit for bit."""
    rng = np.random.default_rng(9)
    ids = _ids(9, 3000, hot=700)
    g = jnp.asarray(rng.uniform(-1, 1, (3000, D)), jnp.float32)
    table = jnp.asarray(rng.uniform(-1, 1, (V, D)), jnp.float32)
    acc = jnp.full((V, D), 0.1, jnp.float32)
    meta = native.sort_meta(ids, V, sparse_apply.CHUNK, sparse_apply.TILE)
    t0, a0 = sparse_apply.adagrad_apply(
        table, acc, jnp.asarray(ids), g, lr=0.1, eps=1e-7
    )
    t1, a1 = sparse_apply.adagrad_apply(
        table, acc, jnp.asarray(ids), g, lr=0.1, eps=1e-7, meta=meta
    )
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))


def test_meta_shape_drift_raises():
    ids = _ids(1, 1024)
    meta = native.sort_meta(ids, V, sparse_apply.CHUNK, sparse_apply.TILE)
    bad = meta._replace(tile_start=meta.tile_start[:-2])
    g = jnp.zeros((1024, D), jnp.float32)
    with pytest.raises(ValueError, match="sort_meta shapes"):
        sparse_apply.adagrad_apply(
            jnp.zeros((V, D), jnp.float32), jnp.zeros((V, D), jnp.float32),
            jnp.asarray(ids), g, lr=0.1, eps=1e-7, meta=bad,
        )


def test_trainer_attaches_meta_and_matches(tmp_path, monkeypatch):
    """Full sparse_step through the Trainer: host_sort on/off must agree
    bit-exactly, and the on path must actually attach meta.

    Pinned to a one-device mesh (the conftest's 8 virtual devices would
    select the sharded apply, where host meta deliberately stays off) —
    this mirrors a single-chip TPU run."""
    from jax.sharding import Mesh

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.parallel import mesh as mesh_lib
    from fast_tffm_tpu.train.loop import Trainer

    monkeypatch.setattr(
        mesh_lib, "make_mesh",
        lambda cfg, devices=None: Mesh(
            np.array(jax.devices()[:1]).reshape(1, 1),
            (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
        ),
    )
    rng = np.random.default_rng(4)
    B, F = 64, 8
    batch = Batch(
        labels=rng.integers(0, 2, (B,)).astype(np.float32),
        ids=rng.integers(0, V, (B, F)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, (B, F)).astype(np.float32),
        fields=np.zeros((B, F), np.int32),
        weights=np.ones((B,), np.float32),
    )
    states = {}
    for host_sort in (True, False):
        cfg = FmConfig(
            vocabulary_size=V, factor_num=D - 1, max_features=F,
            batch_size=B, learning_rate=0.1, sparse_apply="tile",
            host_sort=host_sort,
            model_file=str(tmp_path / f"m{int(host_sort)}"),
        )
        tr = Trainer(cfg)
        put = tr._put(batch)
        assert (put.sort_meta is not None) == host_sort
        tr.state = tr._train_step(tr.state, put)
        states[host_sort] = np.asarray(tr.state.params.table)
    np.testing.assert_array_equal(states[True], states[False])


def test_pipeline_workers_attach_meta(tmp_path):
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.pipeline import BatchPipeline

    path = tmp_path / "data.libsvm"
    rng = np.random.default_rng(0)
    lines = [
        "1 " + " ".join(
            f"{rng.integers(0, V)}:0.5" for _ in range(4)
        )
        for _ in range(32)
    ]
    path.write_text("\n".join(lines) + "\n")
    cfg = FmConfig(
        vocabulary_size=V, factor_num=D - 1, max_features=8, batch_size=16,
    )
    spec = (V, sparse_apply.CHUNK, sparse_apply.TILE)
    batches = list(BatchPipeline(
        [str(path)], cfg, epochs=1, shuffle=False, sort_meta_spec=spec
    ))
    assert batches and all(b.sort_meta is not None for b in batches)
    b = batches[0]
    dev = _device_meta(b.ids.reshape(-1), V)
    np.testing.assert_array_equal(b.sort_meta.perm, dev["perm"])


@pytest.mark.parametrize("bad_id", [-1, V, V + 17, np.iinfo(np.int32).min])
def test_sort_meta_rejects_out_of_range_ids(bad_id):
    """An id outside [0, vocab) must fail loud (-1 -> ValueError), never
    index the native histogram/scatter out of bounds.  The normal parser
    mods ids into range, but sort_meta is also called on arbitrary
    Batch.ids via Trainer._put."""
    ids = _ids(2, 1024)
    ids[37] = bad_id
    with pytest.raises(ValueError, match="out-of-range"):
        native.sort_meta(ids, V, sparse_apply.CHUNK, sparse_apply.TILE)


def test_pipeline_worker_sort_meta_failure_degrades(tmp_path, monkeypatch):
    """A sort_meta failure inside a pipeline worker must degrade to the
    device-sort path (sort_meta=None + one warning), not kill the epoch —
    the same contract Trainer._put documents for its own fallback."""
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data import native as native_mod
    from fast_tffm_tpu.data.pipeline import BatchPipeline

    path = tmp_path / "data.libsvm"
    rng = np.random.default_rng(1)
    lines = [
        "1 " + " ".join(f"{rng.integers(0, V)}:0.5" for _ in range(4))
        for _ in range(32)
    ]
    path.write_text("\n".join(lines) + "\n")
    cfg = FmConfig(
        vocabulary_size=V, factor_num=D - 1, max_features=8, batch_size=16,
    )

    def boom(*a, **kw):
        raise ValueError("injected sort_meta failure")

    monkeypatch.setattr(native_mod, "sort_meta", boom)
    spec = (V, sparse_apply.CHUNK, sparse_apply.TILE)
    batches = list(BatchPipeline(
        [str(path)], cfg, epochs=1, shuffle=False, sort_meta_spec=spec
    ))
    assert len(batches) == 2  # the epoch completed
    assert all(b.sort_meta is None for b in batches)
