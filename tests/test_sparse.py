"""Sparse row-update path: parity with the dense optax path + semantics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import sparse
from fast_tffm_tpu.train.loop import Trainer


def _unique_batch(rng, cfg, batch_size):
    """Batch with globally unique ids: sparse == dense exactly."""
    total = batch_size * cfg.max_features
    ids = rng.permutation(cfg.vocabulary_size)[:total]
    return Batch(
        labels=rng.integers(0, 2, size=(batch_size,)).astype(np.float32),
        ids=ids.reshape(batch_size, cfg.max_features).astype(np.int32),
        vals=rng.uniform(0.1, 1.0,
                         size=(batch_size, cfg.max_features)).astype(np.float32),
        fields=np.zeros((batch_size, cfg.max_features), np.int32),
        weights=np.ones((batch_size,), np.float32),
    )


def _dup_batch(rng, cfg, batch_size):
    return Batch(
        labels=rng.integers(0, 2, size=(batch_size,)).astype(np.float32),
        ids=rng.integers(0, cfg.vocabulary_size,
                         size=(batch_size, cfg.max_features)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0,
                         size=(batch_size, cfg.max_features)).astype(np.float32),
        fields=np.zeros((batch_size, cfg.max_features), np.int32),
        weights=np.ones((batch_size,), np.float32),
    )


def _cfg(tmp_path, name, **kw):
    defaults = dict(
        vocabulary_size=4096, factor_num=4, max_features=8, batch_size=32,
        model_file=str(tmp_path / name), log_steps=0, learning_rate=0.1,
        factor_lambda=0.001, bias_lambda=0.001,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


@pytest.mark.parametrize("optimizer", ["adagrad", "sgd"])
def test_sparse_matches_dense_on_unique_ids(tmp_path, optimizer):
    """With no duplicate ids in the batch, sparse and dense updates are the
    same math — tables must match to float tolerance."""
    rng = np.random.default_rng(0)
    cfg_s = _cfg(tmp_path, "s", optimizer=optimizer, sparse_update=True)
    cfg_d = _cfg(tmp_path, "d", optimizer=optimizer, sparse_update=False)
    batches = [_unique_batch(rng, cfg_s, cfg_s.batch_size) for _ in range(3)]

    ts = Trainer(cfg_s)
    td = Trainer(cfg_d)
    assert ts.sparse and not td.sparse
    for b in batches:
        ts.state = ts._train_step(ts.state, ts._put(b))
        td.state = td._train_step(td.state, td._put(b))

    np.testing.assert_allclose(
        np.asarray(ts.state.params.table), np.asarray(td.state.params.table),
        rtol=1e-4, atol=1e-6,
    )
    np.testing.assert_allclose(
        float(ts.state.params.w0), float(td.state.params.w0), rtol=1e-4,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        float(ts.state.metrics.loss_sum), float(td.state.metrics.loss_sum),
        rtol=1e-4,
    )


def test_sparse_ftrl_runs_and_learns(tmp_path):
    rng = np.random.default_rng(1)
    cfg = _cfg(tmp_path, "f", optimizer="ftrl", ftrl_l1=0.001)
    t = Trainer(cfg)
    assert t.sparse
    losses = []
    for _ in range(20):
        b = _dup_batch(rng, cfg, cfg.batch_size)
        # Plant an easy signal: label = 1 iff first feature value > 0.5.
        b = b._replace(labels=(b.vals[:, 0] > 0.55).astype(np.float32))
        t.state = t._train_step(t.state, t._put(b))
        losses.append(float(t.state.metrics.loss_sum))
    # Loss sum grows sub-linearly (per-batch loss decreasing).
    first = losses[4]
    last = losses[-1] - losses[-6]
    assert last < first


def test_sparse_only_touches_batch_rows(tmp_path):
    rng = np.random.default_rng(2)
    cfg = _cfg(tmp_path, "t", optimizer="adagrad")
    t = Trainer(cfg)
    before = np.asarray(t.state.params.table).copy()
    b = _dup_batch(rng, cfg, cfg.batch_size)
    t.state = t._train_step(t.state, t._put(b))
    after = np.asarray(t.state.params.table)
    touched = np.unique(b.ids)
    untouched = np.setdiff1d(np.arange(cfg.vocabulary_size), touched)
    np.testing.assert_array_equal(before[untouched], after[untouched])
    assert np.any(before[touched] != after[touched])


def test_sparse_duplicate_id_semantics(tmp_path):
    """Duplicates: accumulator gets each occurrence's g^2; update uses the
    shared post-update denominator (documented IndexedSlices semantics)."""
    cfg = FmConfig(
        vocabulary_size=8, factor_num=2, max_features=2, batch_size=1,
        learning_rate=0.1, optimizer="adagrad", sparse_update=True,
        adagrad_initial_accumulator=0.1, model_file="/tmp/unused_dup",
    )
    params = jax.tree.map(
        jnp.asarray,
        __import__("fast_tffm_tpu.models.fm", fromlist=["fm"]).FmParams(
            w0=jnp.zeros(()),
            table=jnp.ones((8, 3)) * 0.1,
        ),
    )
    opt = sparse.init_sparse_opt_state(cfg, params)
    batch = Batch(
        labels=np.array([1.0], np.float32),
        ids=np.array([[3, 3]], np.int32),  # same id twice
        vals=np.array([[1.0, 2.0]], np.float32),
        fields=np.zeros((1, 2), np.int32),
        weights=np.ones((1,), np.float32),
    )
    before = np.asarray(params.table).copy()
    p2, o2, scores = jax.jit(
        lambda p, o, b: sparse.sparse_step(cfg, p, o, b)
    )(params, opt, batch)
    # Accumulator for row 3 = init + g1^2 + g2^2 (elementwise).
    acc3 = np.asarray(o2.acc.table[3])
    assert np.all(acc3 > cfg.adagrad_initial_accumulator)
    # Row 3 changed; all other rows untouched.
    after = np.asarray(p2.table)
    assert np.any(after[3] != before[3])
    for r in [0, 1, 2, 4, 5, 6, 7]:
        np.testing.assert_array_equal(after[r], before[r])


def test_sparse_ftrl_matches_dense_on_unique_ids(tmp_path):
    """Duplicate-free batches: sparse FTRL == dense optax-path FTRL."""
    rng = np.random.default_rng(4)
    kw = dict(optimizer="ftrl", ftrl_l1=0.001, ftrl_l2=0.001,
              learning_rate=0.1)
    cfg_s = _cfg(tmp_path, "fs", sparse_update=True, **kw)
    cfg_d = _cfg(tmp_path, "fd", sparse_update=False, **kw)
    batches = [_unique_batch(rng, cfg_s, cfg_s.batch_size) for _ in range(3)]
    ts, td = Trainer(cfg_s), Trainer(cfg_d)
    assert ts.sparse and not td.sparse
    for b in batches:
        ts.state = ts._train_step(ts.state, ts._put(b))
        td.state = td._train_step(td.state, td._put(b))
    np.testing.assert_allclose(
        np.asarray(ts.state.params.table), np.asarray(td.state.params.table),
        rtol=1e-4, atol=1e-6,
    )


@pytest.mark.parametrize("devices", [1, 8])
def test_sparse_ftrl_stable_under_heavy_duplicates(tmp_path, devices):
    """Regression: per-occurrence -sigma*w scatter double-counted duplicate
    rows and diverged to NaN within a few hundred steps.  On one device
    the unique-row apply (the path of the benchmark's FTRL cell); on the
    8-device data mesh GSPMD's per-occurrence scatter.

    Each step is waited for before the next is dispatched.  With steps in
    flight, XLA:CPU's in-process all-reduce of one step could hold the
    client's threads while a device's program of the same step found
    none: seven of eight participants waited, and after 40 s XLA ended
    the process (under a loaded CPU, about one run in three)."""
    rng = np.random.default_rng(5)
    cfg = _cfg(tmp_path, "fdup", optimizer="ftrl", learning_rate=0.5,
               vocabulary_size=50,  # tiny vocab -> heavy duplicates
               sparse_apply="scatter")
    t = Trainer(cfg, mesh=mesh_lib.make_mesh(cfg, jax.devices()[:devices]))
    assert t.mesh.size == devices
    for _ in range(150):
        b = _dup_batch(rng, cfg, cfg.batch_size)
        t.state = jax.block_until_ready(t._train_step(t.state, t._put(b)))
    table = np.asarray(t.state.params.table)
    assert np.all(np.isfinite(table))
    assert np.abs(table).max() < 10.0
    assert np.isfinite(float(t.state.metrics.loss_sum))


@pytest.mark.parametrize("d,m", [(4, 2), (1, 8)])
def test_sparse_sharded_matches_single_device(tmp_path, d, m):
    rng = np.random.default_rng(3)
    cfg1 = _cfg(tmp_path / "a", "m1", mesh_data=1, mesh_model=1)
    cfgN = _cfg(tmp_path / "b", "mN", mesh_data=d, mesh_model=m)
    batches = [_dup_batch(rng, cfg1, cfg1.batch_size) for _ in range(3)]
    t1 = Trainer(cfg1, mesh=mesh_lib.make_mesh(cfg1, jax.devices()[:1]))
    tN = Trainer(cfgN)
    assert t1.sparse and tN.sparse
    for b in batches:
        t1.state = t1._train_step(t1.state, t1._put(b))
        tN.state = tN._train_step(tN.state, tN._put(b))
    np.testing.assert_allclose(
        np.asarray(t1.state.params.table), np.asarray(tN.state.params.table),
        rtol=1e-4, atol=1e-6,
    )
