"""The health carry's row-touch plane taken from the stream writer.

Where the compiled step holds the stream writer (the one-device scatter
apply, the hand-sharded entries exchange), the writer returns the rows
of the step with a real (``val != 0``) occurrence, and the health
update ORs that mask into ``rows_touched`` instead of scattering the
batch's ids.  On the CPU the kernels run interpreted, where the rule
keeps the scatter loop and K2; these tests force the writer as
tests/test_unique_scatter.py and tests/test_mesh_reference.py do:

(a) three steps on one device and on the 2x2 mesh (both rungs of the
    entries exchange), for every row-local optimizer: the plane is the
    per-occurrence plane of the real ids — a padding occurrence on a
    row no real one touches leaves it unmarked — and every table is the
    one the same writer makes with no mask asked for, bit for bit;
(b) ``Trainer.train()``: gauge ``train.touch_mask`` says where the
    plane came from, and ``emb_rows_touched`` in the final record and
    the run's result is the per-occurrence count either way.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import loop, sparse as sparse_lib

from test_mesh_reference import (
    R_CAP, R_RUNG, R_VOCAB, _mesh, _rung_batch, _rung_cfg, _uniform, _zipf,
)

# Rows only padding occurrences (val == 0) land on: the pipeline pads
# with row 0; the second lies in the second model shard.
PADS = (0, R_VOCAB // 2 + 5)


def _padded(batch):
    """``batch`` with the PADS rows touched by padding alone: a real
    occurrence there moves to the next row, and half the padding slots
    move onto them (the other half keep rows real occurrences touch)."""
    ids, vals = batch.ids.copy(), batch.vals
    for p in PADS:
        ids[(ids == p) & (vals != 0)] = p + 1
    pad = np.argwhere(vals == 0)
    for k, (i, j) in enumerate(pad[::2]):
        ids[i, j] = PADS[k % 2]
    return batch._replace(ids=ids)


def _force_stream(monkeypatch, where):
    """The writer where kernels run compiled, here interpreted."""
    if where == "one_device":
        monkeypatch.setattr(sparse_apply, "takes_stream", lambda *a: True)
    else:
        monkeypatch.setattr(sparse_apply, "exchange_takes_stream",
                            lambda data_shards: data_shards > 1)


def _setup(where, optimizer):
    if where == "one_device":
        cfg = _rung_cfg(optimizer, sparse_apply="scatter", lookup="auto")
        return cfg, None
    return _rung_cfg(optimizer), _mesh()


def _step(cfg, mesh, health):
    return loop.make_sparse_train_step(cfg, mesh, with_health=health)


@pytest.mark.parametrize("where,ids", [
    ("one_device", "zipf"), ("2x2", "zipf"), ("2x2", "uniform"),
])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_writer_mask_is_the_per_occurrence_plane(
        monkeypatch, where, ids, optimizer):
    _force_stream(monkeypatch, where)
    cfg, mesh = _setup(where, optimizer)
    draw = _zipf if ids == "zipf" else _uniform
    batches = [_padded(_rung_batch(s, [draw, draw])) for s in (61, 62, 63)]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *batches)
    params = fm.init_params(jax.random.PRNGKey(5), cfg)
    state = loop.TrainState(
        params, sparse_lib.init_sparse_opt_state(cfg, params),
        loop.MetricState.zeros(), jnp.zeros((), jnp.int32))
    assert sparse_lib.touch_mask(cfg, mesh)
    vocab = cfg.vocabulary_size
    health = loop.HealthState.zeros(vocab)
    if mesh is not None:
        health = jax.device_put(health, loop.health_sharding(cfg, mesh))
        assert health.rows_touched.sharding.spec == jax.sharding.PartitionSpec(
            mesh_lib.MODEL_AXIS)
    scan = jax.jit(loop.make_scan_train_step(
        _step(cfg, mesh, True), loop.make_health_update(cfg)))
    got, health = scan(state, health, stacked)
    # the plane: every row a real occurrence of the three steps touched
    real = np.concatenate([b.ids[b.vals != 0] for b in batches])
    want = np.zeros(vocab, bool)
    want[real] = True
    plane = np.asarray(health.rows_touched)
    np.testing.assert_array_equal(plane, want)
    assert not plane[list(PADS)].any()
    assert all((b.ids == p).any() for b in batches for p in PADS)
    assert any(((b.ids == r) & (b.vals == 0)).any() and want[r]
               for b in batches for r in np.unique(b.ids))  # duplicates
    # the exchange took the rung the ids ask for, every step
    if mesh is not None:
        slots = R_RUNG if ids == "zipf" else R_CAP
        assert int(health.apply_rows[1]) == 3 * 2 * 2 * slots
    # the same writer with no mask asked for (no real-occurrence column)
    plain = jax.jit(loop.make_scan_train_step(_step(cfg, mesh, False)))
    ref = plain(state, stacked)
    for a, b in zip(jax.tree.leaves((got.params, got.opt_state)),
                    jax.tree.leaves((ref.params, ref.opt_state))):
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32))
    assert (np.asarray(got.params.table)
            != np.asarray(params.table)).any()


@pytest.mark.parametrize("where,writer", [
    ("one_device", "stream"), ("one_device", "scatter_loop"),
    ("2x2", "stream"), ("2x2", "k2"),
])
def test_touch_mask_gauge_and_rows_touched_record(
        monkeypatch, tmp_path, where, writer):
    if writer == "stream":
        _force_stream(monkeypatch, where)
    batches = [_padded(_rung_batch(s, [_zipf, _zipf])) for s in (71, 72)]
    # the file holds the real occurrences; the pipeline pads each line
    # to F features with row 0 at value 0
    with open(tmp_path / "train.libsvm", "w") as f:
        for b in batches:
            for y, ids, vals in zip(b.labels, b.ids, b.vals):
                keep = vals != 0
                f.write(f"{int(y)} " + " ".join(
                    f"{i}:{v:.3f}" for i, v in zip(ids[keep], vals[keep]))
                    + "\n")
    real = np.concatenate([b.ids[b.vals != 0] for b in batches])
    assert not np.isin(real, PADS).any()
    cfg, mesh = _setup(where, "adagrad")
    cfg = dataclasses.replace(
        cfg,
        train_files=[str(tmp_path / "train.libsvm")],
        model_file=str(tmp_path / "model"), epoch_num=1, log_steps=1,
        thread_num=1, steps_per_dispatch=1,
        metrics_file=str(tmp_path / "metrics.jsonl"),
        **({} if mesh is None else dict(mesh_data=2, mesh_model=2)),
    )
    if mesh is None:
        mesh = mesh_lib.make_mesh(cfg, jax.devices()[:1])
    trainer = loop.Trainer(cfg, mesh=mesh)
    result = trainer.train()
    gauges = trainer.telemetry.snapshot()["gauges"]
    stream = int(writer == "stream")
    assert gauges["train.apply_stream"] == stream
    assert gauges["train.touch_mask"] == stream
    recs = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    final = [r for r in recs if r.get("record") == "final"][-1]
    touched = len(np.unique(real))
    assert final["health"]["emb_rows_touched"] == touched
    assert result["train"]["health"]["emb_rows_touched"] == touched


def test_the_stream_needs_a_real_column_to_make_a_mask():
    """Asked for no mask (``real`` None), the writer returns none, and
    the scatter loop never makes one."""
    from functools import partial

    rng = np.random.default_rng(9)
    ids = rng.integers(0, 2048, 700).astype(np.int32)
    g = rng.normal(size=(700, 3)).astype(np.float32)
    tables = (rng.uniform(size=(2048, 3)).astype(np.float32),)
    update = partial(sparse_apply.sgd_update, lr=0.1)
    for stream, real, has in [(True, None, False), (False, ids > 3, False),
                              (True, ids > 3, True)]:
        _, count, touched = jax.jit(
            lambda i, gr, t, r=real, s=stream:
            sparse_apply.scatter_apply_unique(
                update, (t,), i, gr, additive=True, stream=s,
                real=None if r is None else jnp.asarray(r)),
        )(ids, g, tables[0])
        assert int(count) == len(np.unique(ids))
        assert (touched is not None) == has
        if has:
            np.testing.assert_array_equal(
                np.asarray(touched), np.isin(np.arange(2048), ids[ids > 3]))
