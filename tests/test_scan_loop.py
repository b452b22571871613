"""Device-resident multi-step training: K-step fused dispatch +
double-buffered H2D prefetch (ISSUE 1 tentpole).

Pins the three guarantees the super-batch loop makes:

  * scan parity — one dispatch of ``make_scan_train_step`` over a stacked
    [K, ...] super-batch produces BIT-IDENTICAL params/metrics to K
    sequential single-step dispatches (fp32; the scan body is the same
    traced step, so nothing may reorder its math),
  * resume exactness — the checkpointed mid-epoch position only advances
    by whole dispatches, so an interrupted run resumed at a super-batch
    boundary (including through the epoch-tail remainder at K' =
    leftover) reproduces the uninterrupted run's params exactly,
  * transfer-stage hygiene — DevicePrefetcher propagates source/transfer
    exceptions to the consumer and shuts its thread down deterministically.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.data.pipeline import DevicePrefetcher, stack_batches
from fast_tffm_tpu.train.loop import Trainer, make_scan_train_step


def _write_data(path, rng, lines=320, vocab=64):
    with open(path, "w") as f:
        for i in range(lines):
            f.write(
                f"{i % 2} {rng.integers(0, vocab)}:1 "
                f"{rng.integers(0, vocab)}:0.5\n"
            )


def _cfg(tmp_path, **kw):
    defaults = dict(
        vocabulary_size=64, factor_num=4, max_features=4, batch_size=32,
        train_files=[str(tmp_path / "train.libsvm")],
        model_file=str(tmp_path / "model"),
        epoch_num=1, log_steps=0, thread_num=1, seed=3,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _batch(rng, b=32, f=4, vocab=64):
    return Batch(
        labels=rng.integers(0, 2, b).astype(np.float32),
        ids=rng.integers(0, vocab, (b, f)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, (b, f)).astype(np.float32),
        fields=np.zeros((b, f), np.int32),
        weights=np.ones((b,), np.float32),
    )


def _tree_equal(a, b):
    eq = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b
    )
    return all(jax.tree.leaves(eq))


# ------------------------------------------------------------- scan parity


@pytest.mark.parametrize("k", [1, 4])
def test_scan_step_parity_exact(tmp_path, rng, k):
    """scan(K) over a stacked super-batch == K sequential single steps,
    bitwise (params, optimizer state, metrics, step counter)."""
    _write_data(tmp_path / "train.libsvm", rng)
    t_scan = Trainer(_cfg(tmp_path, model_file=str(tmp_path / "m_scan")))
    t_one = Trainer(_cfg(tmp_path, model_file=str(tmp_path / "m_one")))

    batches = [_batch(rng) for _ in range(k)]
    stacked = t_scan._put_super(stack_batches(batches))
    t_scan.state = t_scan._scan_train_step(t_scan.state, stacked)
    for b in batches:
        t_one.state = t_one._train_step(t_one.state, t_one._put(b))

    assert int(t_scan.state.step) == k
    assert _tree_equal(t_scan.state, t_one.state)


def test_scan_parity_through_trainer_end_to_end(tmp_path, rng):
    """Full train() at K=4 (10 batches: two full dispatches + a K'=2
    tail) reproduces the K=1 run bit-for-bit."""
    _write_data(tmp_path / "train.libsvm", rng)
    t4 = Trainer(_cfg(tmp_path, model_file=str(tmp_path / "m4"),
                      steps_per_dispatch=4))
    r4 = t4.train()
    t1 = Trainer(_cfg(tmp_path, model_file=str(tmp_path / "m1")))
    r1 = t1.train()
    assert r4["train"]["steps"] == r1["train"]["steps"] == 10
    assert _tree_equal(t4.state.params, t1.state.params)
    assert _tree_equal(t4.state.metrics, t1.state.metrics)


def test_scan_parity_tile_apply_with_host_sort_meta(tmp_path, rng):
    """The stacked host sort_meta rides the scan: the tile apply consumes
    one [n_pad]-slice per step and every per-row leaf (table, Adagrad
    accumulator) stays bit-identical to K=1; ``w0`` within the rounding
    of its batch-wide gradient sum (reasoned below)."""
    from fast_tffm_tpu.parallel import mesh as mesh_lib

    _write_data(tmp_path / "train.libsvm", rng, lines=128, vocab=512)
    kw = dict(vocabulary_size=512, sparse_apply="tile", host_sort=True)
    # Host sort prep rides the single-process, single-device tile path
    # only — pin a 1-device mesh (conftest's virtual mesh has 8).
    cfg2 = _cfg(tmp_path, model_file=str(tmp_path / "mt2"),
                steps_per_dispatch=2, **kw)
    t2 = Trainer(cfg2, mesh=mesh_lib.make_mesh(cfg2, jax.devices()[:1]))
    assert t2._sort_meta_spec() is not None  # host prep actually engaged
    t2.train()
    cfg1 = _cfg(tmp_path, model_file=str(tmp_path / "mt1"), **kw)
    t1 = Trainer(cfg1, mesh=mesh_lib.make_mesh(cfg1, jax.devices()[:1]))
    t1.train()
    assert _tree_equal(t2.state.params.table, t1.state.params.table)
    assert _tree_equal(t2.state.opt_state.acc.table,
                       t1.state.opt_state.acc.table)
    # w0's gradient is ONE float32 sum over the batch's B examples, and
    # XLA:CPU picks that sum's order per program: jax unrolls a length-1
    # scan, so K=1 fuses the step into straight-line code where K=2 runs
    # a loop body, and the two vectorise the reduce differently (every
    # apply mode shows it on this data, from step 2 on, and both results
    # move with the vector ISA).  Two orders of a B-term sum differ by
    # at most 2 (B-1) u sum|x_i|, u = 2^-24; the terms are
    # w_i / sum(w) * dL/ds_i with |dL/ds| <= 1 (logistic loss), so
    # sum|x_i| <= 1.  Adagrad turns a gradient error e into a w0 error
    # of at most lr * e / sqrt(acc), acc >= its initial value, once a
    # step.  Seen: 8e-10; a dropped example would move w0 by ~1e-3.
    b, steps = cfg1.batch_size, int(t1.state.step)
    tol = (steps * cfg1.learning_rate
           / np.sqrt(cfg1.adagrad_initial_accumulator)
           * 2 * (b - 1) * 2.0 ** -24)
    assert tol < 1e-5
    assert abs(float(t2.state.params.w0) - float(t1.state.params.w0)) <= tol


def test_scan_step_retraces_per_k_only(tmp_path, rng):
    """One jitted scan wrapper serves every K (the leading axis is part
    of the input shape): the epoch tail's K' costs one retrace, not a
    rebuilt trainer."""
    _write_data(tmp_path / "train.libsvm", rng)
    t = Trainer(_cfg(tmp_path))
    for k in (3, 1, 3):  # repeat K=3: cache hit, no error
        stacked = t._put_super(stack_batches([_batch(rng) for _ in range(k)]))
        t.state = t._scan_train_step(t.state, stacked)
    assert int(t.state.step) == 7


# -------------------------------------------------- resume at K granularity


def _interrupt_after_dispatches(trainer, n):
    """Make trainer.train() raise after n completed dispatches."""
    real = trainer._scan_train_step
    count = {"n": 0}

    def wrapped(state, batch):
        if count["n"] >= n:
            raise KeyboardInterrupt("simulated preemption")
        count["n"] += 1
        return real(state, batch)

    trainer._scan_train_step = wrapped


def test_resume_lands_on_super_batch_boundary_exact(tmp_path, rng):
    """Interrupt after 2 of 3 dispatches (K=4, 10 batches); the saved
    position is the 8-batch boundary, and the resumed run — whose only
    dispatch is the K'=2 epoch tail — ends bit-identical to the
    uninterrupted run."""
    _write_data(tmp_path / "train.libsvm", rng)
    full = Trainer(_cfg(tmp_path, model_file=str(tmp_path / "m_full"),
                        steps_per_dispatch=4))
    full.train()

    cfg = _cfg(tmp_path, model_file=str(tmp_path / "m_int"),
               steps_per_dispatch=4, save_steps=4)
    t = Trainer(cfg)
    _interrupt_after_dispatches(t, 2)
    with pytest.raises(KeyboardInterrupt):
        t.train()

    from fast_tffm_tpu.train import checkpoint

    ds = checkpoint.restore_data_state(cfg.model_file)
    assert ds["epoch"] == 0 and ds["batches_done"] == 8  # whole dispatches

    t2 = Trainer(cfg)
    r2 = t2.train()
    assert r2["train"]["steps"] == 2  # exactly the tail remainder
    assert _tree_equal(t2.state.params, full.state.params)


def test_resume_skips_prefetched_but_untrained_batches(tmp_path, rng):
    """batches_done counts TRAINED batches only: super-batches the
    transfer stage had already staged when the run died re-parse and
    re-train on resume (nothing is lost to the prefetch buffer)."""
    _write_data(tmp_path / "train.libsvm", rng)
    cfg = _cfg(tmp_path, steps_per_dispatch=2, save_steps=2,
               prefetch_super_batches=2)
    t = Trainer(cfg)
    _interrupt_after_dispatches(t, 1)  # die after 2 of 10 batches
    with pytest.raises(KeyboardInterrupt):
        t.train()
    from fast_tffm_tpu.train import checkpoint

    assert checkpoint.restore_data_state(cfg.model_file)["batches_done"] == 2
    r = Trainer(cfg).train()
    assert r["train"]["steps"] == 8  # the other 8 batches, once each


def test_k8_smoke_tiny_run(tmp_path, rng):
    """Tier-1 exercises the K=8 fused dispatch end-to-end on CPU: a tiny
    run completes, counts every batch once, and trains to finite loss."""
    _write_data(tmp_path / "train.libsvm", rng, lines=640)  # 20 batches
    t = Trainer(_cfg(tmp_path, steps_per_dispatch=8, log_steps=5))
    r = t.train()
    assert r["train"]["steps"] == 20  # 2 full dispatches + K'=4 tail
    assert r["train"]["examples"] == 640.0
    assert np.isfinite(r["train"]["loss"])


# --------------------------------------------------------- DevicePrefetcher


def test_prefetcher_stacks_and_tails(rng):
    batches = [_batch(rng) for _ in range(7)]
    got = list(DevicePrefetcher(batches, 3, lambda b: b, depth=2))
    assert [k for _, k in got] == [3, 3, 1]
    assert got[0][0].labels.shape == (3, 32)
    np.testing.assert_array_equal(got[2][0].ids[0], batches[6].ids)


def test_prefetcher_propagates_source_exception(rng):
    def source():
        yield _batch(rng)
        yield _batch(rng)
        raise RuntimeError("reader died")

    pf = DevicePrefetcher(source(), 2, lambda b: b, depth=2)
    it = iter(pf)
    first, k = next(it)
    assert k == 2
    with pytest.raises(RuntimeError, match="reader died"):
        list(it)
    # The transfer thread is reaped by the iterator's close-on-exit.
    pf._thread.join(timeout=5)
    assert not pf._thread.is_alive()


def test_prefetcher_propagates_put_exception(rng):
    def bad_put(b):
        raise ValueError("transfer failed")

    pf = DevicePrefetcher([_batch(rng)], 1, bad_put, depth=2)
    with pytest.raises(ValueError, match="transfer failed"):
        list(pf)
    pf._thread.join(timeout=5)
    assert not pf._thread.is_alive()


def test_prefetcher_close_unblocks_producer(rng):
    """close() with a full output queue and an unconsumed stream must
    stop the transfer thread (no leak, no deadlock); a second close is a
    no-op."""
    many = (_batch(rng) for _ in range(1000))
    pf = DevicePrefetcher(many, 1, lambda b: b, depth=1)
    next(iter(pf))  # consume one, then abandon the stream
    time.sleep(0.05)  # let the producer fill the bounded queue
    pf.close()
    assert not pf._thread.is_alive()
    pf.close()  # idempotent


def test_prefetcher_bounded_in_flight(rng):
    """At most depth super-batches are shipped ahead of the consumer:
    the put_fn is not called for the whole stream up front."""
    calls = []

    def put(b):
        calls.append(time.monotonic())
        return b

    pf = DevicePrefetcher([_batch(rng) for _ in range(32)], 1, put, depth=2)
    time.sleep(0.3)
    # depth queued + one being offered is the cap before any consumption.
    assert len(calls) <= 3
    pf.close()


def test_stack_batches_meta_all_or_nothing(rng):
    from fast_tffm_tpu.data.libsvm import SortMeta

    b1 = _batch(rng)
    meta = SortMeta(*[np.zeros(4, np.int32)] * 2, np.zeros(4, np.float32),
                    *[np.zeros(2, np.int32)] * 3, np.zeros(3, np.int32))
    bm = b1._replace(sort_meta=meta)
    stacked = stack_batches([bm, bm])
    assert stacked.sort_meta is not None
    assert stacked.sort_meta.perm.shape == (2, 4)
    mixed = stack_batches([bm, b1])
    assert mixed.sort_meta is None  # any meta-less member drops it


def test_prefetcher_closes_source_generator(rng):
    """Ending iteration closes the source generator deterministically so
    a BatchPipeline's worker threads get reaped, not leaked."""
    closed = threading.Event()

    def source():
        try:
            for _ in range(3):
                yield _batch(rng)
        finally:
            closed.set()

    list(DevicePrefetcher(source(), 2, lambda b: b, depth=2))
    assert closed.wait(timeout=5)
