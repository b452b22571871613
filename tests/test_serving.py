"""Online serving path (ISSUE 9 tentpole): compiled fixed-shape scorer
+ request-batching inference server.

The pinned guarantees:

  * parity — served scores are BITWISE-IDENTICAL to offline
    ``predict()`` output for the same examples: both route through the
    same fixed-shape ladder, and per-example scores are independent of
    the batch shape they pad into (pad/bucket parity);
  * zero compiles — after :meth:`warmup`, steady-state serving never
    compiles (every request shape pads into a precompiled rung); a
    shape OUTSIDE the ladder flags ``serve.recompiles_unexpected``;
  * batching — the batcher coalesces concurrent requests into one
    microbatch, honors the ``max_batch_wait_ms`` deadline for lone
    requests, and carries overflow into the next dispatch;
  * hot swap — mid-traffic checkpoint swaps return only old-table or
    new-table scores (never torn), with zero recompiles and no failed
    requests; the manifest watcher picks up a republished checkpoint;
  * overlay — a huge-V ``tiered.npz`` checkpoint predicts/serves via
    the compact per-chunk remap, exactly matching full-table scoring.
"""

from __future__ import annotations

import json
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.serve.batcher import ServeBatcher
from fast_tffm_tpu.serve.scorer import (
    FixedShapeScorer, OverlayScorer, load_model, make_scorer,
)
from fast_tffm_tpu.serve import wire
from fast_tffm_tpu.serve.router import Replica, ServeRouter
from fast_tffm_tpu.serve.server import (
    CheckpointWatcher, parse_request, serve,
)
from fast_tffm_tpu.train import checkpoint, tiered
from fast_tffm_tpu.train.loop import Trainer, predict

V = 256
F = 4


def _cfg(tmp_path, model="model", **kw):
    defaults = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
        train_files=[str(tmp_path / "train.libsvm")],
        predict_files=[str(tmp_path / "train.libsvm")],
        score_path=str(tmp_path / "scores.txt"),
        model_file=str(tmp_path / model),
        epoch_num=1, log_steps=0, thread_num=1, seed=3,
        serve_batch_sizes="32,64", max_batch_wait_ms=1.0,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _write_data(path, rng, lines=256, vocab=V):
    with open(path, "w") as f:
        for i in range(lines):
            f.write(
                f"{i % 2} {rng.integers(0, vocab)}:1 "
                f"{rng.integers(0, vocab)}:0.5 "
                f"{rng.integers(0, vocab)}:0.25\n"
            )


def _params(cfg, seed=0):
    return jax.jit(lambda k: fm.init_params(k, cfg=cfg))(
        jax.random.PRNGKey(seed)
    )


def _examples(rng, n, vocab=V, feat=F):
    ids = rng.integers(0, vocab, (n, feat)).astype(np.int32)
    vals = rng.uniform(0.1, 1.0, (n, feat)).astype(np.float32)
    return ids, vals


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained dense checkpoint shared by the e2e tests."""
    tmp_path = tmp_path_factory.mktemp("serving")
    _write_data(tmp_path / "train.libsvm", np.random.default_rng(0))
    cfg = _cfg(tmp_path)
    Trainer(cfg).train()
    return tmp_path, cfg


# ----------------------------------------------------------------------
# scorer: ladder, padding parity, compile accounting
# ----------------------------------------------------------------------


class TestScorer:
    def test_pad_and_bucket_parity_bitwise(self, rng):
        """The acceptance property: per-example scores are identical
        whatever rung the example pads into — so batching/padding can
        never change an answer."""
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg))
        ids, vals = _examples(rng, 70)
        full = sc.score(ids, vals)  # 64-rung chunk + padded tail
        assert full.shape == (70,)
        one = sc.score(ids[:1], vals[:1])  # 32-rung, 31 pad rows
        np.testing.assert_array_equal(full[:1], one)
        mid = sc.score(ids[10:40], vals[10:40])
        np.testing.assert_array_equal(full[10:40], mid)

    def test_chunking_large_request(self, rng):
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg))
        ids, vals = _examples(rng, 300)  # >> max rung 64
        full = sc.score(ids, vals)
        parts = np.concatenate([
            sc.score(ids[i:i + 50], vals[i:i + 50])
            for i in range(0, 300, 50)
        ])
        np.testing.assert_array_equal(full, parts)

    def test_zero_compiles_after_warmup(self, rng):
        tel = obs.Telemetry()
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        n = sc.warmup()
        assert n == len(sc.ladder) == 2
        for size in (1, 7, 31, 32, 33, 64, 200):
            ids, vals = _examples(rng, size)
            sc.score(ids, vals)
        assert sc.steady_compiles == 0
        snap = tel.snapshot()
        assert snap["timers"]["serve.compile"]["count"] == n
        assert snap["counters"].get(
            "serve.recompiles_unexpected", 0
        ) == 0

    def test_off_ladder_rung_flags_unexpected(self, rng):
        tel = obs.Telemetry()
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        sc.warmup()
        b = 48  # not a ladder rung (multiple of the 8-device data axis)
        ids, vals = _examples(rng, b)
        sc.score_rung(ids, vals, None, b)
        assert sc.steady_compiles == 1
        assert tel.snapshot()["counters"][
            "serve.recompiles_unexpected"
        ] == 1

    def test_ladder_rounds_to_data_axis(self):
        # 8 virtual devices: a rung of 10 must round to a multiple of 8.
        cfg = _cfg_mem(serve_batch_sizes="10,60")
        sc = FixedShapeScorer(cfg, _params(cfg))
        data_n = sc.mesh.shape["data"]
        assert all(b % data_n == 0 for b in sc.ladder)

    def test_compile_records_written(self, rng, tmp_path):
        path = tmp_path / "m.jsonl"
        writer = obs.JsonlWriter(str(path))
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg), writer=writer)
        sc.warmup()
        writer.close()
        recs = [json.loads(l) for l in open(path)]
        assert len(recs) == len(sc.ladder)
        for r in recs:
            assert r["record"] == "compile"
            assert r["where"] == "serve"
            assert r["expected"] is True
            assert r["compile_s"] > 0


def _cfg_mem(**kw):
    """A config never touching disk (in-memory params scorer tests)."""
    defaults = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
        serve_batch_sizes="32,64", max_batch_wait_ms=1.0,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


# ----------------------------------------------------------------------
# batcher: coalescing, deadline, overflow carry
# ----------------------------------------------------------------------


class _FakeScorer:
    """Batcher-facing scorer stub: deterministic scores (sum of vals
    per row), records every dispatched rung."""

    def __init__(self, ladder=(32, 64), delay_s=0.0):
        self.ladder = tuple(ladder)
        self.max_rung = self.ladder[-1]
        self.cfg = _cfg_mem()  # the batcher sizes its pools from this
        self.dispatches: list = []
        self._delay = delay_s

    def rung_for(self, n):
        for b in self.ladder:
            if n <= b:
                return b
        return self.max_rung

    def slots_for(self, n):
        return n

    def launch_rung(self, ids, vals, fields, b, inflight=0):
        if self._delay:
            time.sleep(self._delay)
        self.dispatches.append(b)
        return types.SimpleNamespace(
            scores=vals.sum(axis=1), launch_s=0.0, readback_s=0.0)

    def read_rung(self, flight):
        return flight.scores

    def score(self, ids, vals, fields=None):
        self.dispatches.append(len(ids))
        return vals.sum(axis=1)


class TestBatcher:
    def test_coalesces_concurrent_requests(self, rng):
        fake = _FakeScorer(delay_s=0.005)
        bat = ServeBatcher(fake, max_batch_wait_ms=20.0)
        try:
            ids, vals = _examples(rng, 4)
            results = [None] * 10
            def go(i):
                results[i] = bat.score(ids, vals, timeout=10)
            threads = [
                threading.Thread(target=go, args=(i,))
                for i in range(10)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for r in results:
                np.testing.assert_allclose(r, vals.sum(axis=1))
            # 10 requests x 4 examples coalesced into FEWER dispatches
            # (the first may go alone; the rest pile up behind it).
            assert 1 <= len(fake.dispatches) < 10
            assert all(b <= fake.max_rung for b in fake.dispatches)
        finally:
            bat.close()

    def test_lone_request_honors_deadline(self, rng):
        fake = _FakeScorer()
        bat = ServeBatcher(fake, max_batch_wait_ms=30.0)
        try:
            ids, vals = _examples(rng, 2)
            t0 = time.perf_counter()
            bat.score(ids, vals, timeout=10)
            elapsed = time.perf_counter() - t0
            # Must wait ~the deadline for company, then dispatch —
            # never hang for a full rung that will not arrive.
            assert 0.02 <= elapsed < 5.0
        finally:
            bat.close()

    def test_zero_wait_dispatches_immediately(self, rng):
        fake = _FakeScorer()
        bat = ServeBatcher(fake, max_batch_wait_ms=0.0)
        try:
            ids, vals = _examples(rng, 2)
            t0 = time.perf_counter()
            bat.score(ids, vals, timeout=10)
            assert time.perf_counter() - t0 < 1.0
        finally:
            bat.close()

    def test_overflow_carries_to_next_dispatch(self, rng):
        fake = _FakeScorer(delay_s=0.02)
        bat = ServeBatcher(fake, max_batch_wait_ms=50.0)
        try:
            ids, vals = _examples(rng, 40)
            reqs = [bat.submit(ids, vals) for _ in range(3)]  # 120 > 64
            outs = [bat.result(r, timeout=10) for r in reqs]
            for out in outs:
                np.testing.assert_allclose(out, vals.sum(axis=1))
            # 3 x 40 cannot share a 64-rung: every dispatch stays
            # within the max rung (no torn request across dispatches).
            assert all(b <= fake.max_rung for b in fake.dispatches)
            assert len(fake.dispatches) >= 2
        finally:
            bat.close()

    def test_oversized_request_chunks(self, rng):
        fake = _FakeScorer()
        bat = ServeBatcher(fake, max_batch_wait_ms=1.0)
        try:
            ids, vals = _examples(rng, 200)  # > max rung
            out = bat.score(ids, vals, timeout=10)
            np.testing.assert_allclose(out, vals.sum(axis=1))
        finally:
            bat.close()

    def test_closed_batcher_rejects_and_fails_pending(self, rng):
        fake = _FakeScorer()
        bat = ServeBatcher(fake, max_batch_wait_ms=1.0)
        bat.close()
        ids, vals = _examples(rng, 2)
        with pytest.raises(RuntimeError):
            bat.submit(ids, vals)

    def test_batch_fill_accounting(self, rng):
        fake = _FakeScorer()
        tel = obs.Telemetry()
        bat = ServeBatcher(fake, max_batch_wait_ms=0.0, telemetry=tel)
        try:
            ids, vals = _examples(rng, 32)  # exactly the small rung
            bat.score(ids, vals, timeout=10)
            assert bat.batch_fill == pytest.approx(1.0)
            snap = tel.snapshot()
            assert snap["counters"]["serve.examples"] == 32
            assert snap["counters"]["serve.batches"] == 1
            assert snap["timers"]["serve.latency"]["count"] == 1
            assert "p99_ms" in snap["timers"]["serve.latency"]
        finally:
            bat.close()


# ----------------------------------------------------------------------
# one group in flight: launch n+1, then read n (ISSUE 32)
# ----------------------------------------------------------------------


class _GatedScorer(_FakeScorer):
    """A stub whose halves the test holds and releases.  A group is
    named by its first id; ``log`` is the order of the halves.  The
    model is taken at the launch (as the real scorer takes its
    reference): ``"new"`` adds 1000 to every score."""

    def __init__(self):
        super().__init__()
        self.log: list = []
        self.model = "old"
        self.hold_launches = threading.Event()  # set = launches go
        self.hold_launches.set()
        self.reads: dict = {}  # group name -> Event the read waits on
        self.bad_reads: set = set()

    def gate(self, name):
        return self.reads.setdefault(name, threading.Event())

    def launch_rung(self, ids, vals, fields, b, inflight=0):
        assert self.hold_launches.wait(10)
        name = int(ids[0, 0])
        self.log.append(("launch", name, inflight))
        scores = vals.sum(axis=1) + (1000.0 if self.model == "new" else 0.0)
        return types.SimpleNamespace(
            name=name, scores=scores, launch_s=0.0, readback_s=0.0)

    def read_rung(self, flight):
        assert self.gate(flight.name).wait(10)
        self.log.append(("read", flight.name))
        if flight.name in self.bad_reads:
            raise RuntimeError(f"read of group {flight.name} failed")
        return flight.scores

    def score(self, ids, vals, fields=None):
        self.log.append(("score", int(ids[0, 0])))
        return vals.sum(axis=1)


def _named(rng, name, n):
    """``n`` examples whose every id is ``name``."""
    _, vals = _examples(rng, n)
    return np.full((n, F), name, np.int32), vals


def _wait_for(cond, what, timeout=10.0):
    t_end = time.time() + timeout
    while not cond():
        assert time.time() < t_end, f"timed out waiting for {what}"
        time.sleep(0.002)


class TestOneGroupInFlight:
    """The dispatcher launches group n+1 before it reads group n back
    when the queue closes n+1 with no wait, and not otherwise."""

    def _start(self, wait_ms=2000.0):
        fake = _GatedScorer()
        tel = obs.Telemetry()
        bat = ServeBatcher(fake, max_batch_wait_ms=wait_ms, telemetry=tel)
        return fake, tel, bat

    @staticmethod
    def _counter(tel, name):
        return tel.snapshot()["counters"].get(name, 0)

    @pytest.mark.parametrize("second", ["full_rung", "does_not_fit"])
    def test_queued_group_launches_before_the_read(self, rng, second):
        """(a) and (g): with a group the queue closes at once, n+1's
        launch comes before n's read returns; replies leave in launch
        order; serve.overlapped counts it."""
        fake, tel, bat = self._start()
        try:
            fake.hold_launches.clear()
            a_ids, a_vals = _named(rng, 1, 64)
            a = bat.submit(a_ids, a_vals)
            _wait_for(lambda: bat._q.qsize() == 0, "A picked")
            if second == "full_rung":
                rest = [bat.submit(*_named(rng, 2, 64))]
            else:  # 40 + 40 > 64: the second closes the first's group
                rest = [bat.submit(*_named(rng, 2, 40)),
                        bat.submit(*_named(rng, 3, 40))]
            b = rest[0]
            fake.hold_launches.set()
            _wait_for(lambda: len(fake.log) >= 2, "B launched")
            # B went out while A's read had not returned
            assert fake.log[:2] == [("launch", 1, 0), ("launch", 2, 1)]
            assert not a.event.is_set() and not b.event.is_set()
            fake.gate(1).set()
            np.testing.assert_allclose(
                bat.result(a, timeout=10), a_vals.sum(axis=1))
            assert not b.event.is_set()  # in launch order, A first
            for name in (2, 3):
                fake.gate(name).set()
            for r in rest:
                np.testing.assert_allclose(
                    bat.result(r, timeout=10), r.vals.sum(axis=1))
            assert [e[:2] for e in fake.log[:4]] == [
                ("launch", 1), ("launch", 2), ("read", 1), ("read", 2)]
            assert self._counter(tel, "serve.overlapped") == 1
            assert self._counter(tel, "serve.batches") == len(rest) + 1
            assert bat.batch_fill == pytest.approx(
                (64 + sum(r.n for r in rest)) / (64 * (len(rest) + 1)))
            assert bat.inflight == 0
        finally:
            for name in (1, 2, 3):
                fake.gate(name).set()
            fake.hold_launches.set()
            bat.close()

    def test_ready_answer_never_waits_behind_the_batch_wait(self, rng):
        """(b) and (g): nothing queued after n's launch, so n is read
        and delivered before the dispatcher waits -- a lone request does
        not pay the 2 s batch wait; nor does n wait for a request that
        cannot close a group.  serve.overlapped stays 0."""
        fake, tel, bat = self._start()
        try:
            fake.gate(1).set()
            ids, vals = _named(rng, 1, 64)  # a full rung: no coalesce wait
            t0 = time.perf_counter()
            got = bat.score(ids, vals, timeout=10)
            assert time.perf_counter() - t0 < 1.0
            np.testing.assert_allclose(got, vals.sum(axis=1))
            # B cannot close a group alone: A is landed first, B's
            # launch comes after the read of A
            fake.hold_launches.clear()
            a = bat.submit(*_named(rng, 4, 64))
            _wait_for(lambda: bat._q.qsize() == 0, "A picked")
            b = bat.submit(*_named(rng, 5, 2))
            fake.gate(4).set()
            fake.gate(5).set()
            t0 = time.perf_counter()
            fake.hold_launches.set()
            bat.result(a, timeout=10)
            assert time.perf_counter() - t0 < 1.0  # not B's 2 s wait
            assert not b.event.is_set()
            assert fake.log[-2:] == [("launch", 4, 0), ("read", 4)]
            bat.result(b, timeout=10)  # ... which B then sits out
            assert fake.log[-2:] == [("launch", 5, 0), ("read", 5)]
            assert self._counter(tel, "serve.overlapped") == 0
            assert self._counter(tel, "serve.batches") == 3
        finally:
            fake.hold_launches.set()
            bat.close()

    def test_group_in_flight_keeps_the_model_it_was_launched_on(self, rng):
        """(c) through the batcher: a swap between n's launch and its
        read leaves n on the old model, n+1 on the new."""
        fake, tel, bat = self._start()
        try:
            a_ids, a_vals = _named(rng, 1, 64)
            a = bat.submit(a_ids, a_vals)
            _wait_for(lambda: fake.log == [("launch", 1, 0)], "A launched")
            fake.model = "new"  # A is in flight, its read held
            b_ids, b_vals = _named(rng, 2, 64)
            b = bat.submit(b_ids, b_vals)
            fake.gate(1).set()
            fake.gate(2).set()
            np.testing.assert_allclose(
                bat.result(a, timeout=10), a_vals.sum(axis=1))
            np.testing.assert_allclose(
                bat.result(b, timeout=10), b_vals.sum(axis=1) + 1000.0)
        finally:
            fake.gate(1).set()
            bat.close()

    def test_failed_read_fails_its_own_group_only(self, rng):
        """(d): the read of n raises; n's clients get the error, n+1,
        already launched, is read and delivered."""
        fake, tel, bat = self._start()
        try:
            fake.bad_reads.add(1)
            fake.hold_launches.clear()
            a = bat.submit(*_named(rng, 1, 64))
            _wait_for(lambda: bat._q.qsize() == 0, "A picked")
            b_ids, b_vals = _named(rng, 2, 64)
            b = bat.submit(b_ids, b_vals)
            released = []
            a.on_done = lambda: released.append(1)
            fake.gate(1).set()
            fake.gate(2).set()
            fake.hold_launches.set()
            with pytest.raises(RuntimeError, match="group 1 failed"):
                bat.result(a, timeout=10)
            np.testing.assert_allclose(
                bat.result(b, timeout=10), b_vals.sum(axis=1))
            assert [e[:2] for e in fake.log] == [
                ("launch", 1), ("launch", 2), ("read", 1), ("read", 2)]
            assert released == [1]  # the failed group's scratch is freed
            assert self._counter(tel, "serve.batches") == 1
            assert bat.inflight == 0
        finally:
            fake.hold_launches.set()
            bat.close()

    def test_close_lands_the_group_in_flight(self, rng):
        """(e): close() with a group in flight delivers it; what is
        still queued fails."""
        fake, tel, bat = self._start()
        fake.hold_launches.clear()
        a_ids, a_vals = _named(rng, 1, 64)
        a = bat.submit(a_ids, a_vals)
        _wait_for(lambda: bat._q.qsize() == 0, "A picked")
        b = bat.submit(*_named(rng, 2, 8))  # queued, never picked
        closer = threading.Thread(target=bat.close)
        closer.start()
        _wait_for(lambda: bat._closed, "close() under way")
        fake.hold_launches.set()
        _wait_for(lambda: fake.log == [("launch", 1, 0)], "A launched")
        assert closer.is_alive() and not a.event.is_set()
        fake.gate(1).set()
        closer.join(10)
        assert not closer.is_alive()
        np.testing.assert_allclose(
            bat.result(a, timeout=1), a_vals.sum(axis=1))
        with pytest.raises(RuntimeError, match="closed"):
            bat.result(b, timeout=1)
        assert fake.log == [("launch", 1, 0), ("read", 1)]

    def test_oversized_request_waits_for_the_group_in_flight(self, rng):
        """(e): an oversized lone request (scorer.score(), blocking) is
        scored after the group in flight has been landed."""
        fake, tel, bat = self._start()
        try:
            fake.hold_launches.clear()
            a_ids, a_vals = _named(rng, 1, 64)
            a = bat.submit(a_ids, a_vals)
            _wait_for(lambda: bat._q.qsize() == 0, "A picked")
            c_ids, c_vals = _named(rng, 3, 200)
            c = bat.submit(c_ids, c_vals)
            fake.hold_launches.set()
            _wait_for(lambda: len(fake.log) >= 1, "A launched")
            time.sleep(0.05)
            assert fake.log == [("launch", 1, 0)]  # C not scored yet
            fake.gate(1).set()
            np.testing.assert_allclose(
                bat.result(a, timeout=10), a_vals.sum(axis=1))
            np.testing.assert_allclose(
                bat.result(c, timeout=10), c_vals.sum(axis=1))
            assert fake.log == [("launch", 1, 0), ("read", 1), ("score", 3)]
            assert self._counter(tel, "serve.overlapped") == 0
        finally:
            fake.gate(1).set()
            fake.hold_launches.set()
            bat.close()


class TestLaunchAndRead:
    """The real scorer's two halves at toy size."""

    def test_swap_between_launch_and_read(self, rng):
        """(c): the reference is taken at the launch."""
        cfg = _cfg_mem()
        pa, pb = _params(cfg, seed=0), _params(cfg, seed=1)
        sc = FixedShapeScorer(cfg, pa)
        sc.warmup()
        ids, vals = _examples(rng, 64)
        ref_a = sc.score(ids, vals)
        ref_b = FixedShapeScorer(cfg, pb).score(ids, vals)
        assert not np.array_equal(ref_a, ref_b)
        first = sc.launch_rung(ids.copy(), vals.copy(), None, 64)
        sc.swap(fm.FmParams(*[np.asarray(x) for x in pb]), step=2)
        second = sc.launch_rung(ids.copy(), vals.copy(), None, 64,
                                inflight=1)
        np.testing.assert_array_equal(sc.read_rung(first), ref_a)
        np.testing.assert_array_equal(sc.read_rung(second), ref_b)
        assert sc.steady_compiles == 0

    def test_dispatch_timer_is_the_two_halves_not_the_time_in_flight(
            self, rng):
        tel = obs.Telemetry()
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        sc.warmup()
        ids, vals = _examples(rng, 32)
        flight = sc.launch_rung(ids, vals, None, 32)
        time.sleep(0.2)  # in flight while the caller serves another
        sc.read_rung(flight)
        t = tel.snapshot()["timers"]
        assert t["serve.dispatch"]["count"] == 1
        assert t["serve.dispatch"]["total_s"] == pytest.approx(
            flight.launch_s + flight.readback_s, abs=1e-5)
        assert t["serve.dispatch"]["total_s"] < 0.2
        assert (t["serve.launch"]["total_s"]
                + t["serve.readback"]["total_s"]
                <= t["serve.dispatch"]["total_s"] + 1e-5)

    @pytest.mark.parametrize("field_num", [0, 3])
    def test_back_to_back_groups_of_one_rung_get_their_own_scores(
            self, rng, field_num):
        """(f) the staging buffers: group n+1 is filled and launched
        while n has not been read; each gets the scores of its own
        rows (field-aware too, where one group carries no fields)."""
        cfg = _cfg_mem(field_num=field_num) if field_num else _cfg_mem()
        tel = obs.Telemetry()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        sc.warmup()
        groups = []
        for k in range(6):
            ids, vals = _examples(rng, 64)
            fields = (rng.integers(0, field_num, ids.shape).astype(np.int32)
                      if field_num and k % 2 == 0 else None)
            groups.append((ids, vals, fields, sc.score(ids, vals, fields)))
        assert not np.array_equal(groups[0][3], groups[1][3])
        bat = ServeBatcher(sc, max_batch_wait_ms=1.0, telemetry=tel)
        # hold the first launch until all six full rungs are queued, so
        # that every later one is launched with the one before in flight
        go = threading.Event()
        launch = sc.launch_rung

        def held(*a, **kw):
            assert go.wait(10)
            return launch(*a, **kw)

        sc.launch_rung = held
        try:
            reqs = [bat.submit(i, v, f) for i, v, f, _ in groups]
            go.set()
            for req, (_, _, _, want) in zip(reqs, groups):
                np.testing.assert_array_equal(
                    bat.result(req, timeout=30), want)
        finally:
            go.set()
            bat.close()
        counters = tel.snapshot()["counters"]
        assert counters["serve.overlapped"] == 5
        assert sc.steady_compiles == 0
        # two sets a rung, taken in turn
        first, second = bat._pool(64), bat._pool(64)
        assert first[0] is not second[0] and bat._pool(64)[0] is first[0]


    def test_many_clients_each_get_their_own_scores(self, rng):
        """Stress: more client threads than cores, a short switch
        interval, sizes that close groups at once and sizes that wait;
        every reply is the scores of its own rows, whichever staging
        set and whichever side of an overlap its group took."""
        import sys

        cfg = _cfg_mem()
        tel = obs.Telemetry()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        sc.warmup()
        sizes = (1, 7, 30, 33, 40, 64)
        pool = []
        for k in range(24):
            ids, vals = _examples(rng, sizes[k % len(sizes)])
            pool.append((ids, vals, sc.score(ids, vals)))
        bat = ServeBatcher(sc, max_batch_wait_ms=0.3, telemetry=tel)
        wrong: list = []
        t_end = time.time() + 2.0

        def client(k):
            i = k
            while time.time() < t_end and not wrong:
                ids, vals, want = pool[i % len(pool)]
                try:
                    got = bat.score(ids, vals, timeout=30)
                except Exception as e:  # noqa: BLE001 - reported below
                    wrong.append(repr(e))
                    return
                if not np.array_equal(got, want):
                    wrong.append((i % len(pool), got, want))
                i += 5

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
            bat.close()
        assert not wrong, wrong[:1]
        counters = tel.snapshot()["counters"]
        assert counters["serve.batches"] > 24
        assert 0 < counters["serve.overlapped"] < counters["serve.batches"]
        assert bat.inflight == 0 and sc.steady_compiles == 0


# ----------------------------------------------------------------------
# hot swap
# ----------------------------------------------------------------------


class TestHotSwap:
    def test_swap_mid_traffic_never_torn(self, rng):
        """Concurrent traffic across a swap sees only old-table or
        new-table scores — never a mix — and no request fails."""
        cfg = _cfg_mem()
        pa, pb = _params(cfg, seed=0), _params(cfg, seed=1)
        tel = obs.Telemetry()
        sc = FixedShapeScorer(cfg, pa, telemetry=tel)
        sc.warmup()
        ids, vals = _examples(rng, 8)
        ref_a = sc.score(ids, vals)
        bat = ServeBatcher(sc, max_batch_wait_ms=0.5, telemetry=tel)
        try:
            # Compute the post-swap reference on a SEPARATE scorer so
            # the serving one only ever sees the swap itself.
            ref_b = FixedShapeScorer(cfg, pb).score(ids, vals)
            assert not np.array_equal(ref_a, ref_b)
            stop = threading.Event()
            seen: list = []
            errors: list = []

            def traffic():
                while not stop.is_set():
                    try:
                        seen.append(bat.score(ids, vals, timeout=10))
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                        return

            threads = [
                threading.Thread(target=traffic) for _ in range(3)
            ]
            for t in threads:
                t.start()
            time.sleep(0.15)
            sc.swap(
                fm.FmParams(*[np.asarray(x) for x in pb]), step=7
            )
            time.sleep(0.15)
            stop.set()
            for t in threads:
                t.join()
            assert not errors
            assert len(seen) > 4
            n_a = n_b = 0
            for s in seen:
                if np.array_equal(s, ref_a):
                    n_a += 1
                elif np.array_equal(s, ref_b):
                    n_b += 1
                else:
                    pytest.fail("a served microbatch mixed old and "
                                "new tables (torn swap)")
            assert n_b >= 1  # the swap actually took effect
            assert sc.steady_compiles == 0  # swap never recompiles
            assert sc.step == 7
            assert tel.snapshot()["counters"]["serve.swaps"] == 1
        finally:
            bat.close()

    def test_manifest_watcher_swaps(self, trained):
        """checkpoint.save republishing the manifest drives a watcher
        swap; the reloaded params change served scores."""
        tmp_path, cfg = trained
        fmt, step0, model = load_model(cfg)
        assert fmt == "dense"
        sc = make_scorer(cfg)
        sc.warmup()
        man = checkpoint.read_manifest(cfg.model_file)
        assert man is not None and man["step"] == step0
        watcher = CheckpointWatcher(cfg, sc, poll_secs=0.05)
        try:
            new_params = _params(cfg, seed=9)
            checkpoint.save(
                cfg.model_file, step0 + 100,
                fm.FmParams(*[np.asarray(x) for x in new_params]),
            )
            deadline = time.time() + 10
            while time.time() < deadline and sc.step != step0 + 100:
                time.sleep(0.05)
            assert sc.step == step0 + 100
            assert sc.steady_compiles == 0
        finally:
            watcher.close()
            # Restore the original checkpoint for the other tests.
            checkpoint.save(
                cfg.model_file, step0,
                fm.FmParams(*[np.asarray(x) for x in model]),
            )


# ----------------------------------------------------------------------
# end-to-end: HTTP server vs offline predict (bitwise), observability
# ----------------------------------------------------------------------


class TestEndToEnd:
    def test_served_scores_bitwise_identical_to_predict(self, trained):
        tmp_path, cfg = trained
        n = predict(cfg)
        offline = open(cfg.score_path).read().splitlines()
        assert len(offline) == n == 256
        handle = serve(cfg, port=0)
        try:
            lines = open(cfg.predict_files[0]).read()
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/score",
                data=lines.encode(), method="POST",
            )
            served = urllib.request.urlopen(
                req, timeout=60
            ).read().decode().splitlines()
            assert served == offline  # bitwise at full %.6f precision
            # Steady-state serving performed ZERO compiles: traffic
            # only ever hit precompiled ladder rungs.
            assert handle.scorer.steady_compiles == 0
            # Observability surface: tffm_serve_* series on /metrics,
            # the serve block on /status.
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/metrics", timeout=10
            ).read().decode()
            for series in ("tffm_counter_serve_requests_total",
                           "tffm_counter_serve_examples_total",
                           "tffm_timer_serve_latency_p99_ms",
                           "tffm_gauge_serve_batch_fill",
                           "tffm_timer_serve_compile_count",
                           # The serve record block renders too — the
                           # alertable scalars with no raw-instrument
                           # equivalent (qps, steady_compiles).
                           "tffm_serve_qps",
                           "tffm_serve_steady_compiles"):
                assert series in metrics, f"missing {series}"
            status = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/status", timeout=10
            ).read())
            assert status["record"] == "status"
            blk = status["serve"]
            assert blk["examples"] == 256
            assert blk["steady_compiles"] == 0
            assert blk["qps"] > 0
            assert "p99_ms" in blk
        finally:
            handle.close()

    def test_label_less_lines_accepted(self, trained):
        tmp_path, cfg = trained
        labeled = "1 5:0.5 9:0.25\n"
        bare = "5:0.5 9:0.25\n"
        ids_a, vals_a, _, na, _ = parse_request(labeled, cfg)
        ids_b, vals_b, _, nb, _ = parse_request(bare, cfg)
        assert na == nb == 1
        np.testing.assert_array_equal(ids_a, ids_b)
        np.testing.assert_array_equal(vals_a, vals_b)

    def test_truncation_counted(self, trained):
        """A request wider than max_features is a data-integrity event
        (the example scores as a DIFFERENT example) — parse_request
        reports the dropped occurrences instead of silently eating
        them."""
        tmp_path, cfg = trained  # max_features = 4
        wide = "0 " + " ".join(f"{i}:0.5" for i in range(7)) + "\n"
        ids, vals, _, n, truncated = parse_request(wide, cfg)
        assert n == 1
        assert truncated == 3
        assert (vals[0] != 0).sum() == cfg.max_features

    def test_malformed_line_rejected(self, trained):
        tmp_path, cfg = trained
        with pytest.raises(ValueError, match="line 1"):
            parse_request("not a libsvm line at:all:really:no\n", cfg)

    def test_missing_content_length_rejected(self, trained):
        """A body the handler cannot measure (chunked encoding) must be
        refused, not silently answered with zero scores."""
        import socket

        tmp_path, cfg = trained
        handle = serve(cfg, port=0)
        try:
            s = socket.create_connection(
                ("127.0.0.1", handle.port), timeout=10
            )
            s.sendall(
                b"POST /score HTTP/1.1\r\nHost: x\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
            )
            status_line = s.recv(4096).split(b"\r\n", 1)[0]
            s.close()
            assert b"411" in status_line
        finally:
            handle.close()

    def test_last_line_without_trailing_newline_is_scored(
        self, trained
    ):
        """The framing contract (SERVING.md): one example per
        non-blank LINE, and a final line without a trailing newline is
        still a line — ISSUE 12 flagged this as a potential
        silent-drop off-by-one, so it is pinned both at the parser and
        over the socket."""
        tmp_path, cfg = trained
        with_nl = "1 5:0.5 9:0.25\n0 3:1\n"
        without_nl = "1 5:0.5 9:0.25\n0 3:1"
        ids_a, vals_a, _, na, _ = parse_request(with_nl, cfg)
        ids_b, vals_b, _, nb, _ = parse_request(without_nl, cfg)
        assert na == nb == 2, (
            "a request whose last line lacks the trailing newline "
            "dropped an example"
        )
        np.testing.assert_array_equal(ids_a, ids_b)
        handle = serve(cfg, port=0)
        try:
            scores = []
            for body in (with_nl, without_nl):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{handle.port}/score",
                    data=body.encode(), method="POST",
                )
                scores.append(
                    urllib.request.urlopen(req, timeout=30).read()
                )
            assert scores[0] == scores[1]
            assert len(scores[0].splitlines()) == 2
        finally:
            handle.close()

    def test_binary_transport_bitwise_equals_text(self, trained):
        """/score_bin == /score bitwise for the same examples — both
        directly and proxied through a router mounted over the live
        replica — and the binary decode is accounted in its own
        serve.parse_bin timer."""
        tmp_path, cfg = trained
        handle = serve(cfg, port=0)
        router = None
        try:
            text = open(cfg.predict_files[0]).read()
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/score",
                data=text.encode(), method="POST",
            )
            text_scores = urllib.request.urlopen(
                req, timeout=60
            ).read().decode().splitlines()
            ids, vals, fields, n, _ = parse_request(text, cfg)
            frame = wire.encode_bin_request(ids, vals)
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/score_bin",
                data=frame, method="POST",
            )
            raw = urllib.request.urlopen(req, timeout=60).read()
            bin_scores = [
                f"{s:.6f}" for s in wire.decode_bin_response(raw)
            ]
            assert bin_scores == text_scores
            # Through a router over this live replica: still bitwise.
            router = ServeRouter(
                0, [Replica(0, "127.0.0.1", handle.port)], cfg,
            )
            raw = urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{router.port}/score_bin",
                data=frame, method="POST",
            ), timeout=60).read()
            routed_scores = [
                f"{s:.6f}" for s in wire.decode_bin_response(raw)
            ]
            assert routed_scores == text_scores
            blk = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/status", timeout=10
            ).read())["serve"]
            assert "parse_bin_p50_ms" in blk
            assert "inflight" in blk
        finally:
            if router is not None:
                router.close()
            handle.close()

    def test_transport_knob_gates_endpoints(self, trained):
        import dataclasses

        tmp_path, cfg = trained
        handle = serve(
            dataclasses.replace(cfg, serve_transport="text"), port=0
        )
        try:
            frame = wire.encode_bin_request(
                np.zeros((1, 4), np.int32), np.ones((1, 4), np.float32)
            )
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/score_bin",
                data=frame, method="POST",
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=30)
            assert exc.value.code == 404
            assert b"disabled" in exc.value.read()
        finally:
            handle.close()

    def test_malformed_bin_frame_rejected(self, trained):
        import struct

        tmp_path, cfg = trained
        handle = serve(cfg, port=0)
        try:
            for bad in (b"", b"XXXX" + b"\0" * 9,
                        wire.encode_bin_request(
                            np.zeros((2, 4), np.int32),
                            np.ones((2, 4), np.float32),
                        )[:-3],
                        # n of billions over an f=0 header: the length
                        # check must not be vacuous (a 13-byte body
                        # must never reach an [n, F] allocation).
                        struct.pack("<4sIIB", b"TFB1", 2**31, 0, 0)):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{handle.port}/score_bin",
                    data=bad, method="POST",
                )
                with pytest.raises(urllib.error.HTTPError) as exc:
                    urllib.request.urlopen(req, timeout=30)
                assert exc.value.code == 400
                exc.value.read()
        finally:
            handle.close()

    def test_admin_reload_promote_rollback(self, trained, rng):
        """The canary swap surface on a REAL scorer: only
        /reload?keep_prev=1 (the router's canary reload) retains the
        replaced params for /rollback; a plain /reload leaves no
        window (a stray admin call must neither pin a second table
        nor make the model flippable), and /promote closes it."""
        tmp_path, cfg = trained
        fmt, step0, model = load_model(cfg)
        handle = serve(cfg, port=0)
        base = f"http://127.0.0.1:{handle.port}"
        ids, vals = _examples(rng, 8)
        try:
            ref_old = handle.scorer.score(ids, vals)
            new_params = _params(cfg, seed=21)
            checkpoint.save(
                cfg.model_file, step0 + 50,
                fm.FmParams(*[np.asarray(x) for x in new_params]),
            )
            doc = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"{base}/reload?keep_prev=1", data=b"",
                    method="POST",
                ), timeout=60,
            ).read())
            assert doc["step"] == step0 + 50
            ref_new = handle.scorer.score(ids, vals)
            assert not np.array_equal(ref_old, ref_new)
            # A RETRIED keep_prev reload (a canary check that died
            # between reload and verdict) must anchor, not clobber,
            # the rollback target.
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/reload?keep_prev=1", data=b"", method="POST"
            ), timeout=60).read()
            # Rollback restores the exact ORIGINAL params.
            doc = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"{base}/rollback", data=b"", method="POST"
                ), timeout=60,
            ).read())
            assert doc["step"] == step0
            np.testing.assert_array_equal(
                handle.scorer.score(ids, vals), ref_old
            )
            # A second rollback has nothing to restore -> 409.
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/rollback", data=b"", method="POST"
                ), timeout=60)
            assert exc.value.code == 409
            exc.value.read()
            # A PLAIN reload opens no window at all: rollback 409s
            # and the new params stay.
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/reload", data=b"", method="POST"
            ), timeout=60).read()
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/rollback", data=b"", method="POST"
                ), timeout=60)
            assert exc.value.code == 409
            exc.value.read()
            np.testing.assert_array_equal(
                handle.scorer.score(ids, vals), ref_new
            )
            # keep_prev reload + PROMOTE: the window closes again.
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/reload?keep_prev=1", data=b"", method="POST"
            ), timeout=60).read()
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/promote", data=b"", method="POST"
            ), timeout=60).read()
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/rollback", data=b"", method="POST"
                ), timeout=60)
            assert exc.value.code == 409
            exc.value.read()
            assert handle.scorer.steady_compiles == 0
        finally:
            handle.close()
            # Restore the original checkpoint for the other tests.
            checkpoint.save(
                cfg.model_file, step0,
                fm.FmParams(*[np.asarray(x) for x in model]),
            )

    def test_serve_stream_and_report_compat(self, trained, tmp_path):
        """A serve run's metrics stream carries the serve block;
        tools/report.py --compare flattens serve.* keys and a training
        stream contributes none (back-compat n/a)."""
        import os
        import sys

        tools = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        )
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import report

        _, cfg = trained
        stream = tmp_path / "serve_metrics.jsonl"
        import dataclasses
        scfg = dataclasses.replace(cfg, metrics_file=str(stream))
        handle = serve(scfg, port=0)
        try:
            lines = open(cfg.predict_files[0]).read()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/score",
                data=lines.encode(), method="POST",
            ), timeout=60).read()
        finally:
            handle.close()
        recs = [json.loads(l) for l in open(stream)]
        kinds = {r["record"] for r in recs}
        assert "run_header" in kinds and "final" in kinds
        header = next(r for r in recs if r["record"] == "run_header")
        assert header["mode"] == "serve"
        # ISSUE 16: the accept-path shape is reconstructable from any
        # metrics stream (KD discipline for the new front-end knobs).
        assert header["serve_parse_mode"] == scfg.serve_parse_mode
        assert header["serve_http_threads"] == scfg.serve_http_threads
        assert (
            header["serve_http_acceptors"] == scfg.serve_http_acceptors
        )
        assert header["serve_request_queue_size"] >= 1
        final = next(r for r in recs if r["record"] == "final")
        assert final["serve"]["requests"] >= 1
        flat = report._comparable_metrics(str(stream))
        assert flat["serve.requests"] >= 1
        assert "serve.qps" in flat
        assert report._direction("serve.p99_ms") == "low"
        assert report._direction("serve_qps") == "high"
        assert report._direction("serve_batch_fill") == "high"
        assert report._direction("serve_steady_compiles") == "low"


# ----------------------------------------------------------------------
# tiered overlay predict/serve (direction-2 residue)
# ----------------------------------------------------------------------


class TestOverlay:
    @pytest.fixture()
    def overlay_cfg(self, tmp_path, rng, monkeypatch):
        """A tiered VIRTUAL run at tiny V: its checkpoint is the
        sparse overlay format (tiered.npz), no dense dirs."""
        monkeypatch.setattr(tiered, "EXACT_BYTES_MAX", 0)
        _write_data(tmp_path / "train.libsvm", rng)
        cfg = _cfg(tmp_path, "m", table_tiering="on", hot_rows=192)
        Trainer(cfg).train()
        assert checkpoint.exists_tiered(cfg.model_file)
        assert not checkpoint.exists(cfg.model_file)
        return cfg

    def test_overlay_predict_writes_scores(self, overlay_cfg):
        """The tiered-overlay refusal is gone: predict scores straight
        from tiered.npz via the compact per-batch remap."""
        n = predict(overlay_cfg)
        scores = np.loadtxt(overlay_cfg.score_path)
        assert n == len(scores) == 256
        assert np.all((scores > 0) & (scores < 1))

    def test_overlay_matches_full_table_scoring(self, overlay_cfg, rng):
        """Compact-remap scoring == scoring against the fully
        materialized logical table (the dense-parity oracle)."""
        fmt, step, (w0, store) = load_model(overlay_cfg)
        assert fmt == "tiered" and step == 8
        sc = make_scorer(overlay_cfg)
        assert isinstance(sc, OverlayScorer)
        ids, vals = _examples(rng, 50)
        got = sc.score(ids, vals)
        table = store.gather(np.arange(V))
        ref = np.asarray(jax.nn.sigmoid(fm.fm_scores(
            fm.FmParams(
                w0=jax.numpy.float32(w0),
                table=jax.numpy.asarray(table),
            ),
            ids, vals, None, factor_num=4, field_num=0,
        )))
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)

    def test_overlay_parity_vs_dense_format(self, tmp_path, rng):
        """A tiered EXACT run saves the DENSE format; predict from it
        must equal predict from an identical dense run — the overlay/
        dense interchange contract on the scoring side."""
        _write_data(tmp_path / "train.libsvm", rng)
        cfg_d = _cfg(tmp_path, "dense")
        Trainer(cfg_d).train()
        predict(cfg_d)
        dense_scores = open(cfg_d.score_path).read()
        cfg_t = _cfg(
            tmp_path, "tiered", table_tiering="on", hot_rows=192,
            score_path=str(tmp_path / "scores_t.txt"),
        )
        Trainer(cfg_t).train()
        assert checkpoint.exists(cfg_t.model_file)  # dense format
        predict(cfg_t)
        assert open(cfg_t.score_path).read() == dense_scores

    def test_overlay_descriptor_mismatch_refused(self, overlay_cfg):
        import dataclasses

        bad = dataclasses.replace(overlay_cfg, seed=99)
        with pytest.raises(ValueError, match="different init"):
            load_model(bad)

    def test_overlay_serve_deterministic_and_zero_steady(
        self, overlay_cfg, rng
    ):
        tel = obs.Telemetry()
        sc = make_scorer(overlay_cfg, telemetry=tel)
        sc.warmup()
        ids, vals = _examples(rng, 40)
        a = sc.score(ids, vals)
        # The first >8-unique-ids chunk lazily compiles a larger
        # compact-table bucket — EXPECTED by design, so it must not
        # read as the "shape escaped the ladder" latency-cliff signal.
        assert sc.steady_compiles == 0
        before = sc.compiles
        b = sc.score(ids, vals)
        np.testing.assert_array_equal(a, b)
        # Repeat traffic at a seen (rung, bucket) shape: no compile.
        assert sc.compiles == before
        assert tel.snapshot()["counters"].get(
            "serve.recompiles_unexpected", 0
        ) == 0


# ----------------------------------------------------------------------
# offline predict through the ladder
# ----------------------------------------------------------------------


class TestOfflinePredict:
    def test_predict_emits_accounted_compiles(self, trained, tmp_path):
        tmp, cfg = trained
        import dataclasses

        stream = tmp_path / "predict_metrics.jsonl"
        pcfg = dataclasses.replace(
            cfg, metrics_file=str(stream),
            score_path=str(tmp_path / "s.txt"),
        )
        n = predict(pcfg)
        assert n == 256
        compiles = [
            json.loads(l) for l in open(stream)
            if json.loads(l).get("record") == "compile"
        ]
        assert compiles, "predict compiles must surface as records"
        assert all(c["where"] == "serve" for c in compiles)
        # Every shape predict scores is in its ladder (batch_size is an
        # extra rung): nothing unexpected.
        assert all(c["expected"] for c in compiles)
