"""Model-quality & data-drift plane (obs/sketch.py + obs/quality.py):
sketch unit properties (merge, rank error, fixed memory), windowed
online eval parity, PSI fires-on-shift / quiet-on-identity, the
quality=off inert-knob + parity discipline, manifest sketch
publication, and training→serving skew end-to-end over real sockets.
"""

import json
import math
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.alerts import AlertEngine, parse_rules, resolved_signal
from fast_tffm_tpu.obs.quality import (
    QualityMonitor, ServeSkewMonitor, StreamSketch, window_auc,
    window_logloss,
)
from fast_tffm_tpu.obs.sketch import (
    FreqSketch, QuantileSketch, SketchSet, psi_freq, psi_quantile,
)

# ----------------------------------------------------------------------
# sketch unit properties
# ----------------------------------------------------------------------


class TestQuantileSketch:
    def test_rank_error_bound(self, rng):
        """The pinned accuracy claim: every estimated quantile's true
        rank is within 2% of the requested one at the default k, over
        a stream ~400x the sketch's capacity."""
        data = rng.normal(size=50_000)
        sk = QuantileSketch()
        for chunk in np.array_split(data, 137):  # ragged update sizes
            sk.update(chunk)
        assert sk.n == len(data)
        for q in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
            est = sk.quantile(q)
            true_rank = float(np.mean(data <= est))
            assert abs(true_rank - q) <= 0.02, (q, true_rank)

    def test_merge_order_independence_within_bound(self, rng):
        """Merge associativity, stated honestly: compaction makes
        different merge ORDERS produce different internal states, but
        every order's quantile estimates must stay within the rank
        bound of the full stream — so partial sketches combine like
        one stream regardless of worker scheduling."""
        data = rng.standard_gamma(2.0, size=30_000)
        parts = np.array_split(data, 3)

        def sketch(arr):
            s = QuantileSketch()
            s.update(arr)
            return s

        # (a + b) + c  vs  a + (b + c)
        left = sketch(parts[0]).merge(sketch(parts[1]))
        left.merge(sketch(parts[2]))
        right_tail = sketch(parts[1]).merge(sketch(parts[2]))
        right = sketch(parts[0]).merge(right_tail)
        assert left.n == right.n == len(data)
        for sk in (left, right):
            for q in (0.1, 0.5, 0.9):
                true_rank = float(np.mean(data <= sk.quantile(q)))
                assert abs(true_rank - q) <= 0.03, (q, true_rank)

    def test_fixed_memory(self, rng):
        """Retained items are O(k log n), not O(n): a 400k-element
        stream keeps under ~30 levels x k items."""
        sk = QuantileSketch()
        for _ in range(100):
            sk.update(rng.normal(size=4096))
        assert sk.n == 409_600
        assert sk.retained <= sk.k * 30
        before = sk.retained
        for _ in range(100):  # doubling n must not double retention
            sk.update(rng.normal(size=4096))
        assert sk.retained <= before + 2 * sk.k

    def test_empty_and_nonfinite(self):
        sk = QuantileSketch()
        assert sk.quantile(0.5) is None
        sk.update([np.inf, np.nan])
        assert sk.n == 0  # non-finite inputs never poison the sketch
        sk.update([1.0])
        assert sk.quantile(0.5) == 1.0


class TestFreqSketch:
    def test_merge_is_exact(self, rng):
        a, b = FreqSketch(), FreqSketch()
        ids_a = rng.integers(0, 10_000, 5000)
        ids_b = rng.integers(0, 10_000, 7000)
        a.update(ids_a)
        b.update(ids_b)
        both = FreqSketch()
        both.update(np.concatenate([ids_a, ids_b]))
        merged = FreqSketch()
        merged.merge(a).merge(b)
        np.testing.assert_array_equal(merged.counts, both.counts)
        assert merged.n == both.n == 12_000

    def test_bucket_mismatch_refused(self):
        with pytest.raises(ValueError, match="buckets"):
            FreqSketch(64).merge(FreqSketch(128))


class TestSerialization:
    def test_sketchset_json_roundtrip(self, rng):
        ss = SketchSet()
        for _ in range(20):
            ids = rng.integers(0, 5000, size=(64, 8))
            vals = np.where(rng.random((64, 8)) < 0.7,
                            rng.normal(size=(64, 8)), 0.0)
            ss.update_batch(ids, vals)
        ss.update_scores(rng.random(500))
        doc = json.loads(json.dumps(ss.to_dict()))  # through real JSON
        back = SketchSet.from_dict(doc)
        assert back.examples == ss.examples
        np.testing.assert_array_equal(back.ids.counts, ss.ids.counts)
        # A roundtripped sketch judged against its source is identity.
        psi = back.psi_vs(ss)
        assert psi["psi_max"] <= 0.02, psi


class TestPsi:
    def test_identity_quiet_shift_fires(self, rng):
        base = rng.normal(size=20_000)
        same = rng.normal(size=20_000)
        shifted = rng.normal(1.5, size=20_000)
        s_base, s_same, s_shift = (
            QuantileSketch(), QuantileSketch(), QuantileSketch()
        )
        s_base.update(base)
        s_same.update(same)
        s_shift.update(shifted)
        assert psi_quantile(s_base, s_same) < 0.05
        assert psi_quantile(s_base, s_shift) > 0.25

        f_base, f_same = FreqSketch(), FreqSketch()
        f_base.update(rng.integers(0, 1000, 20_000))
        f_same.update(rng.integers(0, 1000, 20_000))
        assert psi_freq(f_base, f_same) < 0.05
        # Concentration shift (traffic collapsing onto 10x fewer
        # rows): the canonical occupancy drift, read as SHIFTED.
        f_narrow = FreqSketch()
        f_narrow.update(rng.integers(5000, 5100, 20_000))
        assert psi_freq(f_base, f_narrow) > 0.25
        # Matched-density disjoint swap: the documented weak case —
        # still reads as drifting, not stable.
        f_disjoint = FreqSketch()
        f_disjoint.update(rng.integers(5000, 6000, 20_000))
        assert psi_freq(f_base, f_disjoint) > 0.1

    def test_small_window_identity_debiased(self, rng):
        """The debias property thresholds rely on: two SMALL samples
        of the same distribution read ~0, not sampling noise."""
        f1, f2 = FreqSketch(), FreqSketch()
        f1.update(rng.integers(0, 50, 500))
        f2.update(rng.integers(0, 50, 500))
        assert psi_freq(f1, f2) < 0.05

    def test_empty_is_none_not_zero(self):
        assert psi_quantile(QuantileSketch(), QuantileSketch()) is None
        assert psi_freq(FreqSketch(), FreqSketch()) is None
        assert SketchSet().psi_vs(SketchSet()) == {}

    def test_constant_reference(self, rng):
        """A constant reference stream (degenerate cut points) must
        still compare, and still see a moved live stream."""
        ref, same, moved = (
            QuantileSketch(), QuantileSketch(), QuantileSketch()
        )
        ref.update(np.ones(1000))
        same.update(np.ones(1000))
        moved.update(np.full(1000, 5.0))
        assert psi_quantile(ref, same) < 0.05
        assert psi_quantile(ref, moved) > 0.25


# ----------------------------------------------------------------------
# windowed online eval
# ----------------------------------------------------------------------


class TestOnlineEval:
    def test_window_auc_exact_vs_pairwise(self, rng):
        """The windowed AUC is EXACT (weighted Mann-Whitney with
        midranks) — pinned against the O(n^2) definition, ties and
        weights included."""
        s = np.round(rng.random(600), 2)  # plenty of ties
        y = (rng.random(600) < 0.4).astype(float)
        w = rng.uniform(0.5, 2.0, 600)
        got = window_auc(s, y, w)
        P, WP = s[y > 0], w[y > 0]
        N, WN = s[y <= 0], w[y <= 0]
        cmp = ((P[:, None] > N[None, :]).astype(float)
               + 0.5 * (P[:, None] == N[None, :]))
        want = float((WP[:, None] * WN[None, :] * cmp).sum()
                     / (WP.sum() * WN.sum()))
        assert abs(got - want) < 1e-12

    def test_single_class_window_is_none(self):
        assert window_auc(np.array([0.5, 0.6]), np.array([1.0, 1.0]),
                          np.ones(2)) is None

    def test_windowed_stream_vs_exact_batch_parity(self, rng):
        """Online (chunked, ring-buffered) eval == exact batch eval
        over the same most-recent window examples, on a synthetic
        stream longer than the window."""
        window = 1000
        mon = QualityMonitor(loss_type="logistic", window=window)
        raw_all, y_all = [], []
        for _ in range(7):  # 7 x 400 = 2800 > window
            raw = rng.normal(size=400)
            p = 1 / (1 + np.exp(-raw))
            y = (rng.random(400) < p).astype(float)
            mon.observe(raw, y, np.ones(400))
            raw_all.append(raw)
            y_all.append(y)
        raw_all = np.concatenate(raw_all)
        y_all = np.concatenate(y_all)
        p_last = 1 / (1 + np.exp(-raw_all[-window:]))
        y_last = y_all[-window:]
        w = np.ones(window)
        block = mon.block()
        assert block["window_examples"] == window
        assert abs(block["logloss"]
                   - window_logloss(p_last, y_last, w)) < 1e-6
        assert abs(block["auc"] - window_auc(p_last, y_last, w)) < 1e-6

    def test_calib_ratio(self):
        mon = QualityMonitor(loss_type="mse", window=100)
        scores = np.full(100, 0.6)
        labels = (np.arange(100) < 30).astype(float)  # rate 0.3
        mon.observe(scores, labels, np.ones(100))
        block = mon.block()
        assert abs(block["calib_ratio"] - 2.0) < 1e-6
        assert abs(block["score_mean"] - 0.6) < 1e-6
        assert abs(block["label_rate"] - 0.3) < 1e-6

    def test_logloss_drift_rises_on_degradation(self, rng):
        """Stationary stream -> drift ~1; a model that starts scoring
        anti-correlated windows -> drift well above 1."""
        window = 200
        mon = QualityMonitor(loss_type="logistic", window=window)
        t = [0.0]

        def block():
            t[0] += 1.0  # sidestep the memo; one baseline sample per
            return mon.block(now=t[0])  # full window of new examples

        for _ in range(6):  # healthy windows build the baseline
            raw = rng.normal(size=window)
            y = (rng.random(window) < 1 / (1 + np.exp(-raw))).astype(float)
            mon.observe(raw, y, np.ones(window))
            healthy = block()
        assert 0.8 <= healthy.get("logloss_drift", 1.0) <= 1.2
        for _ in range(2):  # poisoned windows: labels flipped
            raw = rng.normal(size=window)
            y = (rng.random(window) >= 1 / (1 + np.exp(-raw))).astype(float)
            mon.observe(raw, y, np.ones(window))
            bad = block()
        assert bad["logloss_drift"] > 1.2, bad


# ----------------------------------------------------------------------
# StreamSketch rotation + drift signals + alert integration
# ----------------------------------------------------------------------


def _feed(sketch, rng, n_batches, id_lo, id_hi, val_scale=1.0):
    for _ in range(n_batches):
        ids = rng.integers(id_lo, id_hi, size=(64, 8))
        vals = np.where(rng.random((64, 8)) < 0.75,
                        rng.random((64, 8)) * val_scale, 0.0)
        sketch.update_batch(ids, vals)


class TestStreamSketch:
    def test_rotation_and_adjacent_window_psi(self, rng):
        ss = StreamSketch(window_examples=512)
        _feed(ss, rng, 16, 0, 1000)  # 1024 identity examples
        assert ss.rotations >= 1
        quiet = ss.psi()
        assert quiet and quiet["psi_max"] < 0.1, quiet
        # Mid-transition (shifted window filling against an identity
        # prev) the drift is loud...
        _feed(ss, rng, 6, 50_000, 50_200, val_scale=40.0)
        loud = ss.psi()
        assert loud["psi_values"] > 0.25, loud
        assert loud["psi_ids"] > 0.25, loud
        # ...and once the NEW regime fills adjacent windows of its
        # own, the rolling baseline self-heals back to quiet.
        _feed(ss, rng, 26, 50_000, 50_200, val_scale=40.0)
        healed = ss.psi()
        assert healed["psi_max"] < 0.1, healed
        # total keeps accumulating across rotations
        assert ss.examples == 48 * 64

    def test_absorb_matches_direct(self, rng):
        """A worker-shipped delta stream reconstructs the same totals
        as direct updates (the procpool contract)."""
        direct = StreamSketch(window_examples=10_000)
        via_deltas = StreamSketch(window_examples=10_000)
        for _ in range(8):
            ids = rng.integers(0, 5000, size=(32, 8))
            vals = rng.random((32, 8))
            direct.update_batch(ids, vals)
            delta = SketchSet()
            delta.update_batch(ids, vals)
            via_deltas.absorb(delta.to_dict())
        assert via_deltas.examples == direct.examples
        np.testing.assert_array_equal(
            via_deltas.total.ids.counts, direct.total.ids.counts
        )

    def test_alert_rule_fires_on_injected_drift(self, rng):
        """The acceptance demo: `quality.psi_values > 0.2 for 3 : warn`
        fires on an injected distribution shift and stays quiet on
        identity — through the REAL AlertEngine over REAL quality
        blocks."""
        rules = parse_rules("quality.psi_values > 0.2 for 3 : warn")
        engine = AlertEngine(rules)
        ss = StreamSketch(window_examples=512)
        mon = QualityMonitor(window=256, sketch=ss)
        t = [0.0]

        def beat():
            t[0] += 1.0
            return engine.observe(
                {"record": "heartbeat", "step": int(t[0]),
                 "quality": mon.block(now=t[0])}
            )

        for _ in range(12):  # identity traffic: no alert
            _feed(ss, rng, 2, 0, 1000)
            assert beat() == []
        assert engine.fired_total == 0
        fired = []
        # Injected shift, beating at a realistic many-beats-per-window
        # cadence (1 batch per beat, 8 beats per window): the breach
        # sustains across the transition and `for 3` fires.
        for _ in range(12):
            _feed(ss, rng, 1, 80_000, 80_200, val_scale=30.0)
            fired += beat()
        assert engine.fired_total >= 1
        assert fired and fired[0]["signal"] == "quality.psi_values"
        assert fired[0]["action"] == "warn"

    def test_quality_aliases_resolve(self):
        assert resolved_signal("logloss_drift") == "quality.logloss_drift"
        assert resolved_signal("calib_ratio") == "quality.calib_ratio"
        assert resolved_signal("psi_max") == "quality.psi_max"


# ----------------------------------------------------------------------
# the fold, against the loop it replaced
# ----------------------------------------------------------------------


class _ParentQuantile:
    """``QuantileSketch`` as it stood before the fold was split and
    vectorised: a float64 copy of every value, levels as Python lists,
    ``sorted()`` in the cascade.  Kept as the reference."""

    def __init__(self, k=128):
        self.k, self.n = k, 0
        self.levels, self.flip = [[]], [False]
        self.min, self.max = math.inf, -math.inf

    def update(self, values):
        arr = np.asarray(values, np.float64).reshape(-1)
        arr = arr[np.isfinite(arr)]
        if arr.size == 0:
            return
        self.n += int(arr.size)
        self.min = min(self.min, float(arr.min()))
        self.max = max(self.max, float(arr.max()))
        if arr.size > QuantileSketch.UPDATE_CAP:
            stride = -(-arr.size // QuantileSketch.UPDATE_CAP)
            arr = arr[(self.n + stride - 1) % stride::stride]
        self.levels[0].extend(arr.tolist())
        i = 0
        while i < len(self.levels) and len(self.levels[i]) >= 2 * self.k:
            items = sorted(self.levels[i])
            keep = items[1 if self.flip[i] else 0::2]
            self.flip[i] = not self.flip[i]
            self.levels[i] = []
            if i + 1 == len(self.levels):
                self.levels.append([])
                self.flip.append(False)
            self.levels[i + 1].extend(keep)
            i += 1

    def to_dict(self):
        r6 = lambda x: float(f"{x:.6g}")  # noqa: E731
        return {"k": self.k, "n": self.n,
                "min": r6(self.min) if self.n else None,
                "max": r6(self.max) if self.n else None,
                "levels": [[r6(x) for x in lvl] for lvl in self.levels]}


class _ParentSketchSet:
    """``SketchSet.update_batch`` / ``FreqSketch.update`` likewise."""

    def __init__(self, buckets=512):
        self.values, self.lengths = _ParentQuantile(), _ParentQuantile()
        self.scores = _ParentQuantile()
        self.buckets, self.ids_n, self.examples = buckets, 0, 0
        self.counts = np.zeros(buckets, np.int64)

    def update_batch(self, ids, vals, weights=None):
        ids, vals = np.asarray(ids), np.asarray(vals)
        if vals.ndim == 1:
            ids, vals = ids.reshape(1, -1), vals.reshape(1, -1)
        if weights is not None:
            rows = np.asarray(weights).reshape(-1) > 0
            ids, vals = ids[rows], vals[rows]
        if vals.shape[0] == 0:
            return
        real = vals != 0
        self.values.update(vals[real])
        self.lengths.update(real.sum(axis=1))
        arr = ids[real].reshape(-1)
        with np.errstate(over="ignore"):
            h = (arr.astype(np.uint64) * FreqSketch._MIX) >> np.uint64(17)
        self.counts += np.bincount(
            (h % np.uint64(self.buckets)).astype(np.int64),
            minlength=self.buckets,
        )
        self.ids_n += int(arr.size)
        self.examples += int(vals.shape[0])

    def to_dict(self):
        return {"version": 1, "examples": self.examples,
                "values": self.values.to_dict(),
                "lengths": self.lengths.to_dict(),
                "ids": {"buckets": self.buckets, "n": self.ids_n,
                        "counts": self.counts.tolist()},
                "scores": self.scores.to_dict()}


def _fold_stream(case, seed=0):
    """(ids, vals, weights, scores) batches of one equality case."""
    rng = np.random.default_rng(seed)
    n, f = case.get("shape", (1024, 39))
    dtype = case.get("dtype", np.float32)
    for _ in range(case.get("batches", 24)):
        ids = rng.integers(-5, 1 << 40, size=(n, f))
        vals = (rng.standard_normal((n, f)) * 3).astype(dtype)
        if case.get("pad"):
            # A zero value is a padded slot; a tail of them is what the
            # parsers leave, a scattered one is as legal.
            vals[rng.random((n, f)) < 0.2] = 0
            vals[:, f - 3:][rng.random((n, 3)) < 0.7] = 0
        if case.get("nonfinite"):
            vals[rng.random((n, f)) < 0.01] = np.nan
            vals[rng.random((n, f)) < 0.01] = np.inf
            vals[rng.random((n, f)) < 0.01] = -np.inf
        weights = None
        if case.get("zero_weights"):
            weights = (rng.random(n) < 0.8).astype(np.float32)
        if case.get("one_d"):
            ids, vals = ids[0], vals[0]
        scores = rng.random(n if not case.get("one_d") else 1)
        yield ids, vals, weights, scores.astype(dtype)


_FOLD_CASES = {
    "1024x39": {},
    "8192x39": {"shape": (8192, 39), "batches": 12},
    "padded": {"pad": True},
    "zero_weights": {"zero_weights": True},
    "all_weights_zero": {"zero_weights": True, "shape": (3, 39),
                         "batches": 40},
    "nonfinite": {"nonfinite": True},
    "everything_float64": {"pad": True, "nonfinite": True,
                           "zero_weights": True, "dtype": np.float64},
    "buckets_500": {"buckets": 500, "pad": True},
    "buckets_500_8192": {"buckets": 500, "shape": (8192, 39),
                         "batches": 6},
    "one_example_1d": {"one_d": True, "batches": 600},
    "int32_ids_float64_vals": {"dtype": np.float64, "int32": True},
    # 96 x 4,096 inserted values cascade seven levels of k=128
    "long_stream": {"batches": 96},
}


class TestFoldEquality:
    @pytest.mark.parametrize("name", sorted(_FOLD_CASES))
    def test_to_dict_equals_the_parent_fold(self, name):
        case = _FOLD_CASES[name]
        buckets = case.get("buckets", 512)
        ref = _ParentSketchSet(buckets)
        new = SketchSet(buckets=buckets)
        for ids, vals, weights, scores in _fold_stream(case):
            if case.get("int32"):
                ids = (ids % (1 << 31)).astype(np.int32)
            before = (ids.copy(), vals.copy())
            ref.update_batch(ids, vals, weights)
            new.update_batch(ids, vals, weights)
            ref.scores.update(scores)
            new.update_scores(scores)
            # the in-place hash works on the fold's own buffer
            np.testing.assert_array_equal(ids, before[0])
            np.testing.assert_array_equal(vals, before[1])
        assert new.to_dict() == ref.to_dict()
        if name == "long_stream":
            assert len(new.values.to_dict()["levels"]) >= 5
        # what the levels are read through: same numbers as the lists
        ref_levels = ref.values.levels
        flat = sorted(x for lvl in ref_levels for x in lvl)
        if flat:
            assert new.values.retained == len(flat)
            assert new.values.quantile(0.0) == ref.values.min
            total = sum(len(lvl) << i for i, lvl in enumerate(ref_levels))
            assert new.values.rank(flat[-1]) == 1.0
            assert new.values._weighted()[1][-1] == total

    def test_window_rotates_twice_on_the_same_sketches(self):
        """One folded batch applied to both views under the lock: the
        stream's ``total`` and each completed window equal the parent's
        per-view folds of the same batches."""
        ss = StreamSketch(window_examples=8192)
        ref_total, ref_window, ref_done = (
            _ParentSketchSet(), _ParentSketchSet(), [])
        for ids, vals, weights, scores in _fold_stream(
                {"pad": True, "zero_weights": True, "batches": 28}):
            ss.update_batch(ids, vals, weights)
            ss.update_scores(scores)
            ref_total.update_batch(ids, vals, weights)
            ref_window.update_batch(ids, vals, weights)
            if ref_window.examples >= 8192:  # a batch's fold rotates
                ref_done.append(ref_window)
                ref_window = _ParentSketchSet()
            ref_total.scores.update(scores)
            ref_window.scores.update(scores)
        assert ss.rotations == len(ref_done) >= 2
        assert ss.total.to_dict() == ref_total.to_dict()
        assert ss.window.to_dict() == ref_window.to_dict()
        assert ss.prev.to_dict() == ref_done[-1].to_dict()
        assert ss.prev2.to_dict() == ref_done[-2].to_dict()

    def test_serve_monitor_folds_the_same_sketch(self):
        mon = ServeSkewMonitor(window_examples=1 << 30)
        ref = _ParentSketchSet()
        for ids, vals, _, scores in _fold_stream({"pad": True,
                                                  "batches": 8}):
            mon.observe_batch(ids, vals)
            mon.observe_scores(scores)
            ref.update_batch(ids, vals)
            ref.scores.update(scores)
        assert mon.live.to_dict() == ref.to_dict()

    def test_serialized_levels_round_trip_and_merge(self):
        a, b = SketchSet(), SketchSet()
        for i, (ids, vals, _, scores) in enumerate(
                _fold_stream({"pad": True, "batches": 10})):
            (a if i % 2 else b).update_batch(ids, vals)
            (a if i % 2 else b).update_scores(scores)
        doc = json.loads(json.dumps(a.to_dict()))
        back = SketchSet.from_dict(doc)
        assert back.to_dict() == doc
        merged = SketchSet.from_dict(doc).merge(b)
        assert merged.examples == a.examples + b.examples
        assert merged.values.n == a.values.n + b.values.n
        assert merged.values.retained < 2 * 128 * len(
            merged.values.to_dict()["levels"])
        assert b.to_dict() == SketchSet.from_dict(b.to_dict()).to_dict()

    @pytest.mark.parametrize("values", [
        [], [np.nan, np.inf], np.float32([1.5, np.nan, -2.0]),
        np.arange(5), [True, False], 3.25,
    ], ids=["empty", "no_finite", "float32_nan", "ints", "bools",
            "scalar"])
    def test_quantile_update_small_inputs(self, values):
        ref, new = _ParentQuantile(), QuantileSketch()
        ref.update(values)
        new.update(values)
        assert new.to_dict() == ref.to_dict()

    def test_histogram_of_another_width_is_refused(self):
        with pytest.raises(ValueError):
            FreqSketch(512).apply(FreqSketch.histogram([1, 2, 3], 500))


class TestStreamSketchThreads:
    THREADS = 8

    def _batches(self, n_batches):
        return [b[:3] for b in _fold_stream(
            {"pad": True, "zero_weights": True, "shape": (256, 39),
             "batches": n_batches}, seed=7)]

    def test_eight_threads_equal_a_serial_fold(self):
        """No update lost or doubled with the arithmetic outside the
        lock: everything order-free equals a serial fold."""
        batches = self._batches(8 * 24)
        window = 4096
        serial = StreamSketch(window_examples=1 << 40)
        for ids, vals, weights in batches:
            serial.update_batch(ids, vals, weights)
        ss = StreamSketch(window_examples=window)
        errors = []

        def work(mine):
            try:
                for ids, vals, weights in mine:
                    ss.update_batch(ids, vals, weights)
                    ss.update_scores(np.float32([0.25, 0.5]))
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [
            threading.Thread(target=work, args=(batches[i::self.THREADS],))
            for i in range(self.THREADS)
        ]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        want, got = serial.total, ss.total
        assert got.examples == want.examples
        np.testing.assert_array_equal(got.ids.counts, want.ids.counts)
        assert got.ids.n == want.ids.n
        for axis in ("values", "lengths"):
            a, b = getattr(got, axis), getattr(want, axis)
            assert (a.n, a._min, a._max) == (b.n, b._min, b._max)
        assert got.scores.n == 2 * len(batches)
        # every batch here is far smaller than a window and a window
        # closes on the batch that fills it, so the count of rotations
        # is that of the examples whatever the order
        sizes = sorted(int((w > 0).sum()) for _, _, w in batches)
        assert ss.rotations >= want.examples // (window + sizes[-1])
        assert ss.rotations <= want.examples // window
        assert ss.window.examples < window
        assert ss.prev is not None and ss.prev.examples >= window

    def test_rotation_count_is_floor_of_examples_over_window(self):
        """Batches that divide the window: exactly floor(n / window)
        rotations, under threads too."""
        rng = np.random.default_rng(3)
        ss = StreamSketch(window_examples=1024)
        batches = [(rng.integers(0, 1 << 20, size=(128, 8)),
                    rng.random((128, 8)) + 0.5) for _ in range(8 * 9)]
        threads = [
            threading.Thread(
                target=lambda mine: [ss.update_batch(*b) for b in mine],
                args=(batches[i::self.THREADS],))
            for i in range(self.THREADS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert ss.examples == 128 * len(batches)
        assert ss.rotations == ss.examples // 1024 == 9
        assert ss.window.examples == 0


# ----------------------------------------------------------------------
# config: inert-knob discipline
# ----------------------------------------------------------------------


class TestConfig:
    def _kw(self, tmp_path):
        return dict(
            model_file=str(tmp_path / "m"),
            heartbeat_secs=1.0,
        )

    def test_refuses_quality_rules_when_off(self, tmp_path):
        with pytest.raises(ValueError, match="quality"):
            FmConfig(
                quality=False,
                alert_rules="quality.psi_values > 0.2 : warn",
                **self._kw(tmp_path),
            )
        with pytest.raises(ValueError, match="quality"):
            FmConfig(
                quality=False,
                alert_rules="logloss_drift > 2 : halt",
                **self._kw(tmp_path),
            )

    def test_quality_rules_accepted_when_on(self, tmp_path):
        cfg = FmConfig(
            alert_rules="quality.psi_values > 0.2 for 3 : warn",
            **self._kw(tmp_path),
        )
        assert cfg.quality

    def test_refuses_skew_rules_when_off(self, tmp_path):
        """serve.skew_* keys only exist when the skew monitor does —
        same inertness hazard as the quality.* rules."""
        with pytest.raises(ValueError, match="quality"):
            FmConfig(
                quality=False,
                alert_rules="serve.skew_psi_max > 0.25 for 3 : warn",
                **self._kw(tmp_path),
            )

    def test_quality_window_validated(self, tmp_path):
        with pytest.raises(ValueError, match="quality_window"):
            FmConfig(quality_window=0, model_file=str(tmp_path / "m"))
        # A window below the judgeable mass would silently disable the
        # PSI signals — refused, and the config's literal must agree
        # with the quality plane's constant.
        from fast_tffm_tpu.obs.quality import _MIN_PSI_EXAMPLES

        assert _MIN_PSI_EXAMPLES == 32
        with pytest.raises(ValueError, match="judgeable"):
            FmConfig(quality_window=16, model_file=str(tmp_path / "m"))
        FmConfig(quality_window=32, model_file=str(tmp_path / "m"))

    def test_cli_no_quality_flag(self):
        from fast_tffm_tpu.cli import build_argparser

        args = build_argparser().parse_args(
            ["train", "x.cfg", "--no_quality"]
        )
        assert args.no_quality
        args2 = build_argparser().parse_args(
            ["train", "x.cfg", "--quality_window", "1234"]
        )
        assert args2.quality_window == 1234


# ----------------------------------------------------------------------
# trainer integration: parity, quality block, manifest publication
# ----------------------------------------------------------------------


def _write_libsvm(path, n_lines, vocab=50, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n_lines):
            feats = rng.choice(vocab, size=3, replace=False)
            toks = " ".join(f"{i}:{rng.uniform(0.1, 1):.3f}" for i in feats)
            f.write(f"{rng.integers(0, 2)} {toks}\n")
    return str(path)


@pytest.fixture(scope="module")
def train_file(tmp_path_factory):
    out = tmp_path_factory.mktemp("quality_data")
    return _write_libsvm(out / "train.libsvm", 320)


def _train_cfg(data, tmp_path, tag, **kw):
    defaults = dict(
        vocabulary_size=50, factor_num=4,
        model_file=str(tmp_path / f"model_{tag}"),
        train_files=[data], epoch_num=1, batch_size=32,
        max_features=4, log_steps=0, thread_num=2,
        steps_per_dispatch=2, seed=3, quality_window=64,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


class TestTrainerQuality:
    def test_quality_off_is_bitwise_identical(self, train_file, tmp_path):
        """The inert-knob parity pin: quality on vs off trains to
        BITWISE-identical parameters (the scan emits scores but the
        carry math is untouched)."""
        from fast_tffm_tpu.train.loop import Trainer

        params = {}
        for tag, on in (("qon", True), ("qoff", False)):
            cfg = _train_cfg(train_file, tmp_path, tag, quality=on)
            trainer = Trainer(cfg)
            results = trainer.train()
            params[tag] = (trainer.state.params, results)
        on_p, on_res = params["qon"]
        off_p, off_res = params["qoff"]
        np.testing.assert_array_equal(
            np.asarray(on_p.table), np.asarray(off_p.table)
        )
        np.testing.assert_array_equal(
            np.asarray(on_p.w0), np.asarray(off_p.w0)
        )
        assert on_res["train"]["loss"] == off_res["train"]["loss"]
        # The block rides results only when the plane is on.
        assert "quality" in on_res["train"]
        assert "quality" not in off_res["train"]

    def test_quality_block_and_manifest(self, train_file, tmp_path):
        from fast_tffm_tpu.train.loop import Trainer
        from fast_tffm_tpu.train.manifest import read_manifest

        mf = str(tmp_path / "metrics_q.jsonl")
        cfg = _train_cfg(
            train_file, tmp_path, "blk", metrics_file=mf,
            heartbeat_secs=0.05,
        )
        res = Trainer(cfg).train()
        q = res["train"]["quality"]
        for key in ("examples", "logloss", "window_examples",
                    "sketch_examples"):
            assert key in q, q
        assert q["examples"] == 320
        # Every parsed example was sketched (thread-worker path).
        assert q["sketch_examples"] == 320
        records = [json.loads(line) for line in open(mf)]
        header = records[0]
        assert header["quality"] is True
        assert header["quality_window"] == 64
        final = [r for r in records if r["record"] == "final"][-1]
        assert "quality" in final
        # The manifest carries the skew reference next to the step.
        man = read_manifest(cfg.model_file)
        assert man["quality"]["examples"] == 320
        ref = SketchSet.from_dict(man["quality"]["sketches"])
        assert ref.examples == 320
        assert ref.scores.n > 0  # training scores sketched too
        # Self-skew of the reference is ~0.
        assert ref.psi_vs(ref)["psi_max"] <= 0.01

    def test_process_workers_ship_sketches(self, train_file, tmp_path):
        """The procpool channel: sketches computed IN spawned workers
        arrive complete (periodic deltas + the done-flush)."""
        from fast_tffm_tpu.train.loop import Trainer
        from fast_tffm_tpu.train.manifest import read_manifest

        cfg = _train_cfg(
            train_file, tmp_path, "procs", parse_processes=2,
        )
        res = Trainer(cfg).train()
        assert res["train"]["quality"]["sketch_examples"] == 320
        man = read_manifest(cfg.model_file)
        assert man["quality"]["examples"] == 320

    def test_parse_thread_fold_is_a_phase(self, train_file, tmp_path,
                                          monkeypatch):
        """Timers ``ingest.sketch`` (the whole fold, one a batch) and
        ``ingest.sketch_lock`` (its part at the accumulator's lock),
        and the annotation ``tffm:ingest.sketch`` with ``n``."""
        from fast_tffm_tpu.obs import telemetry as tel_mod
        from fast_tffm_tpu.train.loop import Trainer

        spans = []

        class Annotation:
            def __init__(self, name, **stats):
                self.item = (name, stats)

            @staticmethod
            def is_enabled():
                return True

            def __enter__(self):
                spans.append(self.item)

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(tel_mod, "_annotation", lambda: Annotation)
        trainer = Trainer(_train_cfg(train_file, tmp_path, "phase"))
        res = trainer.train()
        timers = trainer.telemetry.snapshot()["timers"]
        fold, lock = timers["ingest.sketch"], timers["ingest.sketch_lock"]
        assert fold["count"] == lock["count"] == 320 // 32
        assert 0 < lock["total_s"] <= fold["total_s"]
        mine = [st for name, st in spans if name == "tffm:ingest.sketch"]
        assert len(mine) == 10 and all(st == {"n": 32} for st in mine)
        assert res["train"]["quality"]["sketch_examples"] == 320
        # quality off: no fold, no timer observation, no span
        spans.clear()
        off = Trainer(_train_cfg(train_file, tmp_path, "phase_off",
                                 quality=False))
        off.train()
        assert off.telemetry.snapshot()["timers"].get(
            "ingest.sketch", {"count": 0})["count"] == 0
        assert not [n for n, _ in spans if n == "tffm:ingest.sketch"]

    def test_sketch_failure_never_kills_training(self, train_file,
                                                 tmp_path,
                                                 monkeypatch):
        """The observer contract: a sketching exception on the parse
        path degrades the quality plane, it must never surface through
        the worker's fatal error path and abort the run."""
        from fast_tffm_tpu.train.loop import Trainer

        def boom(self, *a, **kw):
            raise MemoryError("injected sketch failure")

        monkeypatch.setattr(StreamSketch, "update_batch", boom)
        cfg = _train_cfg(train_file, tmp_path, "sketchfail")
        res = Trainer(cfg).train()  # must complete despite the raise
        assert res["train"]["examples"] == 320
        # The plane degraded: no ingest sketch mass, eval still ran.
        q = res["train"]["quality"]
        assert q["sketch_examples"] == 0
        assert q["examples"] == 320

    def test_quality_off_manifest_has_no_payload(self, train_file,
                                                 tmp_path):
        from fast_tffm_tpu.train.loop import Trainer
        from fast_tffm_tpu.train.manifest import read_manifest

        cfg = _train_cfg(train_file, tmp_path, "noq", quality=False)
        Trainer(cfg).train()
        man = read_manifest(cfg.model_file)
        assert "quality" not in man


# ----------------------------------------------------------------------
# serving: skew detection end-to-end over real sockets
# ----------------------------------------------------------------------


def _post(url, body, timeout=30):
    req = urllib.request.Request(url, data=body)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _serve_block_when(url, ready, timeout=10.0):
    """/status's serve block once ``ready(block)`` (or the last one
    read): the dispatcher folds a group into the skew sketches after
    it has released the reply, so a scrape right behind a reply may
    come before the fold."""
    deadline = time.monotonic() + timeout
    while True:
        block = _get_json(url + "/status")["serve"]
        if ready(block) or time.monotonic() > deadline:
            return block
        time.sleep(0.05)


@pytest.fixture(scope="module")
def served(tmp_path_factory, train_file):
    """One trained checkpoint with manifest sketches, shared by the
    serving skew tests."""
    tmp_path = tmp_path_factory.mktemp("quality_serve")
    from fast_tffm_tpu.train.loop import Trainer

    cfg = _train_cfg(train_file, tmp_path, "serve",
                     serve_poll_secs=0, quality_window=128)
    Trainer(cfg).train()
    return tmp_path, cfg, train_file


class TestServeSkew:
    def test_skew_identity_then_breach_over_sockets(self, served):
        """The acceptance path: train -> manifest sketches -> serve ->
        identity traffic reads ~0 -> shifted traffic breaches
        tffm_serve_skew_* on /metrics."""
        from fast_tffm_tpu.serve.server import serve

        _, cfg, data = served
        handle = serve(cfg, port=0)
        try:
            url = f"http://127.0.0.1:{handle.port}"
            body = open(data, "rb").read()
            _post(url + "/score", body)
            block = _serve_block_when(
                url, lambda b: b.get("skew_examples", 0) >= 128)
            assert block["skew_ref_step"] > 0
            assert block["skew_examples"] >= 128
            assert block["skew_psi_max"] <= 0.1, block
            # Shifted traffic: foreign id range, 50x values, 4 feats.
            rng = np.random.default_rng(9)
            shifted = "\n".join(
                "0 " + " ".join(
                    f"{int(j)}:{v * 50:.3f}" for j, v in
                    zip(rng.integers(45, 50, 4), rng.random(4) + 4)
                )
                for _ in range(320)
            ).encode()
            _post(url + "/score", shifted)
            block = _serve_block_when(
                url, lambda b: b.get("skew_psi_max", 0) > 0.25)
            assert block["skew_psi_max"] > 0.25, block
            assert block["skew_psi_values"] > 0.25, block
            metrics = urllib.request.urlopen(
                url + "/metrics", timeout=10
            ).read().decode()
            assert "tffm_serve_skew_psi_max" in metrics
            assert "tffm_serve_skew_psi_values" in metrics
            # Timing percentile series carry their sample-count
            # companion (the tffm_*_count satellite).
            assert "tffm_timer_serve_latency_window_count" in metrics
            assert "latency_count" in block
            assert "latency_window_n" in block
        finally:
            handle.close()

    def test_quality_off_serving_byte_identical(self, served, tmp_path):
        """Responses must be byte-identical with the skew monitor on
        or off — observation only, pinned."""
        import dataclasses

        from fast_tffm_tpu.serve.server import serve

        _, cfg, data = served
        body = open(data, "rb").read()
        out = {}
        for tag, on in (("on", True), ("off", False)):
            c = dataclasses.replace(cfg, quality=on)
            handle = serve(c, port=0)
            try:
                url = f"http://127.0.0.1:{handle.port}"
                out[tag] = _post(url + "/score", body)
                block = _get_json(url + "/status")["serve"]
                if on:
                    assert "skew_ref_step" in block
                else:
                    assert not any(
                        k.startswith("skew_") for k in block
                    ), block
            finally:
                handle.close()
        assert out["on"] == out["off"]

    def test_no_reference_reports_absence(self, served, tmp_path):
        """A pre-quality manifest (no sketches) yields skew_ref_step
        -1 and NO psi keys — absence, never a lying zero."""
        monitor = ServeSkewMonitor(
            window_examples=64, read_reference=lambda: None
        )
        monitor.observe_batch(
            np.ones((80, 4), np.int32), np.ones((80, 4), np.float32)
        )
        block = monitor.block()
        assert block["skew_ref_step"] == -1
        assert not any(k.startswith("skew_psi") for k in block), block

    def test_reference_follows_reload(self, rng):
        """reload_reference() re-reads the manifest payload — the
        hot-swap hook's contract."""
        ref_a = SketchSet()
        ref_a.update_batch(
            rng.integers(0, 100, (64, 4)), rng.random((64, 4))
        )
        payload = [{"step": 7, "sketches": ref_a.to_dict()}]
        monitor = ServeSkewMonitor(
            window_examples=1024, read_reference=lambda: payload[0]
        )
        assert monitor.reload_reference()
        assert monitor.block()["skew_ref_step"] == 7
        payload[0] = {"step": 11, "sketches": ref_a.to_dict()}
        assert monitor.reload_reference()
        assert monitor.block()["skew_ref_step"] == 11

    def test_reference_clears_when_payload_vanishes(self, rng):
        """A readable manifest WITHOUT a quality payload (--no_quality
        retrain, in-place conversion) must CLEAR the reference — a
        stale one would judge the NEW model's traffic against the old
        checkpoint's sketches (phantom skew)."""
        ref = SketchSet()
        ref.update_batch(
            rng.integers(0, 100, (64, 4)), rng.random((64, 4))
        )
        payload = [{"step": 7, "sketches": ref.to_dict()}]
        monitor = ServeSkewMonitor(
            window_examples=1024, read_reference=lambda: payload[0]
        )
        assert monitor.reload_reference()
        monitor.observe_batch(
            np.ones((64, 4), np.int32), np.ones((64, 4), np.float32)
        )
        assert "skew_psi_max" in monitor.block()
        payload[0] = None  # the next manifest carries no sketches
        assert not monitor.reload_reference()
        block = monitor.block()
        assert block["skew_ref_step"] == -1
        assert not any(k.startswith("skew_psi") for k in block), block

    def test_rollback_restores_previous_reference(self, rng):
        """The canary /rollback path: served params revert to the
        pre-canary checkpoint, so the skew reference reverts from the
        stash (its manifest is gone from disk)."""
        ref = SketchSet()
        ref.update_batch(
            rng.integers(0, 100, (64, 4)), rng.random((64, 4))
        )
        payload = [{"step": 7, "sketches": ref.to_dict()}]
        monitor = ServeSkewMonitor(
            window_examples=1024, read_reference=lambda: payload[0]
        )
        assert monitor.reload_reference()  # baseline checkpoint
        payload[0] = {"step": 11, "sketches": ref.to_dict()}
        assert monitor.reload_reference()  # the canary reload
        assert monitor.block()["skew_ref_step"] == 11
        monitor.restore_previous_reference()  # rejected -> rollback
        assert monitor.block()["skew_ref_step"] == 7


# ----------------------------------------------------------------------
# router fleet aggregation + rendering + report
# ----------------------------------------------------------------------


class TestFleetAndTooling:
    def test_router_fleet_scrape_max_merges_skew(self):
        """One router scrape answers 'is ANY replica skewed': skew_psi
        keys MAX-merge under the same names, skew_examples sums."""
        from fast_tffm_tpu.serve.router import ServeRouter

        per = [{"index": 0}, {"index": 1}]
        now = 1000.0
        scrapes = {
            0: (now - 1, {"requests": 10, "skew_psi_max": 0.02,
                          "skew_psi_values": 0.01,
                          "skew_examples": 100}),
            1: (now - 2, {"requests": 20, "skew_psi_max": 0.9,
                          "skew_psi_values": 0.8,
                          "skew_examples": 50}),
        }
        out = ServeRouter._fleet_aggregates(None, per, scrapes, now)
        assert out["skew_psi_max"] == 0.9
        assert out["skew_psi_values"] == 0.8
        assert out["skew_examples"] == 150

    def test_render_prometheus_quality_block_and_window_count(self):
        from fast_tffm_tpu.obs.status import render_prometheus

        tel = obs.Telemetry()
        t = tel.timer("serve.latency")
        for _ in range(5):
            t.observe(0.01)
        rec = {
            "record": "status",
            "quality": {"logloss": 0.31, "psi_max": 0.02},
            "stages": tel.snapshot(),
        }
        text = render_prometheus(rec)
        assert "tffm_quality_logloss 0.31" in text
        assert "tffm_quality_psi_max 0.02" in text
        assert "tffm_timer_serve_latency_window_count 5" in text

    def test_report_directions(self):
        from tools.report import _direction

        assert _direction("quality.logloss") == "low"
        assert _direction("quality.auc") == "high"
        assert _direction("quality.calib_ratio") == "both"
        assert _direction("quality.psi_values") == "low"
        assert _direction("serve.skew_psi_max") == "low"
        assert _direction("quality_overhead") == "low"
        assert _direction("quality_psi_identity") == "low"

    def test_report_quality_section_never_keyerrors(self, capsys):
        """Pre-quality streams (no quality block) summarize with the
        n/a line, never a KeyError."""
        from tools.report import _print_breakdown

        rec = {"record": "final", "step": 10, "elapsed": 1.0,
               "stages": {}}
        _print_breakdown(rec)
        out = capsys.readouterr().out
        assert "quality & drift: n/a" in out

    def test_report_flattens_quality_keys(self, tmp_path):
        from tools.report import _comparable_metrics

        mf = tmp_path / "m.jsonl"
        recs = [
            {"record": "run_header", "time": 0},
            {"record": "final", "step": 4, "elapsed": 1.0,
             "quality": {"logloss": 0.5, "auc": 0.7, "psi_max": 0.1},
             "serve": {"skew_psi_max": 0.2}},
        ]
        mf.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        out = _comparable_metrics(str(mf))
        assert out["quality.logloss"] == 0.5
        assert out["quality.auc"] == 0.7
        assert out["serve.skew_psi_max"] == 0.2
