"""Kernel autotuner (ISSUE 17 tentpole): measured promotion of the
interaction hot path + fused stack+H2D shipping + persistent caches.

The pinned guarantees:

  * zero-overhead CPU contract — ``interaction_impl=auto`` off-TPU
    resolves to reference through the single-candidate fast path
    WITHOUT running one measurement;
  * parity gate — a candidate whose outputs drift from reference
    beyond PARITY_TOL is excluded from selection no matter how fast
    it measured (a wrong kernel can never win);
  * cache discipline — a persistent-cache hit skips measurement
    entirely; ANY drift in the key (batch, table dtype, jax version,
    ...) re-measures; pins and the legacy surface never consult it;
  * training equivalence — a run resolved via ``auto`` produces
    BIT-IDENTICAL tables to one pinned to the impl auto chose;
  * fused H2D — FusedShipper's single-buffer ship + on-device carve
    is bitwise-equal to the classic stack_batches + shard_super_batch
    path (core leaves AND sort_meta), and its gate never opens on a
    multi-device mesh;
  * serve warmup — the concurrent ladder warmup compiles every rung
    (zero steady-state compiles after), and with a persistent compile
    cache a fresh scorer spawn re-lowers nothing.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax

from fast_tffm_tpu import obs, platform
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch, SortMeta
from fast_tffm_tpu.data.pipeline import stack_batches
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import autotune
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.serve.scorer import FixedShapeScorer
from fast_tffm_tpu.train.loop import Trainer

V = 64
F = 4


@pytest.fixture(autouse=True)
def _isolated_autotune(monkeypatch):
    """Every test gets an empty in-process cache and a memory-only
    default cache path (no autotune_cache.json left on disk unless the
    test passes cache_path explicitly)."""
    monkeypatch.setattr(autotune, "_MEM_CACHE", {})
    monkeypatch.setenv("FAST_TFFM_AUTOTUNE_CACHE", "")


def _cfg(**kw):
    defaults = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _train_cfg(tmp_path, model, **kw):
    return _cfg(
        train_files=[str(tmp_path / "train.libsvm")],
        model_file=str(tmp_path / model),
        epoch_num=1, log_steps=0, thread_num=1, seed=3, **kw,
    )


def _write_data(path, rng, lines=160, vocab=V):
    with open(path, "w") as f:
        for i in range(lines):
            f.write(
                f"{i % 2} {rng.integers(0, vocab)}:1 "
                f"{rng.integers(0, vocab)}:0.5\n"
            )


# ----------------------------------------------------------------------
# resolve: pins, CPU fast path, parity gate
# ----------------------------------------------------------------------


class TestResolve:
    def test_cpu_auto_is_reference_with_zero_measurement(self):
        """The near-zero-overhead contract: off-TPU `auto` must win by
        construction, not by benchmark."""
        n0 = autotune.measurement_count()
        d = autotune.resolve(_cfg(interaction_impl="auto"))
        assert d.impl == "reference"
        assert d.interaction == "jnp"
        assert d.source == "single_candidate"
        assert autotune.measurement_count() == n0

    def test_pin_bypasses_measurement_and_cache(self, tmp_path):
        cache = str(tmp_path / "autotune_cache.json")
        n0 = autotune.measurement_count()
        d = autotune.resolve(
            _cfg(interaction_impl="packed"), cache_path=cache
        )
        assert (d.impl, d.interaction, d.source) == (
            "packed", "flat", "pinned"
        )
        assert autotune.measurement_count() == n0
        assert not os.path.exists(cache)

    def test_legacy_surface_maps_without_measurement(self):
        n0 = autotune.measurement_count()
        d = autotune.resolve(_cfg(interaction="flat"))
        assert (d.impl, d.interaction, d.source) == (
            "packed", "flat", "legacy"
        )
        assert autotune.measurement_count() == n0

    def test_ffm_collapses_to_reference(self):
        """field_num > 0: impl routing doesn't apply to the FFM op, so
        auto must not measure anything."""
        n0 = autotune.measurement_count()
        d = autotune.resolve(
            _cfg(interaction_impl="auto", field_num=3)
        )
        assert d.impl == "reference"
        assert d.source == "single_candidate"
        assert autotune.measurement_count() == n0

    def test_parity_gate_rejects_wrong_candidate(self):
        """A deliberately-wrong 'packed' (scores scaled 2x) must lose
        to reference even though it is the 'fastest' — wrong answers
        never get timed, let alone win."""
        cfg = _cfg(interaction_impl="auto")
        rng = np.random.default_rng(0)
        rows = rng.uniform(-0.1, 0.1, (32, F, 4)).astype(np.float32)
        vals = rng.uniform(0.1, 1.0, (32, F)).astype(np.float32)

        def make(user_impl):
            from fast_tffm_tpu.ops import interaction

            scale = 2.0 if user_impl == "packed" else 1.0

            def f(r, v):
                return interaction.fm_interaction(r, v, "jnp") * scale

            return jax.jit(f)

        d = autotune.resolve(
            cfg, candidates=("reference", "packed"),
            candidate_fns=(make, (rows, vals)),
        )
        assert d.source == "measured"
        assert d.impl == "reference"
        assert d.parity_err["packed"] > autotune.PARITY_TOL
        assert "packed" not in d.times_ms  # gated out before timing

    def test_real_packed_candidate_passes_parity(self):
        """The actual flat-layout impl IS element-wise equivalent: a
        forced CPU measurement must keep it as a survivor (times
        recorded) with tiny parity error, whoever wins."""
        d = autotune.resolve(
            _cfg(interaction_impl="auto"),
            candidates=("reference", "packed"),
        )
        assert d.source == "measured"
        assert "packed" in d.times_ms
        assert d.parity_err["packed"] <= autotune.PARITY_TOL

    def test_serve_context_int8_dequant_candidates(self):
        """Serve-context measurement routes the int8 fused-gather
        forward; packed must be parity-equivalent there too."""
        d = autotune.resolve(
            _cfg(interaction_impl="auto", serve_table_dtype="int8"),
            context="serve", batch=32,
            candidates=("reference", "packed"), table_dtype="int8",
        )
        assert d.source == "measured"
        assert d.impl in ("reference", "packed")
        assert d.parity_err["packed"] <= autotune.PARITY_TOL


# ----------------------------------------------------------------------
# persistent cache: hits skip measurement, drift re-measures
# ----------------------------------------------------------------------


class TestCache:
    CANDS = ("reference", "packed")

    def test_hit_skips_measurement(self, tmp_path):
        cfg = _cfg(interaction_impl="auto")
        cache = str(tmp_path / "autotune_cache.json")
        d1 = autotune.resolve(
            cfg, candidates=self.CANDS, cache_path=cache
        )
        assert d1.source == "measured"
        n1 = autotune.measurement_count()
        d2 = autotune.resolve(
            cfg, candidates=self.CANDS, cache_path=cache
        )
        assert d2.source == "cache"
        assert d2.impl == d1.impl
        assert autotune.measurement_count() == n1

    def test_hit_from_disk_across_processes(self, tmp_path, monkeypatch):
        """A fresh process (fresh _MEM_CACHE) reads the file — the
        replica-fleet / restart contract."""
        cfg = _cfg(interaction_impl="auto")
        cache = str(tmp_path / "autotune_cache.json")
        autotune.resolve(cfg, candidates=self.CANDS, cache_path=cache)
        assert os.path.exists(cache)
        monkeypatch.setattr(autotune, "_MEM_CACHE", {})  # "new process"
        n1 = autotune.measurement_count()
        d = autotune.resolve(cfg, candidates=self.CANDS, cache_path=cache)
        assert d.source == "cache"
        assert autotune.measurement_count() == n1

    @pytest.mark.parametrize("drift", ["batch", "table_dtype",
                                       "jax_version", "candidates"])
    def test_key_drift_re_measures(self, tmp_path, drift):
        """ANY axis of the key changing invalidates the entry — a
        stale winner never leaks across shapes/dtypes/upgrades."""
        cfg = _cfg(interaction_impl="auto")
        cache = str(tmp_path / "autotune_cache.json")
        kw = dict(candidates=self.CANDS, cache_path=cache, batch=32)
        autotune.resolve(cfg, **kw)
        n1 = autotune.measurement_count()
        if drift == "batch":
            kw["batch"] = 64
        elif drift == "table_dtype":
            kw["table_dtype"] = "bf16"
        elif drift == "jax_version":
            kw["jax_version"] = "999.0.0"
        else:
            kw["candidates"] = ("reference", "pallas", "packed")
        d = autotune.resolve(cfg, **kw)
        assert d.source == "measured"
        assert autotune.measurement_count() > n1

    def test_corrupt_cache_file_re_measures(self, tmp_path):
        cfg = _cfg(interaction_impl="auto")
        cache = str(tmp_path / "autotune_cache.json")
        with open(cache, "w") as f:
            f.write("{not json")
        d = autotune.resolve(cfg, candidates=self.CANDS, cache_path=cache)
        assert d.source == "measured"
        # and the re-measure repaired the file in place
        entries = autotune.load_cache(cache)
        assert entries and all(
            e["impl"] in autotune.INTERNAL for e in entries.values()
        )

    def test_record_schema(self, tmp_path):
        """The `record: autotune` observability contract
        OBSERVABILITY.md pins: impl/source/time always present."""
        path = tmp_path / "m.jsonl"
        writer = obs.JsonlWriter(str(path))
        autotune.resolve(
            _cfg(interaction_impl="auto"), writer=writer,
        )
        writer.close()
        recs = [json.loads(l) for l in open(path)]
        assert len(recs) == 1
        r = recs[0]
        assert r["record"] == "autotune"
        for key in ("impl", "source", "time", "context", "key",
                    "candidates", "times_ms", "parity_err"):
            assert key in r
        assert r["impl"] == "reference"


# ----------------------------------------------------------------------
# training through the resolved impl
# ----------------------------------------------------------------------


def test_train_auto_bitwise_identical_to_pinned_reference(tmp_path, rng):
    """The acceptance property: a training run resolved via `auto`
    produces BIT-IDENTICAL params/metrics to one pinned to the impl
    auto chose (on CPU: reference) — selection may change speed,
    never math."""
    _write_data(tmp_path / "train.libsvm", rng)
    t_auto = Trainer(
        _train_cfg(tmp_path, "m_auto", interaction_impl="auto")
    )
    assert t_auto.kernel_impl == "reference"  # CPU contract
    assert t_auto._autotune is not None
    assert t_auto._autotune.source == "single_candidate"
    r_auto = t_auto.train()
    t_ref = Trainer(
        _train_cfg(tmp_path, "m_ref", interaction_impl="reference")
    )
    r_ref = t_ref.train()
    assert r_auto["train"]["steps"] == r_ref["train"]["steps"] > 0
    for a, b in zip(jax.tree.leaves(t_auto.state.params),
                    jax.tree.leaves(t_ref.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("knobs", [
    dict(table_tiering="on", hot_rows=64),
    dict(table_tiering="on", hot_rows=64, cold_dtype="bf16"),
    dict(compute_dtype="bfloat16"),
], ids=["tiered", "tiered-bf16-cold", "bf16-compute"])
def test_train_auto_identical_at_parity_matrix_knobs(tmp_path, rng,
                                                     knobs):
    """The existing tiered/quant parity matrices hold through the
    autotuner: at each knob point, `auto` training == pinned-reference
    training element-wise (the resolution happens before step build,
    so every downstream path sees the same impl)."""
    _write_data(tmp_path / "train.libsvm", rng)
    t_auto = Trainer(_train_cfg(
        tmp_path, "m_auto", interaction_impl="auto", **knobs
    ))
    r_auto = t_auto.train()
    t_ref = Trainer(_train_cfg(
        tmp_path, "m_ref", interaction_impl="reference", **knobs
    ))
    r_ref = t_ref.train()
    assert r_auto["train"]["steps"] == r_ref["train"]["steps"] > 0
    for a, b in zip(jax.tree.leaves(t_auto.state.params),
                    jax.tree.leaves(t_ref.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_header_carries_kernel_impl(tmp_path, rng):
    _write_data(tmp_path / "train.libsvm", rng)
    cfg = _train_cfg(
        tmp_path, "m_hdr", interaction_impl="auto",
        metrics_file=str(tmp_path / "m.jsonl"),
    )
    Trainer(cfg).train()
    recs = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    header = [r for r in recs if r.get("record") == "run_header"][-1]
    assert header["kernel_impl"] == "reference"
    assert header["interaction_impl"] == "auto"
    assert [r for r in recs if r.get("record") == "autotune"]


# ----------------------------------------------------------------------
# fused stack+H2D shipping
# ----------------------------------------------------------------------


def _batch(rng, b=32, f=F, vocab=V, with_meta=False):
    meta = None
    if with_meta:
        n_pad = b * f
        meta = SortMeta(
            perm=rng.integers(0, n_pad, n_pad).astype(np.int32),
            upos=rng.integers(0, n_pad, n_pad).astype(np.int32),
            lrow_last=rng.uniform(0, 8, n_pad).astype(np.float32),
            starts=rng.integers(0, n_pad, n_pad // 8).astype(np.int32),
            firsts=rng.integers(0, 2, n_pad // 8 + 1).astype(np.int32),
            ends=rng.integers(0, n_pad, n_pad // 8).astype(np.int32),
            tile_start=rng.integers(0, n_pad, 9).astype(np.int32),
        )
    return Batch(
        labels=rng.integers(0, 2, b).astype(np.float32),
        ids=rng.integers(0, vocab, (b, f)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, (b, f)).astype(np.float32),
        fields=np.zeros((b, f), np.int32),
        weights=np.ones((b,), np.float32),
        sort_meta=meta,
    )


class TestFusedShipper:
    @pytest.mark.parametrize("k", [1, 3])
    @pytest.mark.parametrize("with_meta", [False, True])
    def test_bitwise_matches_classic_path(self, rng, k, with_meta):
        """One fused buffer ship + on-device carve == stack_batches +
        shard_super_batch, bitwise, every leaf (the unpack is a pure
        bitcast — no arithmetic may touch the payload)."""
        cfg = _cfg()
        mesh = mesh_lib.make_mesh(cfg, jax.devices()[:1])
        ship = mesh_lib.FusedShipper(mesh, depth=2)
        group = [_batch(rng, with_meta=with_meta) for _ in range(k)]
        fused = ship(group)
        classic = mesh_lib.shard_super_batch(stack_batches(group), mesh)
        assert ship.ships == 1
        for name in ("labels", "ids", "vals", "fields", "weights"):
            a, b = getattr(fused, name), getattr(classic, name)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if with_meta:
            assert fused.sort_meta is not None
            for a, b in zip(fused.sort_meta, classic.sort_meta):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                )
        else:
            assert fused.sort_meta is None

    def test_meta_all_or_nothing(self, rng):
        """Mixed group (one member meta-less) drops meta, mirroring
        stack_batches."""
        cfg = _cfg()
        mesh = mesh_lib.make_mesh(cfg, jax.devices()[:1])
        ship = mesh_lib.FusedShipper(mesh)
        group = [_batch(rng, with_meta=True), _batch(rng)]
        assert ship(group).sort_meta is None

    def test_empty_group_declines(self):
        cfg = _cfg()
        mesh = mesh_lib.make_mesh(cfg, jax.devices()[:1])
        assert mesh_lib.FusedShipper(mesh)([]) is None

    def test_unpack_cache_reused_across_ships(self, rng):
        cfg = _cfg()
        mesh = mesh_lib.make_mesh(cfg, jax.devices()[:1])
        ship = mesh_lib.FusedShipper(mesh)
        for _ in range(3):
            ship([_batch(rng), _batch(rng)])
        assert ship.ships == 3
        assert len(ship._unpack_cache) == 1  # one spec -> one jit

    def test_gate_closed_on_multi_device_mesh(self, monkeypatch):
        """The structural gate is unconditional: a multi-device mesh
        never fuses, even force-enabled (the flat replicated buffer
        can't reproduce per-leaf data sharding)."""
        cfg = _cfg()
        multi = mesh_lib.make_mesh(cfg)  # conftest: 8 virtual devices
        assert multi.size > 1
        monkeypatch.setenv("FAST_TFFM_FUSED_H2D", "1")
        assert mesh_lib.fused_h2d_enabled(multi) is False
        single = mesh_lib.make_mesh(cfg, jax.devices()[:1])
        assert mesh_lib.fused_h2d_enabled(single) is True
        monkeypatch.setenv("FAST_TFFM_FUSED_H2D", "0")
        assert mesh_lib.fused_h2d_enabled(single) is False
        monkeypatch.delenv("FAST_TFFM_FUSED_H2D")
        # default off-TPU: classic path (device_put is zero-copy there)
        assert mesh_lib.fused_h2d_enabled(single) is False

    def test_train_with_fused_shipping_matches_classic(self, tmp_path,
                                                       rng, monkeypatch):
        """End-to-end: a K=4 training run through the fused transfer
        stage reproduces the classic-path run bit-for-bit."""
        _write_data(tmp_path / "train.libsvm", rng)
        monkeypatch.setenv("FAST_TFFM_FUSED_H2D", "1")
        cfg_f = _train_cfg(tmp_path, "m_fused", steps_per_dispatch=4)
        t_fused = Trainer(
            cfg_f, mesh=mesh_lib.make_mesh(cfg_f, jax.devices()[:1])
        )
        r_fused = t_fused.train()
        monkeypatch.setenv("FAST_TFFM_FUSED_H2D", "0")
        cfg_c = _train_cfg(tmp_path, "m_classic", steps_per_dispatch=4)
        t_classic = Trainer(
            cfg_c, mesh=mesh_lib.make_mesh(cfg_c, jax.devices()[:1])
        )
        r_classic = t_classic.train()
        assert r_fused["train"]["steps"] == r_classic["train"]["steps"]
        for a, b in zip(jax.tree.leaves(t_fused.state.params),
                        jax.tree.leaves(t_classic.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# serve: concurrent warmup + persistent compile cache
# ----------------------------------------------------------------------


def _params(cfg, seed=0):
    return jax.jit(lambda k: fm.init_params(k, cfg=cfg))(
        jax.random.PRNGKey(seed)
    )


def _cfg_mem(**kw):
    defaults = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
        serve_batch_sizes="8,16,32", max_batch_wait_ms=1.0,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


class TestServeWarmup:
    def test_concurrent_warmup_compiles_every_rung(self, rng):
        """The serial-ladder fix: warmup still compiles the WHOLE
        ladder (scores after it are steady-state, zero compiles) and
        accounts both the wall time and the summed compile seconds."""
        tel = obs.Telemetry()
        cfg = _cfg_mem()
        sc = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
        n = sc.warmup()
        assert n == len(sc.ladder) == 3
        assert sc.warmup_wall_s > 0.0
        assert sc.warmup_compile_s > 0.0
        for size in (1, 7, 16, 33, 100):
            ids = rng.integers(0, V, (size, F)).astype(np.int32)
            vals = rng.uniform(0.1, 1.0, (size, F)).astype(np.float32)
            sc.score(ids, vals)
        assert sc.steady_compiles == 0
        snap = tel.snapshot()
        assert snap["timers"]["serve.compile"]["count"] == n

    def test_warmup_scores_match_lazy_compiled_scorer(self, rng):
        """Concurrent compilation may reorder nothing: scores from a
        warmed ladder equal a never-warmed scorer's lazily-compiled
        ones bitwise."""
        cfg = _cfg_mem()
        params = _params(cfg)
        warm = FixedShapeScorer(cfg, params)
        warm.warmup()
        lazy = FixedShapeScorer(cfg, params)
        ids = rng.integers(0, V, (20, F)).astype(np.int32)
        vals = rng.uniform(0.1, 1.0, (20, F)).astype(np.float32)
        np.testing.assert_array_equal(
            warm.score(ids, vals), lazy.score(ids, vals)
        )

    def test_warm_spawn_zero_fresh_lowers(self, rng, tmp_path):
        """With compile_cache_dir set, a second scorer spawn (same
        shapes/params structure) must warm up purely from the
        persistent cache: hits > 0, NO new misses."""
        if not platform.enable_compile_cache(str(tmp_path / "cc")):
            pytest.skip("persistent compile cache unavailable")
        try:
            cfg = _cfg_mem(serve_batch_sizes="8,16")
            params = _params(cfg)
            a = FixedShapeScorer(cfg, params)
            a.warmup()
            st0 = platform.compile_cache_stats()
            assert st0["misses"] > 0  # cold spawn populated the cache
            b = FixedShapeScorer(cfg, params)
            b.warmup()
            st1 = platform.compile_cache_stats()
            assert st1["misses"] == st0["misses"]  # zero fresh lowers
            assert st1["hits"] > st0["hits"]
            ids = rng.integers(0, V, (10, F)).astype(np.int32)
            vals = rng.uniform(0.1, 1.0, (10, F)).astype(np.float32)
            np.testing.assert_array_equal(
                a.score(ids, vals), b.score(ids, vals)
            )
        finally:
            platform.disable_compile_cache()
