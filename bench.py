#!/usr/bin/env python
"""Benchmark: end-to-end FM training throughput on a Criteo-like workload.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "examples/sec", "vs_baseline": N, ...}

Baseline: the driver target of 2M examples/sec aggregate on a v5e-16
(BASELINE.md) = 125k examples/sec/chip; ``vs_baseline`` is the per-chip
ratio vs that target, scaled by the number of chips actually used.

Headline metric (the judged one): END-TO-END examples/sec — libsvm text
files generated on disk, parsed by the native C++ parser through
BatchPipeline (host threads overlapping device steps), trained with the
full sparse train step.  Feature ids are Zipf(1.1)-skewed then
hash-spread, matching CTR data's duplicate structure (which stresses the
dedup/carry chain in the sparse apply path) rather than uniform ids.
The e2e loop is the train() hot path: parse threads + the stacking/H2D
transfer thread (DevicePrefetcher) + the K-step fused scan dispatch
(steps_per_dispatch=8).  Also reported: device-step-only throughput at
K=8 and K=1 (their per-step difference is ``dispatch_overhead_ms``, the
amortized Python/runtime dispatch cost), e2e at K=1, the parse-only
rate, and ``h2d_overlap_frac`` — the fraction of the synchronous
stack+transfer cost the background transfer thread hides.

One process on whatever backend the environment gives (JAX_PLATFORMS=cpu
for a CPU rehearsal; a TPU where one is attached): no backend probe, no
watchdog child, no fallback.  A section that raises ends the bench with a
nonzero exit code — a kernel the compiler refuses must never be measured
as something else.  The persistent compile cache lives where
JAX_COMPILATION_CACHE_DIR says, else at the fixed <repo>/.jax_cache.

Timing note: completion is forced by reading back scalars that depend on
both the metrics chain and the updated table.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from fast_tffm_tpu import obs as obs_mod  # stdlib-only; no jax import
from fast_tffm_tpu.data.synth import gen_libsvm_files, zipf_ids

PER_CHIP_TARGET = 2_000_000 / 16  # BASELINE.md: 2M ex/s on v5e-16


def _drain(state) -> float:
    """Force the full dependency chain: metrics + updated params."""
    s = float(state.metrics.loss_sum)
    s += float(state.params.table[0, 0])
    s += float(state.step)
    return s


def _make_batch(rng, cfg, vocab: int):
    from fast_tffm_tpu.data.libsvm import Batch

    return Batch(
        labels=rng.integers(0, 2, size=(cfg.batch_size,)).astype(np.float32),
        ids=zipf_ids(rng, (cfg.batch_size, cfg.max_features), vocab),
        vals=rng.uniform(
            0.1, 1.0, size=(cfg.batch_size, cfg.max_features)
        ).astype(np.float32),
        fields=np.zeros((cfg.batch_size, cfg.max_features), np.int32),
        weights=np.ones((cfg.batch_size,), np.float32),
    )


def _bench_quality_identity() -> float:
    """Self-skew floor of the quality plane: sketch two independent
    draws of the SAME synthetic example distribution (ids, values,
    lengths, scores) and report their psi_max.  The debiased PSI must
    read ~0 — `report.py --compare` gates it low, so any future sketch
    or PSI change that starts seeing drift in identical data flags."""
    from fast_tffm_tpu import obs

    rng = np.random.default_rng(7)
    ref, live = obs.SketchSet(), obs.SketchSet()
    for sk in (ref, live):
        for _ in range(64):
            ids = rng.integers(0, 1 << 20, size=(256, 16))
            vals = np.where(
                rng.random((256, 16)) < 0.8,
                rng.lognormal(size=(256, 16)), 0.0
            )
            sk.update_batch(ids, vals)
            sk.update_scores(rng.random(256))
    return round(float(live.psi_vs(ref).get("psi_max", 0.0)), 6)


def _bench_step_only(trainer, cfg, steps: int) -> float:
    rng = np.random.default_rng(0)
    batches = [trainer._put(_make_batch(rng, cfg, cfg.vocabulary_size))
               for _ in range(4)]
    for i in range(3):
        trainer.state = trainer._train_step(trainer.state, batches[i % 4])
    _drain(trainer.state)
    t0 = time.perf_counter()
    for i in range(steps):
        trainer.state = trainer._train_step(trainer.state, batches[i % 4])
    _drain(trainer.state)
    return steps * cfg.batch_size / (time.perf_counter() - t0)


def _bench_step_scan(trainer, cfg, steps: int, k: int) -> float:
    """Device-step throughput with the K-step fused dispatch: one
    lax.scan dispatch trains k steps, so Python/runtime dispatch overhead
    is paid once per k (the steps_per_dispatch hot path)."""
    from fast_tffm_tpu.data.pipeline import stack_batches

    rng = np.random.default_rng(0)
    supers = [
        trainer._put_super(stack_batches(
            [_make_batch(rng, cfg, cfg.vocabulary_size) for _ in range(k)]
        ))
        for _ in range(2)
    ]
    n_disp = max(2, steps // k)
    trainer.state = trainer._scan_train_step(trainer.state, supers[0])
    _drain(trainer.state)
    t0 = time.perf_counter()
    for i in range(n_disp):
        trainer.state = trainer._scan_train_step(
            trainer.state, supers[i % 2]
        )
    _drain(trainer.state)
    return n_disp * k * cfg.batch_size / (time.perf_counter() - t0)


def _bench_put_only(trainer, cfg, k: int, reps: int = 6) -> float:
    """Synchronous per-example transfer cost: stack K batches + shard +
    device_put, blocked to completion.  The overlap fraction compares
    this against the e2e-vs-step gap."""
    import jax

    from fast_tffm_tpu.data.pipeline import stack_batches

    rng = np.random.default_rng(2)
    groups = [
        [_make_batch(rng, cfg, cfg.vocabulary_size) for _ in range(k)]
        for _ in range(2)
    ]
    t0 = time.perf_counter()
    for i in range(reps):
        sb = trainer._put_super(stack_batches(groups[i % 2]))
        jax.block_until_ready(
            (sb.labels, sb.ids, sb.vals, sb.fields, sb.weights)
        )
    dt = time.perf_counter() - t0
    return dt / (reps * k * cfg.batch_size)


def _bench_parse_only(files, cfg) -> float:
    """Raw native-parser rate on the generated files (single pass, the
    internally-threaded parse_raw fast path)."""
    from fast_tffm_tpu.data import native as native_lib
    from fast_tffm_tpu.data.pipeline import _iter_raw_groups

    try:
        parser = native_lib.NativeParser(
            cfg.vocabulary_size, cfg.max_features, cfg.hash_feature_id,
            cfg.field_num, cfg.thread_num,
        )
    except Exception:  # pragma: no cover - env-dependent
        return 0.0
    n = 0
    t0 = time.perf_counter()
    for buf, starts, ends in _iter_raw_groups(files, cfg.batch_size):
        parser.parse_raw(buf, starts, ends, cfg.batch_size)
        n += len(starts)
    dt = time.perf_counter() - t0
    return n / dt if dt > 0 else 0.0


def _bench_e2e(trainer, cfg, files, warmup: int, epochs: int,
               k: int = 1, telemetry_enabled: bool = True,
               tracer=None, status: bool = False,
               resource: bool = False, quality: bool = False,
               fleet: bool = False) -> tuple:
    """Examples/sec through BatchPipeline + DevicePrefetcher — the
    train() hot path: parse threads, the stacking/H2D transfer thread,
    and the K-step fused dispatch all overlapped.  ``warmup`` counts
    BATCHES (rounded up to whole dispatches).

    Multi-epoch runs use the pipeline's parsed-batch cache (epoch 0
    parses the text, later epochs replay in permuted order) — on a
    host whose cores are saturated by the device step itself (1-core
    CPU boxes; a small TPU host) re-parsing identical text
    every epoch is pure overhead no overlap can hide.

    Returns (overall_rate, cache_result, epoch0_rate, cached_rate,
    tele_report): the pipeline's in-band EpochEnd markers split the run
    into per-epoch windows (draining the device at each marker so the
    window measures completed training, not enqueue speed) — epoch 0
    pays the parse, epochs 1+ replay from the cache, and their gap is
    exactly what the cache buys.

    ``tele_report`` is the run's obs.Telemetry self-report: the final
    stage snapshot plus ``ingest_wait_frac`` over the TIMED region —
    the same per-stage attribution a training run's heartbeat emits,
    measured here instead of re-derived with bench-local stopwatches.
    With ``telemetry_enabled=False`` the run uses no-op instruments
    (the on/off rate ratio is the layer's measured overhead).

    ``tracer`` (an enabled obs.Tracer) additionally records the causal
    span layer through the pipeline + prefetcher + this loop's
    wait/dispatch — the trace-overhead probe runs the identical e2e
    with it attached and compares rates.

    ``status=True`` attaches a live obs.StatusServer serving this
    run's telemetry snapshot AND a scraper thread hitting ``/metrics``
    every 200 ms — the endpoint-overhead probe (endpoint on + scraped
    vs off) under a realistic Prometheus-ish cadence.

    ``resource=True`` attaches a resource-plane sampler thread: RSS /
    peak-RSS (``/proc`` reads) + the component byte gauges + the
    compile-sentinel snapshot, every 200 ms — the marginal cost of the
    resource plane's live sampling at an aggressive heartbeat-like
    cadence (the AOT dispatch path itself is already in the baseline:
    the trainer's cfg has resource_metrics on by default).

    ``quality=True`` attaches the model-quality plane's full run-time
    work: the parse-path drift sketches (StreamSketch on the pipeline)
    and the windowed online-eval monitor consuming each dispatch's
    scores one dispatch delayed, exactly like train() — the
    quality-overhead probe.  The scan's score EMISSION is in the
    baseline too (the bench trainer's cfg has quality on by default);
    it is one [K, B] store whose bitwise-no-op-ness the parity tests
    pin, so the on/off ratio here measures the part that does real
    work: sketch updates + the window statistics + the extra D2H.
    """
    import threading

    from fast_tffm_tpu import obs
    from fast_tffm_tpu.data.pipeline import (
        BatchPipeline, DevicePrefetcher, EpochEnd,
    )

    tel = obs.Telemetry(enabled=telemetry_enabled)
    status_server = None
    scrape_stop = threading.Event()
    scraper = None
    res_sampler = None
    fleet_plane = None

    def _start_resource():
        nonlocal res_sampler

        def _sample():
            sent = getattr(trainer, "_sentinel", None)
            while not scrape_stop.wait(0.2):
                obs.read_rss()
                gauges = tel.snapshot().get("gauges") or {}
                sum(
                    gauges.get(name, 0) or 0
                    for name in ("ingest.ring_bytes",
                                 "ingest.cache_bytes",
                                 "prefetch.staging_bytes")
                )
                if sent is not None:
                    sent.snapshot()

        res_sampler = threading.Thread(target=_sample, daemon=True)
        res_sampler.start()

    def _start_status():
        # Called inside the try below so a pipeline/prefetcher
        # construction failure cannot leak the server + scraper into
        # the rest of the bench (they would keep scraping a dead
        # probe's registry and perturb every later timing).
        nonlocal status_server, scraper
        import urllib.request

        status_server = obs.StatusServer(
            0,
            lambda: {
                "record": "status",
                "time": time.time(),
                "stages": tel.snapshot(),
            },
            telemetry=tel,
        )

        def _scrape():
            url = f"http://127.0.0.1:{status_server.port}/metrics"
            while not scrape_stop.wait(0.2):
                try:
                    urllib.request.urlopen(url, timeout=2).read()
                except Exception:  # noqa: BLE001 - probe must not die
                    pass

        scraper = threading.Thread(target=_scrape, daemon=True)
        scraper.start()

    def _start_fleet():
        # The training-fleet plane at production shape (ISSUE 18):
        # the live /status endpoint with the per-rank metrics_extra
        # hook, a TrainFleet scraping it on the heartbeat cadence
        # (0.2 s, the smoke/aggressive setting), and an external
        # /metrics scraper on top — prices scrape + merge +
        # labeled-series rendering together.
        nonlocal status_server, scraper, fleet_plane
        import urllib.request

        t0 = time.time()
        status_server = obs.StatusServer(
            0,
            lambda: {
                "record": "status",
                "time": time.time(),
                "rank": 0,
                "step": 0,
                "elapsed": round(time.time() - t0, 3),
                "stages": tel.snapshot(),
            },
            telemetry=tel,
            metrics_extra=lambda: (
                fleet_plane.metrics_lines() if fleet_plane else ""
            ),
        )
        fleet_plane = obs.TrainFleet(
            [f"127.0.0.1:{status_server.port}"], interval_s=0.2,
            telemetry=tel,
        )

        def _scrape():
            url = f"http://127.0.0.1:{status_server.port}/metrics"
            while not scrape_stop.wait(0.2):
                try:
                    urllib.request.urlopen(url, timeout=2).read()
                except Exception:  # noqa: BLE001 - probe must not die
                    pass

        scraper = threading.Thread(target=_scrape, daemon=True)
        scraper.start()
    tracer = tracer if tracer is not None else obs.NULL_TRACER
    qual_mon = None
    qual_sketch = None
    pending_q = None
    if quality:
        qual_sketch = obs.StreamSketch(cfg.quality_window)
        qual_mon = obs.QualityMonitor(
            loss_type=cfg.loss_type, window=cfg.quality_window,
            sketch=qual_sketch,
        )
    t_wait = tel.timer("train.wait_input")
    t_disp = tel.timer("train.dispatch")
    # The dataset (not epochs) bounds the cache: size the budget to hold
    # it so the reported ingest_cache outcome only says "overflow" when
    # the files genuinely outgrow host memory expectations.  ordered=True
    # matches the trainer's own pipeline (sequence-numbered delivery —
    # same throughput) and makes the marker positions exact.
    pipeline = BatchPipeline(
        files, cfg, epochs=epochs, shuffle=True, ordered=True,
        cache_epochs=True, cache_max_bytes=4 << 30, epoch_marks=True,
        # Pre-stacked cache: groups stack once at epoch-0 boundaries and
        # replay epochs hand whole super-batches to the prefetcher (the
        # trainer's cache_prestacked path).
        prestack_k=k,
        telemetry=tel,
        tracer=tracer,
        quality=qual_sketch,
    )

    # Real-example counts ride the host stack (transfer thread), keeping
    # the timed loop free of device readbacks.
    def put(stacked):
        return (
            trainer._put_super(stacked),
            int(np.sum(stacked.weights > 0)),
        )

    prefetcher = DevicePrefetcher(
        pipeline, k, put, depth=cfg.prefetch_super_batches, telemetry=tel,
        # put() device_puts (copies out of host memory), so stacking can
        # recycle the pre-allocated staging buffers like the trainer.
        staging=True,
        tracer=tracer,
    )
    it = iter(prefetcher)
    epoch_rates: dict[int, float] = {}
    try:
        if status:
            _start_status()
        if fleet:
            _start_fleet()
        if resource:
            _start_resource()
        warmed = 0
        # sb label counts from the first super-batch CONSUMED, warmup
        # included, so the trace's train.dispatch args.sb stays aligned
        # with the prefetcher's stack/h2d sb ids (trace_chains joins on
        # it).
        sb_i = 0
        while warmed < warmup:
            item = next(it)
            if isinstance(item, EpochEnd):  # tiny stream: epoch < warmup
                continue
            (sb, _), kk = item
            trainer.state = trainer._scan_train_step(trainer.state, sb)
            sb_i += 1
            warmed += kk
        _drain(trainer.state)
        n = 0
        # Wall-clock attribution over the timed region only: subtract
        # the warmup's accumulated wait/dispatch totals.
        wait0, disp0 = t_wait.total_s, t_disp.total_s
        t0 = time.perf_counter()
        n_mark, t_mark = 0, t0
        while True:
            with t_wait.time(), tracer.span("train.wait_input"):
                item = next(it, None)
            if item is None:
                break
            if isinstance(item, EpochEnd):
                _drain(trainer.state)
                now = time.perf_counter()
                if n > n_mark:
                    epoch_rates[item.epoch] = (
                        (n - n_mark) / max(now - t_mark, 1e-9)
                    )
                n_mark, t_mark = n, now
                continue
            (sb, n_real), kk = item
            with t_disp.time(), tracer.span(
                "train.dispatch", args={"sb": sb_i, "k": kk}
            ):
                trainer.state = trainer._scan_train_step(trainer.state, sb)
            sb_i += 1
            n += n_real
            if qual_mon is not None and getattr(
                trainer, "_with_scores", False
            ):
                # The trainer's one-dispatch-delayed quality feed,
                # reproduced: async D2H this dispatch's scores, consume
                # the previous dispatch's.
                arrs = (trainer._last_scores, sb.labels, sb.weights)
                for a in arrs:
                    try:
                        a.copy_to_host_async()
                    except Exception:  # noqa: BLE001 - backend drift
                        pass
                if pending_q is not None:
                    qual_mon.observe(
                        np.asarray(pending_q[0]),
                        np.asarray(pending_q[1]),
                        np.asarray(pending_q[2]),
                    )
                    qual_mon.block()
                pending_q = arrs
        _drain(trainer.state)
        dt = time.perf_counter() - t0
    finally:
        scrape_stop.set()
        if scraper is not None:
            scraper.join()
        if res_sampler is not None:
            res_sampler.join()
        if fleet_plane is not None:
            fleet_plane.close()
        if status_server is not None:
            status_server.close()
        prefetcher.close()
    epoch0 = epoch_rates.get(0, 0.0)
    replays = [r for e, r in epoch_rates.items() if e > 0]
    cached = float(np.median(replays)) if replays else 0.0
    wait_s = t_wait.total_s - wait0
    disp_s = t_disp.total_s - disp0
    snap = tel.snapshot()
    tele_report = {
        "ingest_wait_frac": round(wait_s / max(dt, 1e-9), 4),
        "wait_input_s": round(wait_s, 3),
        "dispatch_s": round(disp_s, 3),
        "timed_wall_s": round(dt, 3),
        "stages": snap,
    }
    # Prestacked-cache split: how many dispatches skipped the transfer-
    # stage stack (epoch 0 stacks once in the pipeline; replays reuse),
    # and the once-per-group stack cost wherever it was paid.
    counters = snap.get("counters", {})
    timers = snap.get("timers", {})
    supers = counters.get("prefetch.super_batches", 0)
    if supers:
        tele_report["prestack_hit_frac"] = round(
            counters.get("prefetch.prestack_hits", 0) / supers, 4
        )
    stack_n = (
        timers.get("prefetch.stack", {}).get("count", 0)
        + timers.get("ingest.prestack", {}).get("count", 0)
    )
    stack_s = (
        timers.get("prefetch.stack", {}).get("total_s", 0.0)
        + timers.get("ingest.prestack", {}).get("total_s", 0.0)
    )
    if stack_n:
        tele_report["stack_ms_per_superbatch"] = round(
            1e3 * stack_s / stack_n, 3
        )
    return (
        (n / dt if dt > 0 else 0.0), pipeline.cache_result, epoch0, cached,
        tele_report,
    )


def _rss_mb() -> float:
    """Current process RSS in MB (not peak: per-section DELTAS are the
    point — peak never comes back down, so one section's residue used
    to skew every later section's reading)."""
    from fast_tffm_tpu import obs as _obs

    return _obs.read_rss()[0] / (1 << 20)


def _with_rss_delta(section_fn, *args) -> dict:
    """Run one bench section and stamp its own RSS before/delta into
    its dict — each section's memory story is measured at its own
    boundaries, regardless of section order."""
    before = _rss_mb()
    out = section_fn(*args)
    if isinstance(out, dict):
        out["rss_before_mb"] = round(before, 1)
        out["rss_delta_mb"] = round(_rss_mb() - before, 1)
    return out


def _spread(samples) -> dict:
    """min/max of a repeated-trial rate measurement — the run-to-run
    swing the medians hide (the documented 0.99-1.10 e2e/step drift),
    quantified per BENCH record instead of folklore."""
    if not samples:
        return {"min": 0.0, "max": 0.0, "n": 0}
    return {
        "min": round(float(min(samples)), 1),
        "max": round(float(max(samples)), 1),
        "n": len(samples),
    }


def _bench_tiered(workers: int) -> dict:
    """Tiered-table section: a V=2^28 Zipf-1.1 training run that CANNOT
    exist as a dense device table (2^28 x 9 f32 params + optimizer slots
    ~= 19 GB before activations), completed through the two-tier store
    with hot_rows = 2^20, plus a dense V=2^26 baseline for the
    migration-overlap comparison (is ingest_wait_frac still ~0 with
    remap+migration riding the prefetch stage?).

    Multi-epoch on purpose: epoch 0 pays the cold-start misses (every
    distinct id loads once), replay epochs re-touch the same rows — the
    steady-state regime a production trainer lives in, and what
    hot_hit_frac is meant to measure.
    """
    import shutil as _sh

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.train.loop import Trainer

    out: dict = {"completed": False}
    tmpdir = tempfile.mkdtemp(prefix="fast_tffm_tiered_")
    try:
        vocab = 1 << 28
        hot = 1 << 20
        batch = 4096
        epochs = 8
        rng = np.random.default_rng(11)
        lines = 12 * batch
        files = gen_libsvm_files(tmpdir, rng, 2, lines // 2, 39, vocab)

        def run(tag, **overrides):
            kw = dict(
                vocabulary_size=vocab, factor_num=8, max_features=39,
                batch_size=batch, learning_rate=0.05,
                model_file=os.path.join(tmpdir, f"model_{tag}"),
                log_steps=0, thread_num=workers, queue_size=workers,
                epoch_num=epochs, steps_per_dispatch=8,
                cache_epochs=True, cache_prestacked=True,
                cache_max_bytes=4 << 30,
                train_files=files,
                save_steps=0,
            )
            kw.update(overrides)
            c = FmConfig(**kw)
            t0 = time.perf_counter()
            r = Trainer(c).train()
            r["train"]["wall_s"] = time.perf_counter() - t0
            _sh.rmtree(c.model_file, ignore_errors=True)
            return r["train"]

        tiered = run("tiered", table_tiering="on", hot_rows=hot)
        # The dense V=2^26 baseline allocates ~5 GB of tables; its
        # failure (tight-memory box) must not discard the tiered result.
        try:
            dense = run("dense", vocabulary_size=1 << 26)
        except Exception as e:  # noqa: BLE001 - keep the tiered half
            dense = None
            out["dense_baseline_error"] = f"{type(e).__name__}: {e}"
        snap = tiered.get("tiered", {})
        out.update({
            "completed": True,
            "vocab_log2": 28,
            "hot_rows_log2": 20,
            "batch_size": batch,
            "epochs": epochs,
            "examples_per_sec": round(tiered["examples_per_sec"], 1),
            "hot_hit_frac": snap.get("hot_hit_frac", 0.0),
            "rows_loaded": snap.get("rows_loaded", 0),
            "rows_evicted": snap.get("rows_evicted", 0),
            "resident_rows": snap.get("resident_rows", 0),
            "cold_store_bytes": snap.get("cold_store_bytes", 0),
            "ingest_wait_frac": tiered["ingest_wait_frac"],
        })
        if dense is not None:
            out["dense_baseline"] = {
                "vocab_log2": 26,
                "examples_per_sec": round(dense["examples_per_sec"], 1),
                "ingest_wait_frac": dense["ingest_wait_frac"],
            }
            # The acceptance comparison: migration must hide behind the
            # prefetch transfer — the tiered run's starvation fraction
            # vs the dense baseline's, same step/ingest configuration.
            out["migration_overlap"] = {
                "ingest_wait_frac_tiered": tiered["ingest_wait_frac"],
                "ingest_wait_frac_dense": dense["ingest_wait_frac"],
                "delta": round(
                    tiered["ingest_wait_frac"]
                    - dense["ingest_wait_frac"], 4
                ),
            }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return out


# Fleet-bench worker (ISSUE 19): one subprocess per role.  "global" is
# the single-process host-global tiered baseline on a (1 data x 2
# model) mesh; "fleet" is one of two gloo ranks running the SAME
# config rank-sharded (one model column = one tier shard each);
# "overlap" A/Bs the compute-overlapped entries exchange on a 2x2
# mesh.  Each prints one `FLEETBENCH {json}` line.
_FLEET_BENCH_WORKER = r"""
import json, os, sys, time

mode = sys.argv[1]          # "fleet" | "global" | "overlap"
tmpdir = sys.argv[2]
threads = int(sys.argv[3])

import jax
jax.config.update("jax_platforms", "cpu")
if mode == "fleet":
    # CPU cross-process collectives need the gloo transport.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=sys.argv[4],
        num_processes=2,
        process_id=int(sys.argv[5]),
    )

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.train.loop import Trainer

VOCAB = 1 << 16
files = sorted(
    os.path.join(tmpdir, f) for f in os.listdir(tmpdir)
    if f.endswith(".libsvm")
)


def run(tag, **kw):
    base = dict(
        vocabulary_size=VOCAB, factor_num=8, max_features=16,
        batch_size=1024, learning_rate=0.05, train_files=files,
        model_file=os.path.join(tmpdir, "model_" + tag),
        log_steps=0, thread_num=threads, queue_size=threads,
        epoch_num=2, steps_per_dispatch=2, save_steps=0,
    )
    base.update(kw)
    t = Trainer(FmConfig(**base))
    t.save = lambda stepno: None  # perf section, not a checkpoint test
    t0 = time.perf_counter()
    r = t.train()
    wall = time.perf_counter() - t0
    exch = t.telemetry.timer("train.exchange").snapshot().get(
        "total_s", 0.0
    )
    return {
        "examples_per_sec": r["train"]["examples_per_sec"],
        "wall_s": round(wall, 3),
        "exchange_frac": round(exch / wall, 6) if wall > 0 else 0.0,
        "device_bytes": int(t._state_bytes_est),
        "tiered": r["train"].get("tiered"),
        "overlap_active": bool(t._overlap_active),
    }


if mode == "fleet":
    rank = int(sys.argv[5])
    port0, port1 = int(sys.argv[6]), int(sys.argv[7])
    out = run(
        "fleet%d" % rank, mesh_data=1, mesh_model=2,
        table_tiering="on", hot_rows=1 << 15,
        tiered_partition="shards",
        status_port=port0 if rank == 0 else port1,
        train_fleet_scrape="127.0.0.1:%d,127.0.0.1:%d" % (port0, port1),
        heartbeat_secs=0.5,
    )
    out["rank"] = rank
elif mode == "global":
    out = run(
        "global", mesh_data=1, mesh_model=2,
        table_tiering="on", hot_rows=1 << 15,
        tiered_partition="global",
    )
else:  # overlap: off/on A/B, same process, same files, same mesh
    port_off, port_on = int(sys.argv[4]), int(sys.argv[5])
    kw = dict(
        mesh_data=2, mesh_model=2, sparse_apply="tile",
        sparse_exchange="entries", heartbeat_secs=0.5,
    )
    out = {
        "off": run("ov_off", sparse_exchange_overlap="off",
                   status_port=port_off,
                   train_fleet_scrape="127.0.0.1:%d" % port_off, **kw),
        "on": run("ov_on", sparse_exchange_overlap="on",
                  status_port=port_on,
                  train_fleet_scrape="127.0.0.1:%d" % port_on, **kw),
    }
print("FLEETBENCH " + json.dumps(out), flush=True)
"""


def _bench_fleet_train(workers: int) -> dict:
    """Fleet-training section (ISSUE 19): the rank-sharded tiered table
    and the overlapped sparse exchange, measured as real processes.

    Three sub-runs over one generated dataset (V=2^16 Zipf, hot=2^15):

      * a single-process host-global tiered baseline on the (1x2) mesh
        — the pre-sharding memory/throughput reference;
      * a 2-rank gloo fleet running the SAME recipe rank-sharded: each
        rank's hot-table+optimizer device bytes and cold-store bytes
        must land at ~1/R of the baseline's (the tentpole's memory
        claim, asserted here as shard_bytes_frac_ok);
      * an overlap A/B on a 2x2 mesh with the entries exchange: the
        train.exchange probe's synchronous window fraction with the
        overlap off vs on — on must read strictly lower (the merge is
        hidden behind rank-local apply; parity is pinned bitwise in
        tests/test_tiered_fleet.py, this section measures the win).
    """
    import socket

    out: dict = {"completed": False}
    tmpdir = tempfile.mkdtemp(prefix="fast_tffm_fleet_")
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        rng = np.random.default_rng(13)
        gen_libsvm_files(tmpdir, rng, 2, 8192, 16, 1 << 16)
        script = os.path.join(tmpdir, "fleet_bench_worker.py")
        with open(script, "w") as f:
            f.write(_FLEET_BENCH_WORKER)
        threads = max(2, workers // 2)

        def spawn(argv, devices):
            env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                XLA_FLAGS=(
                    "--xla_force_host_platform_device_count=%d"
                    % devices
                ),
                PYTHONPATH=repo + os.pathsep + os.environ.get(
                    "PYTHONPATH", ""
                ),
            )
            return subprocess.Popen(
                [sys.executable, script] + [str(a) for a in argv],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )

        def harvest(proc, tag, timeout=600):
            o, e = proc.communicate(timeout=timeout)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"{tag} worker rc={proc.returncode}: {e[-1500:]}"
                )
            for line in o.splitlines():
                if line.startswith("FLEETBENCH "):
                    return json.loads(line[len("FLEETBENCH "):])
            raise RuntimeError(f"{tag} worker printed no result line")

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        glob_res = harvest(
            spawn(["global", tmpdir, threads], 2), "global"
        )

        coord = f"127.0.0.1:{free_port()}"
        p0, p1 = free_port(), free_port()
        procs = [
            spawn(["fleet", tmpdir, threads, coord, r, p0, p1], 1)
            for r in range(2)
        ]
        ranks = []
        try:
            for i, p in enumerate(procs):
                ranks.append(harvest(p, f"fleet rank {i}"))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()

        ov = harvest(
            spawn(["overlap", tmpdir, threads, free_port(),
                   free_port()], 4),
            "overlap",
        )

        rank_bytes = max(r["device_bytes"] for r in ranks)
        glob_bytes = max(1, glob_res["device_bytes"])
        rank_cold = max(
            (r.get("tiered") or {}).get("cold_store_bytes", 0)
            for r in ranks
        )
        glob_cold = (glob_res.get("tiered") or {}).get(
            "cold_store_bytes", 0
        )
        shard_frac = rank_bytes / glob_bytes
        out.update({
            "completed": True,
            "tier_shards": 2,
            "vocab_log2": 16,
            "hot_rows_log2": 15,
            "sharded_examples_per_sec": round(
                min(r["examples_per_sec"] for r in ranks), 1
            ),
            "global_examples_per_sec": round(
                glob_res["examples_per_sec"], 1
            ),
            "fleet_exchange_frac": round(
                max(r["exchange_frac"] for r in ranks), 6
            ),
            "rank_device_bytes": rank_bytes,
            "global_device_bytes": glob_res["device_bytes"],
            "shard_bytes_frac": round(shard_frac, 4),
            # The ~1/R acceptance at R=2: each rank's table+optimizer
            # device bytes must sit near half the host-global run's
            # (w0/scalars stay replicated, hence the band, not 0.5).
            "shard_bytes_frac_ok": bool(0.3 < shard_frac < 0.7),
            "rank_cold_store_bytes": rank_cold,
            "global_cold_store_bytes": glob_cold,
            "cold_bytes_frac": round(
                rank_cold / max(1, glob_cold), 4
            ),
            "rank_owned_shards": [
                (r.get("tiered") or {}).get("owned_shards") for r in ranks
            ],
            "exchange_frac_off": ov["off"]["exchange_frac"],
            "exchange_overlap_frac": ov["on"]["exchange_frac"],
            "overlap_active": bool(ov["on"]["overlap_active"]),
            # The overlap acceptance: the synchronous exchange window
            # must shrink when the merge rides behind rank-local apply.
            "overlap_hides_exchange": bool(
                ov["on"]["exchange_frac"] < ov["off"]["exchange_frac"]
            ),
            "overlap_examples_per_sec_off": round(
                ov["off"]["examples_per_sec"], 1
            ),
            "overlap_examples_per_sec_on": round(
                ov["on"]["examples_per_sec"], 1
            ),
        })
        if not out["shard_bytes_frac_ok"]:
            out["error"] = (
                f"per-rank device bytes {rank_bytes} not ~1/2 of "
                f"host-global {glob_res['device_bytes']}"
            )
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return out


def _bench_quant(workers: int) -> dict:
    """Quantized-table section: the BENCH tiered config (V=2^28 Zipf,
    hot_rows=2^20) trained with each cold_dtype — step rate + real
    compact cold-store footprint fp32 vs bf16 vs int8 — plus the DENSE
    table bytes/row of each serving format (measured by quantizing a
    real table block, not derived): the two byte axes the quantization
    layer exists to shrink.  The acceptance frame: bf16 >= 2x fewer
    table bytes/row (int8 ~4x at quant_chunk=64) with e2e step rate
    within 0.95x of fp32 — quantization must buy bytes, not cost
    throughput (encode/decode rides the transfer thread, off the
    dispatch path).
    """
    import shutil as _sh

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.ops import quant as quant_mod
    from fast_tffm_tpu.train.loop import Trainer

    out: dict = {"completed": False}
    tmpdir = tempfile.mkdtemp(prefix="fast_tffm_quant_")
    try:
        vocab = 1 << 28
        hot = 1 << 20
        batch = 4096
        epochs = 4
        rng = np.random.default_rng(13)
        lines = 8 * batch
        files = gen_libsvm_files(tmpdir, rng, 2, lines // 2, 39, vocab)
        dims = 9  # 1 + factor_num at the bench shapes

        def run(dtype):
            cfg = FmConfig(
                vocabulary_size=vocab, factor_num=8, max_features=39,
                batch_size=batch, learning_rate=0.05,
                model_file=os.path.join(tmpdir, f"model_{dtype}"),
                log_steps=0, thread_num=workers, queue_size=workers,
                epoch_num=epochs, steps_per_dispatch=8,
                cache_epochs=True, cache_prestacked=True,
                cache_max_bytes=4 << 30,
                train_files=files, save_steps=0,
                table_tiering="on", hot_rows=hot, cold_dtype=dtype,
            )
            r = Trainer(cfg).train()
            _sh.rmtree(cfg.model_file, ignore_errors=True)
            snap = r["train"].get("tiered", {})
            return {
                "examples_per_sec": round(
                    r["train"]["examples_per_sec"], 1
                ),
                "cold_store_bytes": snap.get("cold_store_bytes", 0),
                "cold_bytes_per_row": snap.get("cold_bytes_per_row", 0),
                "hot_hit_frac": snap.get("hot_hit_frac", 0.0),
            }

        runs = {}
        for dtype in ("fp32", "bf16", "int8"):
            runs[dtype] = run(dtype)
        # Dense (serving-format) bytes/row, measured on a real block.
        block = np.random.default_rng(5).normal(
            0, 0.01, (4096, dims)
        ).astype(np.float32)
        dense_bpr = {"fp32": 4.0 * dims}
        for dtype in ("bf16", "int8"):
            qt = quant_mod.quantize_table(block, dtype, 64)
            dense_bpr[dtype] = round(qt.nbytes / len(block), 3)
        fp32_rate = runs["fp32"]["examples_per_sec"]
        out.update({
            "completed": True,
            "vocab_log2": 28,
            "hot_rows_log2": 20,
            "epochs": epochs,
            "quant_chunk": 64,
            "runs": runs,
            "table_bytes_per_row": dense_bpr,
            # Bytes-per-row ratios are the gated axis (deterministic —
            # cold_store_bytes is workload-dependent: a run whose hot
            # set never overflows writes no overlay rows at all, and
            # 0/0 would gate nothing).
            "cold_bytes_per_row_frac": {
                d: round(
                    runs[d]["cold_bytes_per_row"]
                    / max(1, runs["fp32"]["cold_bytes_per_row"]), 4
                )
                for d in ("bf16", "int8")
            },
            "step_rate_frac": {
                d: round(
                    runs[d]["examples_per_sec"] / fp32_rate, 4
                ) if fp32_rate > 0 else 0.0
                for d in ("bf16", "int8")
            },
        })
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return out


def _bench_serve(workers: int) -> dict:
    """Serving section: latency under concurrent load through the FULL
    online path — HTTP socket -> request batcher -> compiled
    fixed-shape scorer — the numbers a million-user deployment is
    sized from.

    Client threads fire mixed-size scoring requests (1..64 examples,
    Zipf-ish small-heavy, the online-traffic shape) flat-out for a
    fixed window; latency is measured CLIENT-side (connect to last
    byte, the number a user actually sees), throughput as completed
    requests/s.  ``serve_batch_fill`` and the compile accounting come
    from the server's own telemetry — ``serve_steady_compiles`` MUST
    be 0 (every shape precompiled at warmup; a nonzero value here is
    the latency cliff the ladder exists to prevent).
    """
    import threading as _th
    import urllib.request as _rq

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.models import fm as _fm
    from fast_tffm_tpu.serve.batcher import ServeBatcher
    from fast_tffm_tpu.serve.scorer import FixedShapeScorer
    from fast_tffm_tpu.serve.server import ServeServer
    from fast_tffm_tpu import obs as _obs

    import jax as _jax

    out: dict = {"completed": False}
    server = batcher = None
    try:
        cfg = FmConfig(
            vocabulary_size=1 << 20, factor_num=8, max_features=39,
            batch_size=1024, model_file="/tmp/fast_tffm_serve_bench",
        )
        params = _jax.jit(
            lambda k: _fm.init_params(k, cfg=cfg)
        )(_jax.random.PRNGKey(3))
        tel = _obs.Telemetry()
        scorer = FixedShapeScorer(cfg, params, telemetry=tel)
        warm_compiles = scorer.warmup()
        batcher = ServeBatcher(
            scorer, max_batch_wait_ms=cfg.max_batch_wait_ms,
            queue_size=cfg.queue_size, telemetry=tel,
        )
        server = ServeServer(
            0, batcher, cfg,
            lambda: {"record": "status", "stages": tel.snapshot()},
            telemetry=tel,
        )
        rng = np.random.default_rng(5)
        # Pre-render request bodies (mixed sizes, small-request-heavy)
        # so client threads measure the SERVER, not body formatting.
        sizes = [1, 1, 2, 4, 4, 8, 16, 32, 64]
        bodies = []
        for n in sizes * 4:
            lines = []
            for _ in range(n):
                ids = rng.integers(0, cfg.vocabulary_size, 12)
                lines.append("0 " + " ".join(
                    f"{i}:{rng.uniform(0.1, 1.0):.3f}" for i in ids
                ))
            bodies.append(("\n".join(lines) + "\n").encode())
        url = f"http://127.0.0.1:{server.port}/score"
        duration = 4.0
        n_clients = min(8, max(2, workers))
        lat_lock = _th.Lock()
        lats: list = []
        errors: list = []

        def client(seed: int):
            r = np.random.default_rng(seed)
            end = time.perf_counter() + duration
            my = []
            try:
                while time.perf_counter() < end:
                    body = bodies[int(r.integers(0, len(bodies)))]
                    t0 = time.perf_counter()
                    try:
                        resp = _rq.urlopen(_rq.Request(
                            url, data=body, method="POST"
                        ), timeout=30)
                        resp.read()
                    except Exception as e:  # noqa: BLE001 - report below
                        errors.append(f"{type(e).__name__}: {e}")
                        return
                    my.append(time.perf_counter() - t0)
            finally:
                # A client dying mid-window still contributes the work
                # it DID complete — qps/percentiles must not silently
                # drop a whole client's samples over one late error.
                with lat_lock:
                    lats.extend(my)

        # Warm the HTTP+dispatch path once so client 0's first request
        # doesn't measure connection/jit-cache cold start.
        _rq.urlopen(_rq.Request(url, data=bodies[0], method="POST"),
                    timeout=60).read()
        threads = [
            _th.Thread(target=client, args=(100 + i,))
            for i in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if not lats:
            out["error"] = "no request completed: " + "; ".join(
                errors[:3]
            )
            return out
        arr = np.array(lats) * 1e3
        # Binary-transport probe: the same mixed-size traffic shape
        # over POST /score_bin.  serve.parse_bin times the per-request
        # frame decode exactly where serve.parse times the text parse,
        # so serve_bin_p50_ms vs serve_parse_p50_ms is the measured
        # host cost the binary transport removes from the hot path.
        from fast_tffm_tpu.serve import wire as _wire

        bin_frames = []
        for n in sizes * 4:
            b_ids = rng.integers(
                0, cfg.vocabulary_size, (n, 12)
            ).astype(np.int32)
            b_vals = rng.uniform(0.1, 1.0, (n, 12)).astype(np.float32)
            bin_frames.append(_wire.encode_bin_request(b_ids, b_vals))
        bin_url = f"http://127.0.0.1:{server.port}/score_bin"
        bin_errors = []
        for frame in bin_frames * 3:
            try:
                _rq.urlopen(_rq.Request(
                    bin_url, data=frame, method="POST",
                    headers={"Content-Type":
                             "application/octet-stream"},
                ), timeout=30).read()
            except Exception as e:  # noqa: BLE001 - report below
                bin_errors.append(f"{type(e).__name__}: {e}")
                break
        if bin_errors:
            out["bin_probe_error"] = bin_errors[0]
        snap = tel.snapshot()
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        timers = snap.get("timers", {})
        # Quantized-serving sizing probe: place the SAME params as an
        # int8 table (no HTTP window — placement sets the table-bytes
        # and probe-error gauges; no ladder compiles happen) so the
        # serve section reports the replica-density numbers next to
        # the latency ones.
        try:
            q_tel = _obs.Telemetry()
            q_cfg = FmConfig(
                vocabulary_size=1 << 20, factor_num=8, max_features=39,
                batch_size=1024, serve_table_dtype="int8",
                quant_chunk=64,
                model_file="/tmp/fast_tffm_serve_bench_q",
            )
            FixedShapeScorer(q_cfg, params, telemetry=q_tel)
            q_gauges = q_tel.snapshot().get("gauges", {})
            out["serve_table_mb_int8"] = round(
                q_gauges.get("serve.table_bytes", 0) / (1 << 20), 3
            )
            out["serve_quant_error_max_int8"] = round(
                float(q_gauges.get("serve.quant_error_max", 0.0)), 6
            )
        except Exception as e:  # noqa: BLE001 - probe must not sink it
            out["quant_probe_error"] = f"{type(e).__name__}: {e}"
        # Paired serve-trace overhead probe (ISSUE 14): identical
        # client windows against the SAME warm scorer — tracing OFF
        # (the main stack, no tracer) vs request sampling at 0.1 with
        # a live tracer — back-to-back so box drift can't masquerade
        # as overhead.  serve_trace_overhead = qps_off / qps_on;
        # budget <= 1.05, the standard obs-overhead budget.
        try:
            import dataclasses as _dc
            import shutil as _sh2
            import tempfile as _tf2

            from fast_tffm_tpu.obs.trace import Tracer as _Tracer

            def _probe_window(url_: str, dur: float):
                done = [0]

                def cl(seed: int):
                    r = np.random.default_rng(seed)
                    end = time.perf_counter() + dur
                    while time.perf_counter() < end:
                        body = bodies[int(r.integers(0, len(bodies)))]
                        try:
                            _rq.urlopen(_rq.Request(
                                url_, data=body, method="POST"
                            ), timeout=30).read()
                        except Exception:  # noqa: BLE001 - end window
                            return
                        with lat_lock:
                            done[0] += 1

                ths = [
                    _th.Thread(target=cl, args=(500 + i,))
                    for i in range(n_clients)
                ]
                w0 = time.perf_counter()
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
                return done[0], time.perf_counter() - w0

            trace_dir = _tf2.mkdtemp(prefix="tffm_bench_strace_")
            t_cfg = _dc.replace(
                cfg, serve_trace_sample=0.1,
                trace_file=os.path.join(trace_dir, "serve_trace.json"),
            )
            t_tel = _obs.Telemetry()
            tracer = _Tracer(enabled=True, process_name="serve-bench")
            t_batcher = ServeBatcher(
                scorer, max_batch_wait_ms=cfg.max_batch_wait_ms,
                queue_size=cfg.queue_size, telemetry=t_tel,
                tracer=tracer,
            )
            t_server = ServeServer(
                0, t_batcher, t_cfg,
                lambda: {"record": "status"}, telemetry=t_tel,
                tracer=tracer,
            )
            try:
                t_url = f"http://127.0.0.1:{t_server.port}/score"
                _rq.urlopen(_rq.Request(
                    t_url, data=bodies[0], method="POST"
                ), timeout=60).read()
                n_off, w_off = _probe_window(url, 2.0)
                n_on, w_on = _probe_window(t_url, 2.0)
                qps_off = n_off / w_off if w_off > 0 else 0.0
                qps_on = n_on / w_on if w_on > 0 else 0.0
                out["serve_trace_overhead"] = (
                    round(qps_off / qps_on, 4) if qps_on > 0 else -1.0
                )
                out["serve_trace_dropped"] = int(tracer.dropped_events)
            finally:
                t_server.close()
                t_batcher.close()
                tracer.close()
                _sh2.rmtree(trace_dir, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 - probe must not sink it
            out["trace_probe_error"] = f"{type(e).__name__}: {e}"
        # Paired traffic-capture overhead probe (ISSUE 20): identical
        # client windows against the SAME warm scorer — capture OFF
        # (the main stack) vs a TFC1 CaptureWriter sampling at 0.1 —
        # back-to-back so box drift can't masquerade as overhead.
        # capture_overhead = qps_off / qps_on; budget <= 1.05, the
        # standard obs-overhead budget.
        try:
            import dataclasses as _dc4
            import shutil as _sh4
            import tempfile as _tf4

            def _cap_window(url_: str, dur: float):
                done = [0]

                def cl(seed: int):
                    r = np.random.default_rng(seed)
                    end = time.perf_counter() + dur
                    while time.perf_counter() < end:
                        body = bodies[int(r.integers(0, len(bodies)))]
                        try:
                            _rq.urlopen(_rq.Request(
                                url_, data=body, method="POST"
                            ), timeout=30).read()
                        except Exception:  # noqa: BLE001 - end window
                            return
                        with lat_lock:
                            done[0] += 1

                ths = [
                    _th.Thread(target=cl, args=(900 + i,))
                    for i in range(n_clients)
                ]
                w0 = time.perf_counter()
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
                return done[0], time.perf_counter() - w0

            cap_dir = _tf4.mkdtemp(prefix="tffm_bench_capture_")
            cap_path = os.path.join(cap_dir, "requests.capture")
            c_cfg = _dc4.replace(
                cfg, serve_capture_sample=0.1,
                serve_capture_file=cap_path,
            )
            c_tel = _obs.Telemetry()
            cap = _wire.CaptureWriter(
                cap_path, sample=0.1, telemetry=c_tel,
            )
            c_batcher = ServeBatcher(
                scorer, max_batch_wait_ms=cfg.max_batch_wait_ms,
                queue_size=cfg.queue_size, telemetry=c_tel,
            )
            c_server = ServeServer(
                0, c_batcher, c_cfg,
                lambda: {"record": "status"}, telemetry=c_tel,
                capture=cap,
            )
            try:
                c_url = f"http://127.0.0.1:{c_server.port}/score"
                _rq.urlopen(_rq.Request(
                    c_url, data=bodies[0], method="POST"
                ), timeout=60).read()
                n_off, w_off = _cap_window(url, 2.0)
                n_on, w_on = _cap_window(c_url, 2.0)
                qps_off = n_off / w_off if w_off > 0 else 0.0
                qps_on = n_on / w_on if w_on > 0 else 0.0
                out["capture_overhead"] = (
                    round(qps_off / qps_on, 4) if qps_on > 0 else -1.0
                )
                out["capture_requests"] = int(cap.count)
            finally:
                c_server.close()
                c_batcher.close()
                cap.close()
                _sh4.rmtree(cap_dir, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 - probe must not sink it
            out["capture_probe_error"] = f"{type(e).__name__}: {e}"
        # Vectorized-parser speedup probe (ISSUE 16): the SAME decoded
        # request bodies through parse_request twice — the vec path
        # (the default this section serves with) vs the legacy
        # per-line loop — direct calls, no HTTP, so the ratio isolates
        # the parser.  Median-of-3 windows per mode so one GC pause
        # can't set the headline.
        try:
            import dataclasses as _dc3

            from fast_tffm_tpu.serve.textparse import parse_request

            texts = [b.decode() for b in bodies]
            leg_cfg = _dc3.replace(cfg, serve_parse_mode="legacy")

            def _parse_window(pcfg) -> float:
                p0 = time.perf_counter()
                for txt in texts:
                    parse_request(txt, pcfg)
                return time.perf_counter() - p0

            _parse_window(cfg)  # warm both paths once
            _parse_window(leg_cfg)
            vec_s = sorted(_parse_window(cfg) for _ in range(3))[1]
            leg_s = sorted(_parse_window(leg_cfg) for _ in range(3))[1]
            out["serve_parse_vec_speedup"] = (
                round(leg_s / vec_s, 3) if vec_s > 0 else -1.0
            )
        except Exception as e:  # noqa: BLE001 - probe must not sink it
            out["parse_probe_error"] = f"{type(e).__name__}: {e}"
        # Pooled-accept toggle probe (ISSUE 16): paired client windows
        # against the SAME warm batcher — the pooled front end above
        # vs a legacy thread-per-connection mount
        # (serve_http_threads=0) — back-to-back so box drift can't
        # masquerade as an accept-model difference.
        try:
            import dataclasses as _dc4

            l_cfg = _dc4.replace(cfg, serve_http_threads=0)
            l_server = ServeServer(
                0, batcher, l_cfg,
                lambda: {"record": "status"}, telemetry=tel,
            )
            try:
                l_url = f"http://127.0.0.1:{l_server.port}/score"
                _rq.urlopen(_rq.Request(
                    l_url, data=bodies[0], method="POST"
                ), timeout=60).read()

                def _accept_window(url_: str, dur: float):
                    done = [0]

                    def cl2(seed: int):
                        r = np.random.default_rng(seed)
                        end = time.perf_counter() + dur
                        while time.perf_counter() < end:
                            body = bodies[int(
                                r.integers(0, len(bodies))
                            )]
                            try:
                                _rq.urlopen(_rq.Request(
                                    url_, data=body, method="POST"
                                ), timeout=30).read()
                            except Exception:  # noqa: BLE001 - end
                                return
                            with lat_lock:
                                done[0] += 1

                    ths2 = [
                        _th.Thread(target=cl2, args=(900 + i,))
                        for i in range(n_clients)
                    ]
                    a0 = time.perf_counter()
                    for t in ths2:
                        t.start()
                    for t in ths2:
                        t.join()
                    return done[0], time.perf_counter() - a0

                n_leg, w_leg = _accept_window(l_url, 2.0)
                n_pool, w_pool = _accept_window(url, 2.0)
                qps_leg = n_leg / w_leg if w_leg > 0 else 0.0
                qps_pool = n_pool / w_pool if w_pool > 0 else 0.0
                out["serve_qps_legacy_accept"] = round(qps_leg, 1)
                out["serve_accept_pooled_x"] = (
                    round(qps_pool / qps_leg, 4)
                    if qps_leg > 0 else -1.0
                )
            finally:
                l_server.close()
        except Exception as e:  # noqa: BLE001 - probe must not sink it
            out["accept_probe_error"] = f"{type(e).__name__}: {e}"
        out.update({
            "completed": True,
            "clients": n_clients,
            "duration_s": round(wall, 2),
            "requests": len(lats),
            "serve_qps": round(len(lats) / wall, 1),
            "serve_examples_per_sec": round(
                counters.get("serve.examples", 0) / wall, 1
            ),
            "serve_p50_ms": round(float(np.percentile(arr, 50)), 3),
            "serve_p95_ms": round(float(np.percentile(arr, 95)), 3),
            "serve_p99_ms": round(float(np.percentile(arr, 99)), 3),
            "serve_batch_fill": round(batcher.batch_fill, 4),
            "serve_batches": int(counters.get("serve.batches", 0)),
            "warmup_compiles": warm_compiles,
            "serve_steady_compiles": int(scorer.steady_compiles),
            "max_batch_wait_ms": cfg.max_batch_wait_ms,
            # Device-resident table footprint of THIS (fp32) server and
            # the measured per-request text-parse cost (the host time a
            # binary transport would remove — serve.parse timer).
            "serve_table_mb": round(
                gauges.get("serve.table_bytes", 0) / (1 << 20), 3
            ),
            "serve_parse_p50_ms": float(
                (timers.get("serve.parse") or {}).get("p50_ms", 0.0)
            ),
            "serve_bin_p50_ms": float(
                (timers.get("serve.parse_bin") or {}).get("p50_ms", 0.0)
            ),
            # Which accept model served THIS section's numbers: the
            # pooled worker front end (serve_http_threads > 0) or the
            # legacy thread-per-connection server.
            "serve_http_threads": int(cfg.serve_http_threads),
            "serve_accept_pooled": (
                1 if cfg.serve_http_threads > 0 else 0
            ),
        })
        if errors:
            out["client_errors"] = errors[:5]
    finally:
        # A failed probe must not leak the serve stack (HTTP thread,
        # dispatcher thread, the device-resident scorer) into the
        # sections that run after it — exactly the cross-section
        # contamination this section was reordered to avoid.
        if server is not None:
            server.close()
        if batcher is not None:
            batcher.close()
    return out


def _bench_serve_router(workers: int) -> dict:
    """Scale-out serving section: the 2-replica router fleet under
    concurrent load, then under a 4x-offered-load burst.

    Three numbers are the point (ROADMAP direction 3):

    - ``serve_router_qps`` vs the single-process section's
      ``serve_qps`` — does throughput scale with processes (the main
      wiring records the ratio as ``serve_router_scaleout_x``; on a
      1-core box the replicas share the core, so judge the ratio on a
      multi-core host);
    - ``serve_shed_frac`` under the burst — overload must produce fast
      429s, not an unbounded queue;
    - ``serve_burst_p99_ms`` — the ADMITTED-request tail under 4x
      offered load; graceful degradation means it stays within ~2x the
      unloaded ``serve_router_p99_ms`` instead of collapsing.

    Replicas are REAL subprocesses (shared-nothing: their own jax
    runtimes, own ports) against a checkpoint this section saves; the
    router runs in-process.
    """
    import shutil as _sh
    import tempfile as _tf
    import threading as _th
    import urllib.request as _rq

    from fast_tffm_tpu.config import FmConfig, load_config
    from fast_tffm_tpu.models import fm as _fm
    from fast_tffm_tpu.serve import router as _router
    from fast_tffm_tpu.train import checkpoint as _ckpt

    import jax as _jax

    out: dict = {"completed": False}
    if _jax.default_backend() == "tpu":
        # This process holds the chip, and a replica subprocess that
        # needs it fails or hangs: one process per chip.
        out["not_measured"] = (
            "not measured on chip: the replica subprocesses cannot "
            "share the chip this bench process holds"
        )
        return out
    handle = None
    tmpdir = _tf.mkdtemp(prefix="tffm_bench_router_")
    try:
        model_dir = os.path.join(tmpdir, "model")
        gen_cfg = FmConfig(
            vocabulary_size=1 << 20, factor_num=8, max_features=39,
            batch_size=1024, model_file=model_dir,
        )
        params = _jax.jit(
            lambda k: _fm.init_params(k, cfg=gen_cfg)
        )(_jax.random.PRNGKey(3))
        _ckpt.save(
            model_dir, 1,
            _fm.FmParams(*[np.asarray(x) for x in params]),
        )
        cfg_path = os.path.join(tmpdir, "serve.cfg")
        # 15 ms deadline budget: ~the unloaded p99 (admitted requests
        # stay bounded near it), far below the seconds-long queues a
        # 4x overload would otherwise build.
        with open(cfg_path, "w") as f:
            f.write(f"""[General]
vocabulary_size = {1 << 20}
factor_num = 8
model_file = {model_dir}
[Train]
batch_size = 1024
[Predict]
serve_replicas = 2
serve_shed_deadline_ms = 15
serve_poll_secs = 0
[Tpu]
max_features = 39
""")
        cfg = load_config(cfg_path)
        handle = _router.start_fleet(cfg, cfg_path, port=0)
        url = f"http://127.0.0.1:{handle.port}/score"
        rng = np.random.default_rng(7)

        def make_bodies(sizes):
            rendered = []
            for n in sizes:
                lines = []
                for _ in range(n):
                    ids = rng.integers(0, cfg.vocabulary_size, 12)
                    lines.append("0 " + " ".join(
                        f"{i}:{rng.uniform(0.1, 1.0):.3f}" for i in ids
                    ))
                rendered.append(("\n".join(lines) + "\n").encode())
            return rendered

        # Unloaded window: the online mixed-size shape (same as the
        # single-replica section).  Burst window: max-rung-heavy bodies
        # so 4x the client concurrency genuinely exceeds fleet
        # capacity — overload must come from offered WORK, not from
        # client-thread count.
        bodies = make_bodies([1, 1, 2, 4, 4, 8, 16, 32, 64] * 4)
        burst_bodies = make_bodies([64] * 8 + [32] * 2)
        lat_lock = _th.Lock()

        import http.client as _hc

        router_port = handle.port

        def window(n_clients: int, duration: float, bodies):
            """Closed-loop client window over PERSISTENT keep-alive
            connections (a latency-path client does not reconnect per
            request, and the router keeps 429s on the same
            connection); returns (ok_lats_ms, shed, errors, wall)."""
            lats: list = []
            shed = [0]
            errors: list = []

            def client(seed: int):
                r = np.random.default_rng(seed)
                end = time.perf_counter() + duration
                my = []
                my_shed = 0
                conn = _hc.HTTPConnection(
                    "127.0.0.1", router_port, timeout=30
                )
                try:
                    while time.perf_counter() < end:
                        body = bodies[int(r.integers(0, len(bodies)))]
                        t0 = time.perf_counter()
                        try:
                            conn.request(
                                "POST", "/score", body=body,
                                headers={"Content-Type": "text/plain"},
                            )
                            resp = conn.getresponse()
                            resp.read()
                            if resp.will_close:
                                conn.close()
                                conn = _hc.HTTPConnection(
                                    "127.0.0.1", router_port,
                                    timeout=30,
                                )
                        except (OSError, _hc.HTTPException) as e:
                            errors.append(f"{type(e).__name__}: {e}")
                            return
                        if resp.status == 200:
                            my.append(time.perf_counter() - t0)
                        elif resp.status == 429:
                            # A shed IS the overload discipline
                            # working: count it, back off briefly
                            # (real clients honor Retry-After; the
                            # bench caps it at 50 ms so the window
                            # still measures sustained overload —
                            # zero-backoff clients would just burn
                            # the box on the shed path itself).
                            my_shed += 1
                            time.sleep(0.05)
                        else:
                            errors.append(f"HTTP {resp.status}")
                            return
                finally:
                    conn.close()
                    with lat_lock:
                        lats.extend(my)
                        shed[0] += my_shed

            threads = [
                _th.Thread(target=client, args=(200 + i,))
                for i in range(n_clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return lats, shed[0], errors, time.perf_counter() - t0

        # Warm the proxy path (connection pools, both replicas) before
        # measuring.
        for _ in range(4):
            _rq.urlopen(_rq.Request(url, data=bodies[0], method="POST"),
                        timeout=60).read()
        n_clients = min(8, max(2, workers))
        lats, shed, errors, wall = window(n_clients, 4.0, bodies)
        if not lats:
            out["error"] = "no request completed: " + "; ".join(
                errors[:3]
            )
            return out
        arr = np.array(lats) * 1e3
        out.update({
            "replicas": len(handle.replicas),
            "clients": n_clients,
            "duration_s": round(wall, 2),
            "requests": len(lats),
            "serve_router_qps": round(len(lats) / wall, 1),
            "serve_router_p50_ms": round(
                float(np.percentile(arr, 50)), 3
            ),
            "serve_router_p99_ms": round(
                float(np.percentile(arr, 99)), 3
            ),
            "unloaded_shed": shed,
        })
        # The burst's fair baseline: the same max-rung-heavy bodies,
        # unloaded (a 64-example request costs more than the mixed
        # shape above even with no queue).
        h_lats, _, h_errors, _ = window(n_clients, 2.0, burst_bodies)
        h_arr = np.array(h_lats) * 1e3 if h_lats else np.zeros(1)
        heavy_unloaded_p99 = float(np.percentile(h_arr, 99))
        # Burst probe: 4x the offered concurrency for 3 s, max-rung
        # bodies.  The admission budget must shed (429) rather than
        # queue, and the ADMITTED tail must stay near the unloaded
        # tail (serve_burst_p99_x is admitted-p99 over the
        # same-bodies unloaded p99 — the graceful-degradation ratio;
        # note everything here shares one box, so core contention
        # itself inflates burst service time on small hosts).
        b_lats, b_shed, b_errors, b_wall = window(
            n_clients * 4, 3.0, burst_bodies
        )
        total = len(b_lats) + b_shed
        b_arr = np.array(b_lats) * 1e3 if b_lats else np.zeros(1)
        burst_p99 = float(np.percentile(b_arr, 99))
        out.update({
            "burst_clients": n_clients * 4,
            "burst_requests": total,
            "serve_shed_frac": round(
                b_shed / total, 4
            ) if total else 0.0,
            "serve_burst_p99_ms": round(burst_p99, 3),
            "burst_unloaded_p99_ms": round(heavy_unloaded_p99, 3),
            "serve_burst_p99_x": round(
                burst_p99 / heavy_unloaded_p99, 3
            ) if heavy_unloaded_p99 > 0 else 0.0,
            "burst_admitted_qps": round(
                len(b_lats) / b_wall, 1
            ) if b_wall > 0 else 0.0,
        })
        errors.extend(h_errors)
        if errors or b_errors:
            out["client_errors"] = (errors + b_errors)[:5]
        # Per-replica steady-compile audit: the zero-compile contract
        # must hold on every replica (scraped from each replica's own
        # /metrics), and the router must not have evicted anyone.
        steady = []
        for rep in handle.replicas:
            try:
                text = _rq.urlopen(
                    f"http://{rep.host}:{rep.port}/metrics", timeout=5
                ).read().decode()
                m = re.search(
                    r"^tffm_serve_steady_compiles (\d+)", text,
                    re.MULTILINE,
                )
                steady.append(int(m.group(1)) if m else -1)
            except Exception:  # noqa: BLE001 - audit is best-effort
                steady.append(-1)
        out["serve_router_steady_compiles"] = max(steady) if steady \
            else -1
        router_block = handle.router._build()["serve"]
        out["router_evictions"] = router_block["evictions"]
        out["router_retries"] = router_block["retries"]
        # Fleet metrics-scrape cost (ISSUE 14): one health-loop sweep
        # pulling every replica's /status serve block — the price of
        # one-scrape-sees-the-whole-fleet, kept visible so it can't
        # silently grow with fleet size.
        r_timers = handle.telemetry.snapshot().get("timers", {})
        out["fleet_scrape_ms"] = float(
            (r_timers.get("serve.fleet_scrape") or {}).get(
                "p50_ms", 0.0
            )
        )
        out["fleet_replicas_scraped"] = int(
            router_block.get("replicas_scraped", 0)
        )
        out["completed"] = True
    finally:
        if handle is not None:
            handle.close()
        _sh.rmtree(tmpdir, ignore_errors=True)
    return out


def _bench_pipeline_ingest(files, cfg, parse_processes: int
                           ) -> tuple[float, float]:
    """(lines/sec, ring_zero_copy_frac) draining the FULL BatchPipeline
    (reader + parse workers + delivery) with no training attached —
    threads vs a process pool on the same files is the parse_processes
    scaling comparison, now running on the inbound SHM ring (the frac
    reports how many raw windows went zero-copy vs pickled; -1 when the
    mode has no ring, i.e. threads)."""
    import dataclasses

    from fast_tffm_tpu import obs
    from fast_tffm_tpu.data.pipeline import BatchPipeline

    c = dataclasses.replace(cfg, parse_processes=parse_processes)
    tel = obs.Telemetry()
    n = 0
    t0 = time.perf_counter()
    for b in BatchPipeline(files, c, epochs=1, shuffle=False,
                           telemetry=tel):
        n += int(np.count_nonzero(b.weights))
    dt = time.perf_counter() - t0
    counters = tel.snapshot().get("counters", {})
    ring = counters.get("ingest.ring_windows", 0)
    fallback = counters.get("ingest.ring_fallback_windows", 0)
    frac = ring / (ring + fallback) if (ring + fallback) else -1.0
    return (n / dt if dt > 0 else 0.0), frac


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["e2e", "step"], default="e2e")
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()

    # Preflight: tier-1 marker audit (tools/check_tier1.py, static AST —
    # milliseconds).  A test file whose every test went slow has silently
    # dropped out of the correctness gate; the bench JSON records that
    # drift every run so it can't pass unnoticed.
    tier1_audit = None
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, os.path.join(repo, "tools"))
        import check_tier1

        a = check_tier1.audit(os.path.join(repo, "tests"), repo)
        tier1_audit = {
            "ok": a["ok"], "files": a["files"], "tier1": a["tier1"],
            "slow": a["slow"],
        }
        if a["problems"]:
            tier1_audit["problems"] = a["problems"][:5]
    except Exception as e:  # noqa: BLE001 - preflight must not sink bench
        tier1_audit = {"ok": False, "problems": [f"audit failed: {e}"]}

    # Preflight: the full static-analysis suite (tools/lint — tier-1
    # audit is one of its rules, but the bench JSON keeps tier1_audit
    # as its own back-compat block).  lint_findings_new is a gated
    # --compare key: a PR that introduces a new finding regresses the
    # bench trajectory exactly like a perf key (direction: low).
    lint_findings_new = None
    lint_findings_baselined = None
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from tools import lint as lint_mod

        lr = lint_mod.run(root=repo)
        lint_findings_new = (
            len(lr["new"]) + len(lr["stale"]) + len(lr["uncommented"])
        )
        lint_findings_baselined = len(lr["baselined"])
    except Exception as e:  # noqa: BLE001 - preflight must not sink bench
        print(f"lint preflight failed: {e}", file=sys.stderr)

    # Preflight: bench-trajectory trend (the same adjacent-step rule
    # `tools/report.py --timeline` prints) over any committed
    # BENCH_r*.json stack next to this script.  timeline_regressions
    # is the count of keys whose trend already crossed the threshold —
    # a numeric top-level key, so --compare gates a NEW one appearing
    # (direction: low) without anyone remembering to run --timeline.
    timeline_regs = None
    timeline_reg_keys = None
    try:
        import glob as glob_mod

        repo = os.path.dirname(os.path.abspath(__file__))
        hist = sorted(glob_mod.glob(os.path.join(repo, "BENCH_r*.json")))
        if len(hist) >= 2:
            if repo not in sys.path:
                sys.path.insert(0, repo)
            from tools import report as report_mod

            tr = report_mod.timeline_regressions(hist)
            timeline_regs = len(tr["regressions"])
            if tr["regressions"]:
                timeline_reg_keys = dict(
                    sorted(tr["regressions"].items())[:8]
                )
    except Exception as e:  # noqa: BLE001 - preflight must not sink bench
        print(f"timeline preflight failed: {e}", file=sys.stderr)

    import jax

    from fast_tffm_tpu import platform as platform_mod

    platform_mod.enable_compile_cache(platform_mod.REPO_COMPILE_CACHE_DIR)
    platform = jax.devices()[0].platform
    n_chips = len(jax.devices())
    on_tpu = platform not in ("cpu",)
    step_rate, e2e_rate, parse_rate, bf16_rate = 0.0, 0.0, 0.0, 0.0
    step_rate_k1, e2e_rate_k1 = 0.0, 0.0
    s_samples, s1_samples, e_samples = [], [], []
    tiered_section = None
    fleet_section = None
    serve_section = None
    serve_router_section = None
    quant_section = None
    dispatch_overhead_ms, h2d_overlap_frac = 0.0, 0.0
    e2e_epoch0, e2e_cached = 0.0, 0.0
    ingest_threads_rate, ingest_procs_rate = 0.0, 0.0
    ring_zero_copy_frac = -1.0
    bench_procs = 0
    ingest_cache = "off"
    tele_report = None
    e2e_tel_off = 0.0
    e2e_trace_on, trace_events = 0.0, 0
    e2e_status_on = 0.0
    e2e_resource_on = 0.0
    e2e_quality_on = 0.0
    e2e_fleet_on = 0.0
    bench_compile_s = 0.0
    autotune_rate_auto, autotune_rate_ref = 0.0, 0.0
    autotune_kernel_impl, autotune_times = "", {}
    K = 8  # steps_per_dispatch for the headline (K=1 also reported)
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.train.loop import Trainer

    workers = min(16, max(4, (os.cpu_count() or 4) - 2))

    def make_cfg(**overrides):
        kw = dict(
            vocabulary_size=1 << 22 if on_tpu else 1 << 20,
            factor_num=8,
            max_features=39,
            batch_size=(16384 if on_tpu else 4096) * max(1, n_chips),
            learning_rate=0.05,
            model_file="/tmp/fast_tffm_tpu_bench_model",
            log_steps=0,
            thread_num=workers,
            # One queued group per worker: shallower starves parallel
            # parsers on multi-core hosts, deeper just front-loads
            # parsing (the timed-region sizing below scales with the
            # in-flight bound so warmup can't pre-parse the measured
            # region either way).
            queue_size=workers,
        )
        kw.update(overrides)
        c = FmConfig(**kw)
        shutil.rmtree(c.model_file, ignore_errors=True)
        return c

    cfg = make_cfg()
    trainer = Trainer(cfg)

    steps = args.steps if on_tpu else min(args.steps, 10)
    # Dispatch split: the same device step at one dispatch per batch
    # (K=1) vs the K-step fused scan; the per-step difference is the
    # amortized Python/runtime dispatch overhead.  Step-only regions
    # are short (seconds), so each rate is a median of 3 trials —
    # single-shot step rates on a shared box swing several percent,
    # which would swamp the e2e-vs-step split the JSON reports.
    trials = 1 if on_tpu else 3
    s1_samples = [
        _bench_step_only(trainer, cfg, steps) for _ in range(trials)
    ]
    step_rate_k1 = float(np.median(s1_samples))
    s_samples = [
        _bench_step_scan(trainer, cfg, max(steps, K), K)
        for _ in range(trials)
    ]
    step_rate = float(np.median(s_samples))
    # Compile attribution so far (the step-only regions' K=8 + K=1
    # scan compiles); the e2e block re-captures after its probes.
    if getattr(trainer, "_sentinel", None) is not None:
        bench_compile_s = trainer._sentinel.compile_s

    if args.mode == "e2e":
        tmpdir = tempfile.mkdtemp(prefix="fast_tffm_bench_")
        try:
            rng = np.random.default_rng(7)
            # Full GLOBAL batches per epoch (scales with chip
            # count so no partial zero-padded groups distort the
            # judged number).  An epoch must span SEVERAL K=8
            # dispatches: the e2e warmup consumes one whole
            # dispatch, and the per-epoch rate split (epoch-0
            # parse vs cached replay) needs timed batches left in
            # epoch 0 after it — 8 batches/epoch used to leave
            # zero and reported e2e_epoch0 = 0.  CPU pays 32
            # (cheap lines); TPU pays 16 (disk-bound filegen).
            n_files = 4
            lines_per_file = (4 if on_tpu else 8) * cfg.batch_size
            files = gen_libsvm_files(
                tmpdir, rng, n_files, lines_per_file,
                cfg.max_features, cfg.vocabulary_size,
            )
            parse_rate = _bench_parse_only(files, cfg)
            batches_per_epoch = n_files * lines_per_file // cfg.batch_size
            # Timed region must be >> the max in-flight buffer
            # (work + out queues + one batch per parser thread),
            # else the timed loop mostly drains batches pre-parsed
            # during warmup and overstates ingest throughput.
            # In-flight now also counts the transfer stage's
            # stacked super-batches (depth + 1 in flight, K
            # batches each).
            inflight = (
                cfg.thread_num + 2 * cfg.queue_size + 2
                + K * (cfg.prefetch_super_batches + 1)
            )
            want_batches = 4 + max(
                64 if on_tpu else 24,
                (5 if on_tpu else 3) * inflight,
            )
            # >= 3 epochs so the cached-replay rate (epochs 1+)
            # gets at least two windows behind the epoch-0 parse.
            epochs = max(3, -(-want_batches // batches_per_epoch))
            # PAIRED measurement of the judged split: alternate
            # K=8 step-only and K=8 e2e rounds and take the
            # median of each.  The two rates are compared against
            # each other, and on a shared box throughput drifts
            # several percent minute to minute — separately-timed
            # windows would hand that drift straight to the
            # ratio, while interleaved rounds feed both medians
            # from the same span.
            rounds = 1 if on_tpu else 3
            s_samples, s1_samples, e_samples = [], [], []
            e0_samples, ec_samples, off_samples = [], [], []
            for _ in range(rounds):
                s1_samples.append(_bench_step_only(
                    trainer, cfg, steps
                ))
                s_samples.append(_bench_step_scan(
                    trainer, cfg, max(steps, 2 * K), K
                ))
                r, ingest_cache, r0, rc, tele_report = _bench_e2e(
                    trainer, cfg, files, warmup=4, epochs=epochs,
                    k=K,
                )
                e_samples.append(r)
                e0_samples.append(r0)
                ec_samples.append(rc)
                # Telemetry overhead probe, PAIRED: the identical
                # K=8 e2e with no-op instruments runs inside the
                # same round, so the on/off ratio feeds both
                # medians from the same machine-state span
                # instead of handing run-to-run drift to a
                # single trailing off-run.
                off_r, _, _, _, _ = _bench_e2e(
                    trainer, cfg, files, warmup=4, epochs=epochs,
                    k=K, telemetry_enabled=False,
                )
                off_samples.append(off_r)
            e2e_tel_off = float(np.median(off_samples))
            # All three medians feed from the same windows, so
            # the derived dispatch_overhead_ms and e2e/step split
            # compare like with like.
            step_rate_k1 = float(np.median(s1_samples))
            step_rate = float(np.median(s_samples))
            e2e_rate = float(np.median(e_samples))
            e2e_epoch0 = float(np.median(e0_samples))
            e2e_cached = float(np.median(ec_samples))
            # K=1 comparison point (the classic per-batch loop,
            # now also through the transfer stage).
            e2e_rate_k1, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4, epochs=epochs, k=1
            )
            # Trace-overhead probe (telemetry_on_vs_off-style):
            # the identical K=8 e2e with the causal span layer
            # recording through pipeline + prefetcher + the
            # dispatch loop.  trace_overhead = off/on rate
            # ratio; the span layer's budget is <= 1.05.
            from fast_tffm_tpu import obs as _obs

            _tr = _obs.Tracer(enabled=True)
            e2e_trace_on, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4,
                epochs=epochs, k=K, tracer=_tr,
            )
            trace_events = len(_tr.take())
            # Status-endpoint overhead probe (same shape as the
            # telemetry/trace probes): the identical K=8 e2e
            # with the live /metrics endpoint up AND scraped
            # every 200 ms.  status_endpoint_overhead = off/on
            # rate ratio; budget <= 1.05 like the other layers.
            e2e_status_on, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4,
                epochs=epochs, k=K, status=True,
            )
            # Resource-plane overhead probe (the PR 8 pillar,
            # same paired shape): the identical K=8 e2e with
            # RSS + component-ledger + compile-sentinel
            # sampling at an aggressive 200 ms cadence.
            # resource_overhead = off/on rate ratio; budget
            # <= 1.05 like every other obs layer.
            e2e_resource_on, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4,
                epochs=epochs, k=K, resource=True,
            )
            # Model-quality overhead probe (ISSUE 15, same
            # paired shape): the identical K=8 e2e with the
            # parse-path drift sketches + windowed online-eval
            # monitor attached.  quality_overhead = off/on
            # rate ratio; budget <= 1.05 like every obs layer.
            e2e_quality_on, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4,
                epochs=epochs, k=K, quality=True,
            )
            # Training-fleet scrape overhead probe (ISSUE 18,
            # same paired shape): the identical K=8 e2e with
            # the live endpoint up, a TrainFleet scraping its
            # /status every 200 ms, AND /metrics (with the
            # per-rank labeled-series hook) scraped on top.
            # fleet_scrape_overhead = off/on rate ratio;
            # budget <= 1.05 like every other obs layer.
            e2e_fleet_on, _, _, _, _ = _bench_e2e(
                trainer, cfg, files, warmup=4,
                epochs=epochs, k=K, fleet=True,
            )
            # Kernel-autotune overhead probe (ISSUE 17),
            # PAIRED: the identical K=8 step-scan through a
            # trainer resolved via interaction_impl=auto vs
            # one PINNED to reference, interleaved rounds.  On
            # CPU auto collapses to reference at init (single
            # candidate, zero measurement), so the two steady
            # states run the same executable and the ratio
            # prices exactly the autotuner's footprint —
            # budget <= 1.05.  On TPU the ratio instead shows
            # what the measured promotion buys (< 1.0 when a
            # non-reference impl wins).  The probe keeps the
            # autotune cache in memory only so a bench never
            # leaves autotune_cache.json next to the
            # throwaway /tmp model dir.
            _env_prev = os.environ.get(
                "FAST_TFFM_AUTOTUNE_CACHE"
            )
            os.environ["FAST_TFFM_AUTOTUNE_CACHE"] = ""
            try:
                # Own model dirs: make_cfg rmtree's its
                # model_file, and sharing one dir would
                # both delete the main trainer's and make
                # the second probe trainer restore the
                # first's checkpoint.
                c_auto = make_cfg(
                    interaction_impl="auto",
                    model_file=os.path.join(
                        tmpdir, "autotune_m_auto"
                    ),
                )
                c_ref = make_cfg(
                    interaction_impl="reference",
                    model_file=os.path.join(
                        tmpdir, "autotune_m_ref"
                    ),
                )
                t_auto = Trainer(c_auto)
                t_ref = Trainer(c_ref)
                autotune_kernel_impl = t_auto.kernel_impl
                if t_auto._autotune is not None:
                    autotune_times = dict(
                        t_auto._autotune.times_ms
                    )
                a_samples, p_samples = [], []
                for _ in range(rounds):
                    a_samples.append(_bench_step_scan(
                        t_auto, c_auto, max(steps, 2 * K), K
                    ))
                    p_samples.append(_bench_step_scan(
                        t_ref, c_ref, max(steps, 2 * K), K
                    ))
                autotune_rate_auto = float(
                    np.median(a_samples)
                )
                autotune_rate_ref = float(
                    np.median(p_samples)
                )
                del t_auto, t_ref
            finally:
                if _env_prev is None:
                    os.environ.pop(
                        "FAST_TFFM_AUTOTUNE_CACHE", None
                    )
                else:
                    os.environ[
                        "FAST_TFFM_AUTOTUNE_CACHE"
                    ] = _env_prev
            # Compile-sentinel attribution for the BENCH JSON:
            # total train-step compile wall time this bench's
            # trainer paid (the AOT cache makes it exact).
            sent = getattr(trainer, "_sentinel", None)
            if sent is not None:
                bench_compile_s = sent.compile_s
            # parse_processes scaling: drain the bare pipeline
            # with thread workers vs a spawned process pool on
            # the same files (no training attached).
            bench_procs = min(4, max(2, workers // 2))
            ingest_threads_rate, _ = _bench_pipeline_ingest(
                files, cfg, 0
            )
            ingest_procs_rate, ring_zero_copy_frac = (
                _bench_pipeline_ingest(files, cfg, bench_procs)
            )
            # How much of the synchronous stack+H2D cost the
            # transfer thread hides: 1 - (e2e gap) / (blocking
            # transfer cost), both per example at K=8.  An
            # estimate — the residual gap also carries any
            # unhidden parse time.
            put_s = _bench_put_only(trainer, cfg, K)
            if e2e_rate > 0 and step_rate > 0 and put_s > 0:
                gap = max(0.0, 1.0 / e2e_rate - 1.0 / step_rate)
                h2d_overlap_frac = max(0.0, 1.0 - gap / put_s)
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
    # bf16 compute variant (rounds the interaction operands, halving
    # the gathered-rows HBM streams), same kernel path as the f32
    # config.  Runs LAST so the adjacent f32 K=8 step-only and e2e
    # measurements (the judged ratio) see the same machine state.
    c16 = make_cfg(compute_dtype="bfloat16")
    t16 = Trainer(c16)
    bf16_rate = _bench_step_only(t16, c16, steps)
    del t16

    if args.mode == "e2e":
        del trainer
        # Serving section: latency under concurrent load through
        # the HTTP -> batcher -> compiled-ladder path (SERVING.md).
        # Runs BEFORE the tiered section: the V=2^28 cold stores
        # leave ~7 GB of process RSS behind, and serving latency
        # measured under that allocator pressure read ~10x worse
        # than the same probe on a clean process.
        # Every section stamps its own RSS before/delta
        # (_with_rss_delta): the tiered section's ~7 GB residue can
        # never skew another section's memory reading again,
        # whatever the order.
        serve_section = _with_rss_delta(_bench_serve, workers)
        # Scale-out serving section: the 2-replica router fleet
        # (real subprocess replicas) under load and under a
        # 4x-offered burst — the shed/eviction discipline's
        # numbers.  Runs right after the single-replica section so
        # serve_router_qps / serve_qps is measured on the same box
        # state.
        serve_router_section = _with_rss_delta(
            _bench_serve_router, workers
        )
        # Tiered-table section: the V=2^28 run a dense device table
        # cannot hold, plus its dense V=2^26 overlap baseline.  Its
        # own trainers/files; isolated from the judged numbers above.
        tiered_section = _with_rss_delta(_bench_tiered, workers)
        # Quantized-table section: the same tiered config trained
        # under each cold_dtype (bytes per row vs step rate).
        quant_section = _with_rss_delta(_bench_quant, workers)
        # Fleet-training section: rank-sharded tiering (2 gloo
        # ranks vs the host-global baseline — the ~1/R memory
        # claim) and the overlapped-exchange A/B (ISSUE 19).
        fleet_section = _with_rss_delta(_bench_fleet_train, workers)
    # Derived AFTER every update to the step rates so the JSON is
    # internally consistent (the e2e block folds adjacent K=8 samples
    # into the step median).
    if step_rate_k1 > 0 and step_rate > 0:
        dispatch_overhead_ms = max(
            0.0,
            (1.0 / step_rate_k1 - 1.0 / step_rate) * cfg.batch_size * 1e3,
        )
    headline = e2e_rate if e2e_rate > 0 else step_rate
    kind = "e2e" if e2e_rate > 0 else "step_only"
    ingest_note = (
        "libsvm ingest via native parser" if kind == "e2e"
        else "device-resident batches, no ingest"
    )
    per_chip = headline / max(1, n_chips)
    bdesc = cfg.batch_size if cfg else 0
    vdesc = cfg.vocabulary_size.bit_length() - 1 if cfg else 0
    result = {
        "metric": (
            f"fm_train_examples_per_sec_{kind} ({platform} x{n_chips}, "
            f"B={bdesc}, F=39, k=8, vocab=2^{vdesc}, zipf1.1 ids, "
            f"{ingest_note})"
        ),
        "value": round(headline, 1),
        "unit": "examples/sec",
        "vs_baseline": round(per_chip / PER_CHIP_TARGET, 4),
        "steps_per_dispatch": K,
        "step_only_examples_per_sec": round(step_rate, 1),
        "step_only_k1_examples_per_sec": round(step_rate_k1, 1),
        "step_only_bf16_examples_per_sec": round(bf16_rate, 1),
        "e2e_examples_per_sec": round(e2e_rate, 1),
        "e2e_k1_examples_per_sec": round(e2e_rate_k1, 1),
        # Per-epoch split of the judged e2e run: epoch 0 pays the parse,
        # epochs 1+ replay the parsed-batch cache; cached/step is the
        # "ingest overhead left after caching" ratio (target >= 0.97).
        "e2e_epoch0_examples_per_sec": round(e2e_epoch0, 1),
        "e2e_cached_epoch_examples_per_sec": round(e2e_cached, 1),
        "cached_epoch_vs_step_only": round(
            e2e_cached / step_rate, 4
        ) if step_rate > 0 else 0.0,
        # min/max of the repeated trials feeding each judged median —
        # the measured run-to-run swing, no longer folklore.
        "step_rate_spread": {
            "step_only": _spread(s_samples),
            "step_only_k1": _spread(s1_samples),
            "e2e": _spread(e_samples),
        },
        "dispatch_overhead_ms": round(dispatch_overhead_ms, 3),
        "h2d_overlap_frac": round(h2d_overlap_frac, 4),
        "ingest_cache": ingest_cache,  # "cached" | "overflow" | "off"
        # Telemetry overhead: the same K=8 e2e run with instruments
        # disabled; on/off ≈ 1.0 means the layer costs noise-level time.
        "e2e_telemetry_off_examples_per_sec": round(e2e_tel_off, 1),
        "telemetry_on_vs_off": round(
            e2e_rate / e2e_tel_off, 4
        ) if e2e_tel_off > 0 and e2e_rate > 0 else 0.0,
        # Trace overhead: the same K=8 e2e with the causal span layer
        # recording (pipeline/prefetcher/dispatch spans).  off/on rate
        # ratio; budget <= 1.05 (box noise is ±3%, so ~1.0 = free).
        "e2e_trace_on_examples_per_sec": round(e2e_trace_on, 1),
        "trace_overhead": round(
            e2e_rate / e2e_trace_on, 4
        ) if e2e_trace_on > 0 and e2e_rate > 0 else 0.0,
        "trace_events_recorded": trace_events,
        # Status-endpoint overhead: the same K=8 e2e with the live
        # /metrics endpoint up and scraped every 200 ms.  off/on rate
        # ratio; budget <= 1.05 (endpoint requests only read the
        # thread-safe snapshots, so ~1.0 = free).
        "e2e_status_on_examples_per_sec": round(e2e_status_on, 1),
        "status_endpoint_overhead": round(
            e2e_rate / e2e_status_on, 4
        ) if e2e_status_on > 0 and e2e_rate > 0 else 0.0,
        # Training-fleet scrape overhead: the same K=8 e2e with the
        # endpoint up, a TrainFleet scraping /status every 200 ms, and
        # /metrics (per-rank labeled series included) scraped on top.
        # off/on rate ratio, budget <= 1.05 — scrape + merge + render
        # all run off the training thread, so ~1.0 = free.
        "e2e_fleet_on_examples_per_sec": round(e2e_fleet_on, 1),
        "fleet_scrape_overhead": round(
            e2e_rate / e2e_fleet_on, 4
        ) if e2e_fleet_on > 0 and e2e_rate > 0 else 0.0,
        # Resource-plane overhead: the same K=8 e2e with RSS/ledger/
        # sentinel sampling at 200 ms.  off/on rate ratio, budget
        # <= 1.05 — the sampler only reads /proc and lock-guarded
        # snapshots, so ~1.0 = free.
        "e2e_resource_on_examples_per_sec": round(e2e_resource_on, 1),
        "resource_overhead": round(
            e2e_rate / e2e_resource_on, 4
        ) if e2e_resource_on > 0 and e2e_rate > 0 else 0.0,
        # Model-quality overhead: the same K=8 e2e with drift sketches
        # on the parse path + the windowed online-eval monitor
        # consuming every dispatch's scores.  off/on rate ratio,
        # budget <= 1.05 — sketch updates are batch-cadence numpy and
        # the window stats are memoized.
        "e2e_quality_on_examples_per_sec": round(e2e_quality_on, 1),
        "quality_overhead": round(
            e2e_rate / e2e_quality_on, 4
        ) if e2e_quality_on > 0 and e2e_rate > 0 else 0.0,
        # Sketch/PSI correctness floor: two independent samples of the
        # SAME synthetic distribution through the full SketchSet + PSI
        # machinery must read ~0 (the debiased identity).  A rise here
        # is a sketch regression, not a data change.
        "quality_psi_identity": _bench_quality_identity(),
        # Memory & compile attribution of the bench process itself:
        # peak RSS over the whole bench (epoch caches + staged input +
        # jit artifacts), and the train-step compile seconds the AOT
        # sentinel accounted.  --compare gates both (low).
        "peak_rss_mb": round(obs_mod.read_rss()[1] / (1 << 20), 1),
        "compile_s": round(bench_compile_s, 3),
        # Kernel autotuner (ISSUE 17): which interaction impl `auto`
        # promoted for this backend/shape (informational — a string,
        # so --compare skips it), the per-candidate measurement
        # medians when a measurement ran (empty dict on CPU where
        # reference wins by single-candidate), and the paired
        # steady-state ratio reference/auto — the autotuner's whole
        # footprint, budget <= 1.05 (< 1.0 on TPU means the promoted
        # impl is actually faster).
        "kernel_impl": autotune_kernel_impl,
        "autotune_overhead": round(
            autotune_rate_ref / autotune_rate_auto, 4
        ) if autotune_rate_auto > 0 and autotune_rate_ref > 0 else 0.0,
        "autotune_times_ms": autotune_times,
        # Persistent compile cache (JAX_COMPILATION_CACHE_DIR, else the
        # fixed <repo>/.jax_cache): hit/miss events over the whole
        # bench — a second run on the same machine shows hits.
        **{f"compile_cache_{k}": v
           for k, v in platform_mod.compile_cache_stats().items()},
        "parse_lines_per_sec": round(parse_rate, 1),
        # Bare-pipeline drain rates: thread workers vs a spawned
        # parse-process pool on the same files (GIL-free scaling probe).
        "pipeline_ingest_threads_lines_per_sec": round(
            ingest_threads_rate, 1
        ),
        "pipeline_ingest_procs_lines_per_sec": round(
            ingest_procs_rate, 1
        ),
        # Inbound SHM ring: fraction of the procs drain's raw windows
        # that went zero-copy (descriptor-only queue messages); -1 if
        # the procs drain didn't run.
        "ring_zero_copy_frac": round(ring_zero_copy_frac, 4),
        "bench_parse_processes": bench_procs,
        "platform": platform,
        "n_chips": n_chips,
    }
    if tele_report is not None:
        # The judged e2e run's per-stage self-report (what a training
        # heartbeat would have emitted): ingest_wait_frac + queue depths
        # + parse/stack/H2D/dispatch timing histograms.  Rides into
        # BENCH_r0N.json so every committed bench attributes its own
        # wall-clock.
        result["ingest_wait_frac"] = tele_report["ingest_wait_frac"]
        # Prestacked-cache split of the judged run: fraction of
        # dispatches whose stack was skipped (epoch-0 groups stack once
        # in the pipeline, replays reuse them) and the mean once-per-
        # group stack cost wherever it was paid.
        result["prestack_hit_frac"] = tele_report.get(
            "prestack_hit_frac", 0.0
        )
        result["stack_ms_per_superbatch"] = tele_report.get(
            "stack_ms_per_superbatch", 0.0
        )
        result["telemetry"] = tele_report
    if tiered_section is not None:
        result["tiered_table"] = tiered_section
    if serve_section is not None:
        result["serve"] = serve_section
        if serve_section.get("completed"):
            # Top-level copies of the gated axes: --compare only
            # flattens numeric TOP-LEVEL bench keys (serve_p99_ms low,
            # serve_qps/serve_batch_fill high, serve_steady_compiles
            # low — a nonzero steady compile is the latency cliff).
            for key in ("serve_p50_ms", "serve_p95_ms", "serve_p99_ms",
                        "serve_qps", "serve_batch_fill",
                        "serve_steady_compiles"):
                result[key] = serve_section[key]
    if serve_router_section is not None:
        result["serve_router"] = serve_router_section
        if serve_router_section.get("completed"):
            # Gated axes of the fleet (report.py directions: qps high;
            # p50/p99, the burst's admitted p99, the shed fraction at
            # fixed 4x offered load, and bin decode cost all low).
            for key in ("serve_router_qps", "serve_router_p50_ms",
                        "serve_router_p99_ms", "serve_shed_frac",
                        "serve_burst_p99_ms", "serve_burst_p99_x"):
                result[key] = serve_router_section[key]
            if (
                serve_section is not None
                and serve_section.get("completed")
                and serve_section.get("serve_qps")
            ):
                # The scale-out headline: 2-replica router throughput
                # over the single-process section's, same box, same
                # traffic shape.  Meaningful on multi-core hosts; on a
                # 1-core box both fleets share the core.
                result["serve_router_scaleout_x"] = round(
                    serve_router_section["serve_router_qps"]
                    / serve_section["serve_qps"], 4
                )
    if quant_section is not None:
        result["quantized_table"] = quant_section
        if quant_section.get("completed"):
            # Top-level copies of the gated axes (--compare flattens
            # numeric top-level keys only): table bytes must FALL
            # (that is the feature), step rate must not (encode/decode
            # rides the transfer thread, off the dispatch path).
            for d in ("bf16", "int8"):
                # Dense (serving-format) bytes/row vs fp32 — the
                # replica-density headline (bf16 0.5, int8 ~0.25 at
                # quant_chunk=64).
                result[f"quant_table_bytes_frac_{d}"] = round(
                    quant_section["table_bytes_per_row"][d]
                    / quant_section["table_bytes_per_row"]["fp32"], 4
                )
                result[f"quant_step_rate_frac_{d}"] = (
                    quant_section["step_rate_frac"][d]
                )
    if serve_section is not None and serve_section.get("completed"):
        for key in ("serve_table_mb", "serve_parse_p50_ms",
                    "serve_bin_p50_ms", "serve_quant_error_max_int8",
                    "serve_parse_vec_speedup", "serve_accept_pooled",
                    "serve_accept_pooled_x", "serve_qps_legacy_accept",
                    "serve_http_threads"):
            if key in serve_section:
                result[key] = serve_section[key]
    if fleet_section is not None:
        result["fleet_train"] = fleet_section
        if fleet_section.get("completed"):
            # Top-level copies of the gated axes (--compare flattens
            # numeric top-level keys only): the exchange windows must
            # not grow back, the per-rank byte fractions must hold the
            # ~1/R sharding claim, the sharded step rate is a plain
            # throughput axis.
            result["fleet_exchange_frac"] = (
                fleet_section["exchange_frac_off"]
            )
            result["fleet_exchange_overlap_frac"] = (
                fleet_section["exchange_overlap_frac"]
            )
            result["fleet_shard_bytes_frac"] = (
                fleet_section["shard_bytes_frac"]
            )
            result["fleet_cold_bytes_frac"] = (
                fleet_section["cold_bytes_frac"]
            )
            result["fleet_sharded_examples_per_sec"] = (
                fleet_section["sharded_examples_per_sec"]
            )
            result["fleet_global_examples_per_sec"] = (
                fleet_section["global_examples_per_sec"]
            )
            result["fleet_tier_shards"] = fleet_section["tier_shards"]
    if timeline_regs is not None:
        # Bench preflight (--timeline over BENCH_r*.json): how many
        # keys' trends already crossed their threshold, plus the first
        # few attributions.  0 -> N flags in --compare (direction low).
        result["timeline_regressions"] = timeline_regs
        if timeline_reg_keys:
            result["timeline_regression_keys"] = timeline_reg_keys
    if tier1_audit is not None:
        result["tier1_audit"] = tier1_audit
    if lint_findings_new is not None:
        # Numeric top-level keys flow into --compare automatically;
        # 0 -> N flags as a REGRESSION (direction: low in report.py).
        result["lint_findings_new"] = lint_findings_new
        result["lint_findings_baselined"] = lint_findings_baselined
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
