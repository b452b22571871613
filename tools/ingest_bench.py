#!/usr/bin/env python
"""Ingest-component microbench: scan vs parse vs pipeline, threads x rate.

Measures the three stages of the fast-ingest path separately so the
bottleneck is visible (SURVEY.md §7 hard-part 2: the parser must feed the
chips):

  scan      — _iter_raw_windows: chunked reads + ONE C++ memchr pass
  parse     — NativeParser.parse_raw over pre-scanned groups, 1 C++ thread
  pipeline  — BatchPipeline end-to-end drain (reader + N parse workers +
              shuffle), the rate training actually sees

Pipeline-stage records come from the pipeline's OWN telemetry snapshot
(obs.Telemetry) rather than bench-local stopwatches: delivered-example
counts exclude tail-batch padding, and each record carries the stage
attribution a training heartbeat would report (parse total/percentiles,
reader-block, worker delivery-block).

Prints a JSON line per measurement; run with no args on any machine.
Results are committed to INGEST.md with the host's core count — rates
scale with cores since parse workers are independent.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import sys
import tempfile
import time
import shutil

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

BATCH, NFEAT, VOCAB = 4096, 39, 1 << 20


def drain_with_telemetry(pipe, tel) -> dict:
    """Drain a BatchPipeline and report from ITS telemetry snapshot:
    the examples counter (real lines, padding excluded) gives the rate;
    the parse/reader_block/out_block timers attribute the drain's time
    the same way a training run's heartbeat would."""
    t0 = time.perf_counter()
    for _b in pipe:
        pass
    dt = max(time.perf_counter() - t0, 1e-9)
    snap = tel.snapshot()
    timers = snap.get("timers", {})

    def t(name, key):
        return timers.get(name, {}).get(key, 0.0)

    counters = snap.get("counters", {})
    out = {
        "lines_per_sec": round(counters["ingest.examples"] / dt),
        "batches": counters["ingest.batches"],
        "parse_total_s": t("ingest.parse", "total_s"),
        "parse_p50_ms": t("ingest.parse", "p50_ms"),
        "parse_p95_ms": t("ingest.parse", "p95_ms"),
        "reader_block_s": t("ingest.reader_block", "total_s"),
        "worker_out_block_s": t("ingest.out_block", "total_s"),
    }
    # SHM-ring split (parse_processes with ring_slots > 0): how many raw
    # windows went zero-copy vs pickled, and the descriptor bytes that
    # actually crossed the worker queue.
    ring = counters.get("ingest.ring_windows", 0)
    fallback = counters.get("ingest.ring_fallback_windows", 0)
    if ring or fallback:
        out["ring_zero_copy_frac"] = round(ring / (ring + fallback), 4)
        out["ring_window_mb"] = round(
            counters.get("ingest.ring_window_bytes", 0) / 1e6, 2
        )
        out["queue_msg_kb"] = round(
            counters.get("ingest.work_msg_bytes", 0) / 1e3, 2
        )
    # Prestacked-cache split: once-per-group stack cost at the source.
    ps = snap.get("timers", {}).get("ingest.prestack", {})
    if ps.get("count"):
        out["prestack_superbatches"] = ps["count"]
        out["stack_ms_per_superbatch"] = round(
            1e3 * ps["total_s"] / ps["count"], 3
        )
    return out


def _proc_worker(files, epochs, ready, go, out):
    """One ingest process: full BatchPipeline drain over its file shard.

    Same structure as multi-host input sharding (parallel.mesh strided
    file assignment): each process owns disjoint files, runs its own
    reader + parser threads, and shares nothing.  A warmup drain loads
    the native lib and the page cache; the barrier (ready/go events)
    keeps process startup out of the timed region.
    """
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.pipeline import BatchPipeline

    try:
        cfg = FmConfig(
            vocabulary_size=VOCAB, factor_num=8, max_features=NFEAT,
            batch_size=BATCH, thread_num=1, queue_size=8,
        )
        n_warm = 0
        for _b in BatchPipeline(files, cfg, epochs=1, shuffle=False):
            n_warm += 1
            if n_warm >= 2:
                break
        ready.set()
        go.wait()
        t0 = time.perf_counter()
        n = 0
        for _b in BatchPipeline(files, cfg, epochs=epochs, shuffle=True):
            n += BATCH
        out.put((n, time.perf_counter() - t0))
    except BaseException as e:  # noqa: BLE001 - surface in the parent
        ready.set()  # never leave the parent stuck on the barrier
        out.put(("error", f"{type(e).__name__}: {e}"))


def bench_procs(files, n_procs: int, epochs: int = 2):
    """Aggregate lines/s of n_procs independent ingest processes.

    Returns (aggregate_rate, slowest_proc_seconds).  Aggregate is total
    lines over the slowest process's drain time — the rate a training
    fleet would actually see, since the step waits for every host.
    """
    ctx = mp.get_context("spawn")
    shards = [files[i::n_procs] for i in range(n_procs)]
    out = ctx.Queue()
    ready = [ctx.Event() for _ in range(n_procs)]
    go = ctx.Event()
    procs = [
        ctx.Process(target=_proc_worker, args=(s, epochs, r, go, out))
        for s, r in zip(shards, ready)
    ]
    for p in procs:
        p.start()
    for r, p in zip(ready, procs):
        # A worker that dies before the barrier must not hang the bench.
        while not r.wait(timeout=1.0):
            if not p.is_alive():
                go.set()
                raise RuntimeError(
                    f"ingest worker died before ready (exit {p.exitcode})"
                )
    go.set()
    results = []
    for p in procs:
        try:
            results.append(out.get(timeout=300))
        except Exception:
            raise RuntimeError("ingest worker produced no result") from None
    for p in procs:
        p.join()
    errors = [r for r in results if r[0] == "error"]
    if errors:
        raise RuntimeError(f"ingest workers failed: {errors}")
    total = sum(n for n, _ in results)
    slowest = max(dt for _, dt in results)
    return total / slowest, slowest


def main() -> int:
    from fast_tffm_tpu.data.synth import gen_libsvm_files
    from fast_tffm_tpu import obs
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data import native as native_lib
    from fast_tffm_tpu.data.pipeline import BatchPipeline, _iter_raw_groups

    tmpdir = tempfile.mkdtemp(prefix="ingest_bench_")
    try:
        rng = np.random.default_rng(7)
        files = gen_libsvm_files(tmpdir, rng, 4, 8 * BATCH, NFEAT, VOCAB)
        total = 4 * 8 * BATCH
        size = sum(os.path.getsize(f) for f in files)
        print(json.dumps({
            "setup": {"lines": total, "mb": round(size / 1e6, 1),
                      "cpus": os.cpu_count(), "batch": BATCH,
                      "features": NFEAT},
        }))

        def emit(stage, rate, **kw):
            print(json.dumps({
                "stage": stage, "lines_per_sec": round(rate), **kw
            }))

        for _ in range(2):  # second pass = warm page cache
            t0 = time.perf_counter()
            n = 0
            for _, starts, _e in _iter_raw_groups(files, BATCH):
                n += len(starts)
            scan = n / (time.perf_counter() - t0)
        emit("scan", scan)

        groups = list(_iter_raw_groups(files, BATCH))
        for nt in (1, 2, 4):
            p = native_lib.NativeParser(VOCAB, NFEAT, False, 0, nt)
            p.parse_raw(*groups[0], BATCH)
            t0 = time.perf_counter()
            for g in groups:
                p.parse_raw(*g, BATCH)
            emit("parse", total / (time.perf_counter() - t0),
                 internal_threads=nt)

        # sort_meta: the host-side sparse-apply prep that rides the same
        # worker threads when host_sort engages (single-process tile).
        from fast_tffm_tpu.ops import sparse_apply

        ids = rng.integers(0, VOCAB, (BATCH * NFEAT,)).astype(np.int32)
        native_lib.sort_meta(
            ids, VOCAB, sparse_apply.CHUNK, sparse_apply.TILE
        )
        t0 = time.perf_counter()
        reps = 10
        for _ in range(reps):
            native_lib.sort_meta(
                ids, VOCAB, sparse_apply.CHUNK, sparse_apply.TILE
            )
        emit("sort_meta", reps * BATCH * NFEAT / (time.perf_counter() - t0),
             note="feature occurrences/sec, one core")

        for tn in (1, 2, 4, 8):
            for ordered in (False, True):
                cfg = FmConfig(
                    vocabulary_size=VOCAB, factor_num=8, max_features=NFEAT,
                    batch_size=BATCH, thread_num=tn, queue_size=8,
                )
                tel = obs.Telemetry()
                pipe = BatchPipeline(
                    files, cfg, epochs=2, shuffle=not ordered,
                    ordered=ordered, telemetry=tel,
                )
                stats = drain_with_telemetry(pipe, tel)
                emit("pipeline", stats.pop("lines_per_sec"),
                     thread_num=tn, ordered=ordered, **stats)

        # Process-parallel ingest: N fully independent reader+parser
        # processes over disjoint file shards (the multi-host input-
        # sharding structure).  On a multi-core host this demonstrates
        # the claimed aggregate scaling; on a 1-core host it documents
        # the hardware ceiling (processes time-slice one core).
        for np_ in (1, 2, 4):
            if np_ > len(files):
                continue
            rate, slowest = bench_procs(files, np_)
            emit("procs", rate, n_procs=np_,
                 per_proc=round(rate / np_),
                 slowest_s=round(slowest, 2),
                 cores=os.cpu_count())

        # In-pipeline process POOL (parse_processes, PR 2): unlike the
        # independent-shard "procs" stage above, this is ONE pipeline —
        # one reader, N spawned parse workers, parsed batches returning
        # over shared memory as a single trainable stream.  The rate the
        # trainer sees when the GIL (or the Python parse fallback) is
        # the bottleneck.  ring_slots toggles the INBOUND direction:
        # 0 pickles every raw window through the worker queue, >0 writes
        # windows into the SHM ring and ships descriptors only — the
        # threads-vs-procs drain comparison re-run on the ring.
        for np_ in (1, 2, 4):
            for slots in (0, 4):
                cfg = FmConfig(
                    vocabulary_size=VOCAB, factor_num=8,
                    max_features=NFEAT, batch_size=BATCH, queue_size=8,
                    parse_processes=np_, ring_slots=slots,
                )
                tel = obs.Telemetry()
                pipe = BatchPipeline(
                    files, cfg, epochs=1, shuffle=True, telemetry=tel
                )
                stats = drain_with_telemetry(pipe, tel)
                emit("pipeline-procpool", stats.pop("lines_per_sec"),
                     parse_processes=np_, ring_slots=slots,
                     cores=os.cpu_count(), **stats)

        # Pre-stacked epoch cache (cache_prestacked): epoch 0 parses and
        # stacks [K, ...] groups once; epoch 1 replays whole super-
        # batches.  The two epochs are timed SEPARATELY at the in-band
        # EpochEnd marker — the replay-epoch rate is what the trainer's
        # transfer stage sees with its stack skipped; averaging in the
        # epoch-0 parse would overstate it.
        from fast_tffm_tpu.data.pipeline import EpochEnd, SuperBatch

        cfg = FmConfig(
            vocabulary_size=VOCAB, factor_num=8, max_features=NFEAT,
            batch_size=BATCH, thread_num=2, queue_size=8,
            cache_epochs=True, cache_prestacked=True,
            steps_per_dispatch=8,
        )
        tel = obs.Telemetry()
        pipe = BatchPipeline(
            files, cfg, epochs=2, shuffle=True, ordered=True,
            cache_epochs=True, cache_max_bytes=4 << 30, prestack_k=8,
            epoch_marks=True, telemetry=tel,
        )
        t0 = time.perf_counter()
        t_mark = None
        n0 = n1 = 0
        for b in pipe:
            if isinstance(b, EpochEnd):
                if b.epoch == 0:
                    t_mark = time.perf_counter()
                continue
            n = int(np.count_nonzero(b.batch.weights > 0)) if isinstance(
                b, SuperBatch) else int(np.count_nonzero(b.weights > 0))
            if t_mark is None:
                n0 += n
            else:
                n1 += n
        t_end = time.perf_counter()
        ps = tel.snapshot().get("timers", {}).get("ingest.prestack", {})
        emit("pipeline-prestack",
             n1 / max(t_end - t_mark, 1e-9),
             note="cached REPLAY epoch only (epoch-0 parse excluded)",
             epoch0_lines_per_sec=round(n0 / max(t_mark - t0, 1e-9)),
             steps_per_dispatch=8,
             prestack_superbatches=ps.get("count", 0),
             stack_ms_per_superbatch=round(
                 1e3 * ps.get("total_s", 0.0) / max(ps.get("count", 1), 1),
                 3,
             ))

        # Pipeline with per-batch sort_meta on the workers: what the
        # training path actually runs when host_sort engages.
        for tn in (4, 8):
            cfg = FmConfig(
                vocabulary_size=VOCAB, factor_num=8, max_features=NFEAT,
                batch_size=BATCH, thread_num=tn, queue_size=8,
            )
            tel = obs.Telemetry()
            pipe = BatchPipeline(
                files, cfg, epochs=2, shuffle=True,
                sort_meta_spec=(
                    VOCAB, sparse_apply.CHUNK, sparse_apply.TILE
                ),
                telemetry=tel,
            )
            stats = drain_with_telemetry(pipe, tel)
            emit("pipeline+meta", stats.pop("lines_per_sec"),
                 thread_num=tn, **stats)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
