"""Resource & compile observability: memory ledgers + the compile
sentinel — the fourth pillar of the obs plane.

The telemetry/trace/status layers answer *where wall-clock went*; this
module answers the two questions they are blind to:

1. **Where did the memory go?**  The trainer accretes host allocations
   nobody accounts for — SHM ring slots, staging-pool buffers, the
   epoch cache (raw or prestacked), the tiered cold store, the
   tracer's event buffer — plus the device tables themselves.  The
   component owners register byte gauges into the shared telemetry
   registry (``ingest.ring_bytes``, ``ingest.cache_bytes``,
   ``prefetch.staging_bytes``); Trainer-owned components (the tiered
   cold store, the tracer's buffer) are read directly when the block
   is built — no gauge, one number per scrape.  :func:`read_rss`
   samples process
   RSS / peak-RSS from ``/proc/self/statm`` + ``/proc/self/status``
   (no new deps; ~µs, safe on the heartbeat thread).  Device bytes
   come from the backend's ``memory_stats()`` where it exists (TPU);
   the CPU backend returns None there, so the trainer supplies a
   shape-derived table+optimizer estimate as the fallback.

2. **When did the step recompile, and what does it cost?**
   :class:`CompileSentinel` accounts for every train-step compile the
   trainer's AOT cache performs: wall time (``train.compile`` timer —
   its count IS the compile count), XLA ``cost_analysis()`` /
   ``memory_analysis()`` captured at compile time (FLOPs, bytes
   accessed, output/temp bytes), the compiled step's instruction ->
   scope table (``scopes.py``), and — the alerting signal — a
   ``train.recompiles_unexpected`` counter.  The documented epoch-tail
   K'=leftover compile is whitelisted (provisionally at compile time;
   the trainer confirms an epoch boundary actually follows and
   reclassifies via :meth:`CompileSentinel.reclassify_unexpected` if
   not); any OTHER mid-run recompile (batch-shape drift, sort-meta
   presence flips, a foreign K) is a silent multi-second stall and a
   sign the input stream changed shape under the run, so it warns by
   default and feeds the ``recompiles_unexpected`` alert signal.

Everything here is host-side accounting.  Like the rest of ``obs/``
this module imports neither jax nor numpy: the trainer owns anything
heavier (cost-analysis extraction, device queries) and passes plain
dicts in.  Disabled mode (``resource_metrics = off``) means the
trainer never constructs a sentinel and never builds a ``resource``
block — bit-identical training, the same contract as every prior obs
knob.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from fast_tffm_tpu.obs import scopes

__all__ = ["CompileSentinel", "read_rss", "read_open_fds",
           "basic_block"]

log = logging.getLogger(__name__)

_PAGE = None  # resolved once; sysconf is a syscall-free lookup after that


def _page_size() -> int:
    global _PAGE
    if _PAGE is None:
        import os

        try:
            _PAGE = os.sysconf("SC_PAGE_SIZE")
        except (ValueError, OSError, AttributeError):  # pragma: no cover
            _PAGE = 4096
    return _PAGE


def read_rss() -> tuple:
    """(rss_bytes, peak_rss_bytes) of THIS process, cheaply.

    ``/proc/self/statm`` field 2 is resident pages (one short read, no
    allocation churn — fine at heartbeat cadence); ``VmHWM`` in
    ``/proc/self/status`` is the kernel's high-water mark, which
    catches a transient spike (an epoch-cache fill, a merge) even when
    the sampler never lands on it.  Non-Linux fallback:
    ``resource.getrusage`` (stdlib) serves ``ru_maxrss`` for both.
    Returns (0, 0) only when every source fails.
    """
    rss = peak = 0
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * _page_size()
    except (OSError, ValueError, IndexError):
        pass
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    peak = int(line.split()[1]) * 1024
                    break
    except (OSError, ValueError, IndexError):
        pass
    if not rss or not peak:  # pragma: no cover - non-Linux
        try:
            import resource as _res
            import sys as _sys

            # ru_maxrss units differ by platform: kilobytes on Linux,
            # BYTES on macOS (the one platform that actually reaches
            # this fallback, /proc being absent there).
            scale = 1 if _sys.platform == "darwin" else 1024
            maxrss = _res.getrusage(_res.RUSAGE_SELF).ru_maxrss * scale
            rss = rss or maxrss
            peak = peak or maxrss
        except Exception:
            pass
    return rss, max(rss, peak)


def read_open_fds() -> int:
    """Open file-descriptor count of THIS process (one readdir of
    ``/proc/self/fd``), or -1 where /proc is absent.  The fd-leak
    signal for the socket-heavy serving fleet: a replica leaking one
    socket per kept-alive connection climbs here long before accept()
    starts failing.  Alertable as ``open_fds`` (alias for
    ``resource.open_fds``)."""
    import os

    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:  # pragma: no cover - non-Linux
        return -1


def basic_block(t0: float) -> dict:
    """The process-level slice of the ``resource`` block — RSS, uptime,
    open fds — for hosts without a compile sentinel (serve replicas,
    the router).  The trainer builds its richer block in the dispatch
    loop; key spellings here MUST match it so one alert alias covers
    both planes."""
    rss, peak = read_rss()
    out = {
        "rss_mb": round(rss / (1024 * 1024), 1),
        "peak_rss_mb": round(peak / (1024 * 1024), 1),
        "uptime_s": round(time.time() - t0, 3),
    }
    fds = read_open_fds()
    if fds >= 0:
        out["open_fds"] = fds
    return out


class CompileSentinel:
    """Accounting for train-step compilations.

    The trainer's AOT compile cache calls :meth:`record` once per
    actual compile with the wall time, the super-batch length ``k``,
    its expected/unexpected classification, and the XLA cost/memory
    numbers it extracted.  The sentinel:

    - observes the wall time into a ``train.compile`` telemetry timer
      (count == compiles) and bumps ``train.recompiles_unexpected``
      for flagged ones — both resolved lazily from the registry so a
      per-run ``Telemetry.reset()`` never orphans them;
    - writes a self-describing ``record: compile`` JSONL entry through
      the run's writer (same stream as heartbeats);
    - warns loudly on unexpected recompiles (the default-on alert);
    - keeps the steady-state dispatch's cost numbers (largest ``k``
      seen) for the ``resource`` block's throughput attribution.

    Thread-safe: compiles happen on the dispatch loop but snapshots
    run on heartbeat/status threads.
    """

    def __init__(self, telemetry=None, expected_k: int = 1):
        from fast_tffm_tpu.obs import telemetry as telemetry_mod

        self._tel = telemetry if telemetry is not None else telemetry_mod.NULL
        self._lock = threading.Lock()
        self._writer = None
        self.expected_k = int(expected_k)
        self.compiles = 0
        self.compile_s = 0.0
        self.unexpected = 0
        self._cost: dict = {}  # steady-state dispatch cost (largest k)
        self._cost_k = 0

    def set_writer(self, writer) -> None:
        """Attach the run's JsonlWriter (train() owns its lifetime)."""
        self._writer = writer

    def reset(self) -> None:
        """Per-run accounting (mirrors Telemetry.reset): a second
        train() on a warm Trainer reports ITS compiles — usually zero,
        because the AOT cache it feeds from is instance-lived."""
        with self._lock:
            self.compiles = 0
            self.compile_s = 0.0
            self.unexpected = 0
            self._writer = None
            # The cost of the cached steady-state executable still
            # describes what run 2 dispatches; keep it.

    def record(self, wall_s: float, k: int, expected: bool,
               cost: Optional[dict] = None, step: int = 0,
               compiled=None) -> None:
        """Account one actual compile (cache misses only).  Where the
        run writes records, ``compiled`` (the executable) gives the
        ``record: compile`` entry its instruction -> scope table
        (``obs.scopes.record_of``: ``scopes``, ``moved``, ``mixed``,
        ``containers`` and ``scope_table_s``, or ``scope_table_error``)."""
        self._tel.timer("train.compile").observe(wall_s)
        cost = cost or {}
        with self._lock:
            self.compiles += 1
            self.compile_s += wall_s
            if not expected:
                self.unexpected += 1
            if cost and k >= self._cost_k:
                self._cost = dict(cost)
                self._cost_k = k
            writer = self._writer
        if not expected:
            self._tel.counter("train.recompiles_unexpected").add()
            log.warning(
                "UNEXPECTED train-step recompile at step %d (k=%d, "
                "%.2fs): the input stream changed shape mid-run "
                "(batch/max_features drift, sort-meta flip, or a "
                "foreign K) — only the documented epoch-tail "
                "K' < steps_per_dispatch compile is whitelisted",
                step, k, wall_s,
            )
        if writer is not None:
            rec = {
                "record": "compile",
                "time": time.time(),
                "step": step,
                "k": k,
                "compile_s": round(wall_s, 4),
                "expected": bool(expected),
            }
            rec.update(cost)
            if compiled is not None:
                rec.update(scopes.record_of(compiled))
            try:
                writer.write(rec)
            except Exception as e:  # noqa: BLE001 - never kill a compile
                log.warning("compile record write failed: %s", e)

    def reclassify_unexpected(self, k: int, step: int = 0) -> None:
        """Retroactive flag for a short-k compile that was provisionally
        whitelisted as an epoch tail but turned out not to be one (the
        trainer saw another super-batch follow it instead of an epoch
        boundary).  Same counter + warn as an immediate flag; the
        original ``record: compile`` entry stays (its wall time was
        real), only the classification moves."""
        with self._lock:
            self.unexpected += 1
        self._tel.counter("train.recompiles_unexpected").add()
        log.warning(
            "UNEXPECTED train-step recompile at step %d (k=%d): a "
            "short super-batch compiled as a presumed epoch-tail K' "
            "but was NOT followed by an epoch boundary — the input "
            "stream is emitting short super-batches mid-epoch",
            step, k,
        )

    def snapshot(self) -> dict:
        """Compile-side half of the ``resource`` block (flat, numeric
        — safe from any thread, renders straight into Prometheus)."""
        with self._lock:
            out = {
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "recompiles_unexpected": self.unexpected,
            }
            cost = dict(self._cost)
        flops = cost.get("flops", 0.0)
        bytes_acc = cost.get("bytes_accessed", 0.0)
        if flops:
            out["flops_per_dispatch"] = flops
        if bytes_acc:
            out["bytes_per_dispatch"] = bytes_acc
            if flops:
                out["arithmetic_intensity"] = round(flops / bytes_acc, 3)
        for key in ("output_bytes", "temp_bytes"):
            if cost.get(key):
                out[key] = cost[key]
        return out
