"""Multiprocess parse workers: GIL-free ingest parsing.

``parse_processes > 0`` moves batch parsing out of the trainer process
into a pool of SPAWNED workers (never forked: a fork would inherit JAX's
runtime threads and held locks; a spawned child imports only numpy + the
data layer).  This is the rebuild's answer to the reference's free-running
C++ ``FmParser`` threads: the pure-Python parse fallback is GIL-bound no
matter what ``thread_num`` says, and even the ctypes path serializes its
Python-side batch assembly — worker processes sidestep both.

Both directions of the worker queue are shared-memory backed:

- INBOUND (:class:`ShmRing`): the reader writes each raw window's bytes
  (text + line offsets) straight into a slot of one fixed ring segment;
  only a slot DESCRIPTOR (slot id, lengths, group sizes — a few hundred
  bytes) crosses the work queue, and workers parse in place from the
  mapped slot.  The previous design pickled every window's multi-MB
  byte buffer through the queue.  Workers return the slot id on a free
  queue once the window is fully parsed; a window that outgrows the
  slot capacity falls back to the pickled path (counted, never wrong).
- OUTBOUND (``ship_batch``/``attach_batch``): the worker lays the
  parsed batch's contiguous numpy arrays (and, when host sort prep is
  on, the sort_meta arrays — all shapes are static given the config)
  into ONE per-batch segment and ships just the segment name over the
  result queue.  The parent maps the segment and wraps zero-copy views,
  so the only post-parse copy is the super-batch stacking.

Every segment a pipeline creates (the ring and all shipped batches)
carries the pipeline's unique ``shm_tag`` name prefix, so teardown can
sweep ``/dev/shm`` for stragglers — a worker killed between creating a
segment and shipping its name can no longer leak it.

Segment lifecycle (Python 3.10: no ``track=False``):

- the worker creates the segment, UNREGISTERS it from its resource
  tracker (the segment must outlive the worker's queue turnover), writes,
  and closes its own mapping;
- the parent attaches, immediately ``unlink()``\\ s (the name disappears;
  pages persist while mapped) and adopts the raw mmap out of the wrapper
  (``_adopt_mapping``) — the views' .base chain then owns the mapping,
  so the kernel reclaims the pages when the last view dies.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import pickle
import queue as _queue
import time
from multiprocessing import resource_tracker, shared_memory
from typing import Optional

import numpy as np

from fast_tffm_tpu.data.libsvm import Batch, SortMeta

_SHM_DIR = "/dev/shm"
_pipe_ids = itertools.count()
_ship_ids = itertools.count()

# Quality-sketch shipping cadence (WorkerSpec.sketch_every's one
# production value): a serialized SketchSet delta is a few KB, so one
# per batch would undo the ring's descriptor-only queue discipline;
# one per this-many batches amortizes it to noise while keeping the
# parent's windows at most this many batches stale per worker.
SKETCH_SHIP_EVERY = 16


def make_shm_tag() -> str:
    """Unique per-pipeline prefix for every segment the pipeline (or its
    workers) creates — the handle :func:`sweep_segments` cleans up by.
    The trailing delimiter matters: without it, pipeline p1's teardown
    sweep would prefix-match pipeline p10's live segments."""
    return f"tffm{os.getpid()}p{next(_pipe_ids)}_"


@dataclasses.dataclass(frozen=True)
class WorkerSpec:
    """Everything a spawned worker needs to parse (picklable; no FmConfig
    so children never import jax-adjacent modules)."""

    vocabulary_size: int
    max_features: int
    hash_feature_id: bool
    field_num: int
    batch_size: int
    sort_meta_spec: Optional[tuple]  # (vocab, chunk, tile) or None
    shm_tag: str = "tffm0p0"  # name prefix for all segments of this run
    ring_name: Optional[str] = None  # inbound ShmRing segment (None = off)
    ring_slots: int = 0
    ring_slot_bytes: int = 0
    # Record per-batch/per-window trace spans (obs.Tracer) in the worker
    # and ship them back with each result message; the parent merges
    # them into the run's trace file under this worker's pid lane.
    trace: bool = False
    # Model-quality drift sketches (obs/sketch.py): > 0 means sketch
    # every parsed batch's feature values / lengths / id occupancy into
    # a worker-local SketchSet and ship the serialized DELTA back every
    # this-many batches (reset after each ship; the final remainder
    # rides the trailing "done" message) — the parent merges deltas
    # into the run's StreamSketch, the same channel discipline as the
    # shipped parse timings.  0 = off (no per-batch sketch work).
    sketch_every: int = 0


_CORE = ("labels", "ids", "vals", "fields", "weights")
_META = ("perm", "upos", "lrow_last", "starts", "firsts", "ends",
         "tile_start")


def _layout(spec: WorkerSpec):
    """[(name, shape, dtype)] for the core batch and the sort_meta tail.

    Every shape is static given the spec — n_pad/n_chunks/n_tiles mirror
    native.sort_meta's padding math — so writer and reader agree on the
    segment layout without shipping shapes per batch.
    """
    b, f = spec.batch_size, spec.max_features
    core = [
        ("labels", (b,), np.float32),
        ("ids", (b, f), np.int32),
        ("vals", (b, f), np.float32),
        ("fields", (b, f), np.int32),
        ("weights", (b,), np.float32),
    ]
    meta: list = []
    if spec.sort_meta_spec is not None:
        vocab, chunk, tile = spec.sort_meta_spec
        n = b * f
        n_pad = -(-n // chunk) * chunk
        n_chunks = n_pad // chunk
        n_tiles = vocab // tile
        meta = [
            ("perm", (n_pad,), np.int32),
            ("upos", (n_pad,), np.int32),
            ("lrow_last", (n_pad,), np.float32),
            ("starts", (n_chunks,), np.int32),
            ("firsts", (n_chunks + 1,), np.int32),
            ("ends", (n_chunks,), np.int32),
            ("tile_start", (n_tiles + 1,), np.int32),
        ]
    return core, meta


def _nbytes(fields) -> int:
    return sum(
        int(np.prod(shape)) * np.dtype(dt).itemsize for _, shape, dt in fields
    )


def _pad8(n: int) -> int:
    return (n + 7) & ~7


def _untrack(shm: shared_memory.SharedMemory) -> None:
    """Remove this process's resource-tracker registration for a segment
    whose lifetime someone else owns (the tracker would otherwise unlink
    it when THIS process exits, yanking pages from live users)."""
    try:
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:  # pragma: no cover - tracker impl drift
        pass


class ShmRing:
    """Inbound shared-memory ring: raw windows, parent → workers.

    One fixed segment of ``slots`` × ``slot_bytes``.  The parent writes a
    window (text bytes, then the 8-aligned int64 starts/ends offset
    arrays) into a free slot and ships only the slot descriptor; workers
    map the same segment at startup, parse straight out of the slot, and
    return the slot id on a free queue.  Free-slot flow control IS the
    ring's backpressure — the reader blocks on the free queue when every
    slot is in flight.

    The creating (parent) process keeps its resource-tracker
    registration while the ring lives, so a hard-killed parent still
    gets the segment unlinked at tracker exit; :meth:`destroy` is the
    clean path (unlink + unregister, idempotent).  Workers attach with
    :meth:`attach` and drop their own tracker registration — the parent
    owns cleanup.
    """

    def __init__(self, shm: shared_memory.SharedMemory, slots: int,
                 slot_bytes: int):
        self._shm = shm
        self.name = shm.name
        self.slots = slots
        self.slot_bytes = slot_bytes

    @classmethod
    def create(cls, tag: str, slots: int, slot_bytes: int) -> "ShmRing":
        shm = shared_memory.SharedMemory(
            create=True, size=max(1, slots * slot_bytes),
            name=f"{tag}ring",
        )
        return cls(shm, slots, slot_bytes)

    @classmethod
    def attach(cls, name: str, slots: int, slot_bytes: int) -> "ShmRing":
        shm = shared_memory.SharedMemory(name=name)
        # No unregister here: spawned workers SHARE the parent's
        # resource-tracker process (the fd rides the spawn handshake)
        # and its cache is a set — an attach's duplicate registration
        # collapses into the parent's entry, so a worker-side
        # unregister would steal that entry and the parent's final
        # unlink would log a tracker KeyError.  The duplicate register
        # is harmless; the entry dies with the parent's unlink.
        return cls(shm, slots, slot_bytes)

    def write(self, slot: int, text, starts: np.ndarray,
              ends: np.ndarray) -> int:
        """Lay one window into ``slot``; returns bytes written.  Layout:
        ``[text][pad to 8][starts int64 x n][ends int64 x n]``."""
        base = slot * self.slot_bytes
        mv = self._shm.buf
        tl = len(text)
        mv[base:base + tl] = text
        off = base + _pad8(tl)
        n = len(starts)
        dst = np.frombuffer(mv, np.int64, count=2 * n, offset=off)
        dst[:n] = starts
        dst[n:] = ends
        del dst  # drop the buffer export before any close()
        return _pad8(tl) + 16 * n

    def read(self, slot: int, text_len: int, n: int):
        """(text_memoryview, starts, ends) zero-copy views of a slot."""
        base = slot * self.slot_bytes
        text = memoryview(self._shm.buf)[base:base + text_len]
        off = base + _pad8(text_len)
        arr = np.frombuffer(self._shm.buf, np.int64, count=2 * n,
                            offset=off)
        return text, arr[:n], arr[n:]

    @staticmethod
    def need_bytes(text_len: int, n_lines: int) -> int:
        return _pad8(text_len) + 16 * n_lines

    def close(self) -> None:
        try:
            self._shm.close()
        except BufferError:  # pragma: no cover - a view still exported
            pass

    def destroy(self) -> None:
        """Parent-side teardown (idempotent): unlink — which also drops
        this process's tracker registration — and close the mapping.  A
        name already gone (swept externally) still needs the tracker
        registration cleared or exit-time cleanup warns about it."""
        try:
            self._shm.unlink()
        except FileNotFoundError:
            _untrack(self._shm)
        self.close()


def sweep_segments(tag: str) -> int:
    """Unlink every /dev/shm segment carrying ``tag`` — the teardown
    backstop for segments a crashed worker created but never shipped
    (and for the ring, had destroy() not run).  Only called after the
    worker pool is reaped, so nothing tagged is still in use.  Returns
    the number of segments removed."""
    removed = 0
    try:
        names = os.listdir(_SHM_DIR)
    except OSError:  # pragma: no cover - non-Linux /dev/shm layout
        return 0
    for name in names:
        if name.startswith(tag):
            try:
                os.unlink(os.path.join(_SHM_DIR, name))
                removed += 1
            except OSError:  # pragma: no cover - raced another cleaner
                pass
    return removed


def ship_batch(spec: WorkerSpec, batch: Batch, has_meta: bool) -> str:
    """Worker side: copy one parsed batch into a fresh segment; returns
    its name.  The worker's tracker registration is removed — the PARENT
    owns cleanup (it unlinks on attach, or discard_segment on teardown).
    Segments carry the run's shm_tag so a crashed worker's orphans are
    still findable by the parent's teardown sweep."""
    core, meta = _layout(spec)
    fields = core + (meta if has_meta else [])
    size = max(1, _nbytes(fields))
    while True:
        name = f"{spec.shm_tag}o{os.getpid()}x{next(_ship_ids)}"
        try:
            shm = shared_memory.SharedMemory(
                create=True, size=size, name=name
            )
            break
        except FileExistsError:  # pragma: no cover - counter collision
            continue
    _untrack(shm)
    off = 0
    values = {name: getattr(batch, name) for name in _CORE}
    if has_meta:
        values.update(
            {name: getattr(batch.sort_meta, name) for name in _META}
        )
    for name, shape, dt in fields:
        count = int(np.prod(shape))
        dst = np.frombuffer(shm.buf, dt, count=count, offset=off)
        dst[:] = np.ascontiguousarray(values[name], dt).reshape(-1)
        del dst
        off += count * np.dtype(dt).itemsize
    name = shm.name
    shm.close()
    return name


def _adopt_mapping(shm: shared_memory.SharedMemory):
    """Take ownership of the wrapper's mmap and neutralize the wrapper.

    Binding the numpy views straight to the ``mmap`` object makes the
    mapping's lifetime exactly the views' lifetime: the views hold the
    mmap alive through their .base chain, and when the last one dies the
    mmap deallocates (its buffer exports are gone by definition) and the
    kernel reclaims the pages.  The SharedMemory wrapper cannot be left
    to do this — its ``__del__`` calls ``close()``, which raises
    BufferError while views still export the buffer — so its fd is
    closed here (the mapping survives an fd close) and its fields are
    cleared to make that ``__del__`` a no-op.
    """
    mm = shm._mmap
    try:
        shm._buf.release()  # never exported: views come from mm below
    except Exception:  # pragma: no cover - buf impl drift
        pass
    try:
        os.close(shm._fd)
    except OSError:  # pragma: no cover - already closed
        pass
    shm._buf = None
    shm._mmap = None
    shm._fd = -1
    return mm


def attach_batch(spec: WorkerSpec, name: str, has_meta: bool) -> Batch:
    """Parent side: map a shipped segment into zero-copy Batch views.

    The segment is unlinked immediately (pages persist while mapped);
    the mapping frees when the last field view is garbage collected, so
    cached batches keep their pages exactly as long as the cache lives.
    """
    core, meta = _layout(spec)
    fields = core + (meta if has_meta else [])
    shm = shared_memory.SharedMemory(name=name)
    try:
        shm.unlink()
    except FileNotFoundError:  # pragma: no cover - double-teardown race
        pass
    flat = np.frombuffer(_adopt_mapping(shm), np.uint8)
    out = {}
    off = 0
    for name_, shape, dt in fields:
        count = int(np.prod(shape))
        nb = count * np.dtype(dt).itemsize
        out[name_] = flat[off:off + nb].view(dt).reshape(shape)
        off += nb
    sort_meta = (
        SortMeta(*(out[n] for n in _META)) if has_meta else None
    )
    return Batch(*(out[n] for n in _CORE), sort_meta=sort_meta)


def discard_segment(name: str) -> None:
    """Teardown path: unlink a shipped segment that will never be
    attached (its worker already unregistered it from the tracker)."""
    try:
        shm = shared_memory.SharedMemory(name=name)
    except FileNotFoundError:
        return
    try:
        shm.unlink()
    except FileNotFoundError:  # pragma: no cover
        pass
    shm.close()


def put_with_stop(q, item, stop) -> bool:
    """Bounded mp-queue put that gives up once ``stop`` is set — the
    process-pool analogue of ``_ClosableQueue.put`` (an mp.Queue cannot
    be cancelled, so the poll period bounds shutdown latency instead).
    Shared by the pipeline's reader thread and the workers."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except _queue.Full:
            continue
    return False


def get_with_stop(q, stop):
    """Blocking mp-queue get that gives up (returns None) once ``stop``
    is set — used by the reader waiting for a free ring slot."""
    while not stop.is_set():
        try:
            return q.get(timeout=0.1)
        except _queue.Empty:
            continue
    return None


def _safe_exc(e: BaseException) -> BaseException:
    """An exception guaranteed to survive the result queue's pickling
    (an unpicklable error would be dropped by the feeder thread and the
    failure would vanish)."""
    try:
        pickle.loads(pickle.dumps(e))
        return e
    except Exception:
        return RuntimeError(f"{type(e).__name__}: {e}")


def _build_parser(spec: WorkerSpec):
    """(parse_lines_fn, parse_raw_fn, trunc_fn) for this worker: the
    same C++ parser the parent uses (a failed load raises here and
    surfaces as an ("err", ...) result — never a quiet switch to the
    Python oracle, which could disagree bit-for-bit on edge tokens)."""
    from fast_tffm_tpu.data import native

    native_parser = native.NativeParser(
        vocabulary_size=spec.vocabulary_size,
        max_features=spec.max_features,
        hash_feature_id=spec.hash_feature_id,
        field_num=spec.field_num,
        num_threads=1,
    )

    def parse_lines(lines, weights):
        return native_parser.parse_batch(lines, spec.batch_size, weights)

    def parse_raw(buf, starts, ends):
        return native_parser.parse_raw(buf, starts, ends, spec.batch_size)

    def trunc():
        return native_parser.truncated_features

    return parse_lines, parse_raw, trunc


def parse_worker_main(spec: WorkerSpec, work, out, stop,
                      ring_free=None) -> None:
    """Entry point of one spawned parse worker.

    Work messages (from the pipeline's reader thread):
      ("rawslot", seq0, slot, text_len, [n_lines...]) — one raw WINDOW
          already resident in the shared-memory ring (spec.ring_name):
          the descriptor names the slot and the per-group line counts;
          the worker parses IN PLACE from the mapped slot and returns
          the slot id on ``ring_free`` when the window is done — no
          window bytes ever cross the queue;
      ("raw",   seq0, buf, [starts...], [ends...])  — pickled-window
          fallback (ring off, or a window larger than a ring slot);
      ("lines", seq, lines, weights)                — one line-path chunk;
      ("mark",  seq, epoch)                         — epoch marker, echoed;
      None                                          — shutdown sentinel.

    Result messages:
      ("batch", seq, shm_name, has_meta, trunc_delta, note, parse_s,
       spans, sketch_delta)
      ("mark", seq, epoch) | ("err", exc) | ("done", spans,
       sketch_delta)

    ``parse_s`` is this batch's parse+prep wall time in the worker — a
    spawned process cannot write to the parent's telemetry registry, so
    the duration rides the result message and the parent observes it
    into the shared ``ingest.parse`` timer.  ``spans`` works the same
    way for the trace layer: with ``spec.trace`` the worker records
    Chrome-trace events (``parse.batch`` per batch, ``parse.window`` per
    ring window — its end marks the slot release) into a local
    obs.Tracer and ships the accumulated raw events with each result;
    the parent merges them into the run's trace under this worker's pid.
    ``sketch_delta`` (``spec.sketch_every > 0``) is the quality plane's
    version of the same contract: the worker folds each parsed batch
    into a local ``obs.sketch.SketchSet`` and ships the serialized
    delta every ``sketch_every`` batches (None in between; the sketch
    resets at each ship so the parent absorbs every delta exactly
    once).  The trailing ``("done", spans, sketch_delta)`` flushes
    spans that ended after the last batch shipped (the final window
    span) and the sketch remainder.
    """
    parse_lines, parse_raw, trunc = _build_parser(spec)
    from fast_tffm_tpu.obs.trace import Tracer

    tracer = Tracer(
        enabled=spec.trace, process_name=f"parse-worker {os.getpid()}"
    )
    sketch = None
    sketch_pending = 0
    if spec.sketch_every > 0:
        from fast_tffm_tpu.obs.sketch import SketchSet

        sketch = SketchSet()
    meta_spec = spec.sort_meta_spec
    ring = None
    if spec.ring_name is not None:
        ring = ShmRing.attach(
            spec.ring_name, spec.ring_slots, spec.ring_slot_bytes
        )

    def put(msg) -> bool:
        return put_with_stop(out, msg, stop)

    def emit(batch: Batch, seq: int, trunc_delta: int,
             parse_s: float) -> bool:
        nonlocal meta_spec, sketch, sketch_pending
        note = None
        has_meta = False
        if meta_spec is not None:
            from fast_tffm_tpu.data import native

            t0 = time.perf_counter()
            try:
                batch = batch._replace(
                    sort_meta=native.sort_meta(batch.ids, *meta_spec)
                )
                has_meta = True
            except native.OutOfRangeIdsError as e:
                note = ("oor", str(e))  # parent warns per bad batch
            except Exception as e:
                meta_spec = None  # this worker degrades for good
                note = ("meta_failed", f"{type(e).__name__}: {e}")
            # sort prep is parse-stage work; fold it into the shipped time
            parse_s += time.perf_counter() - t0
        delta = None
        if sketch is not None:
            # Guarded like the thread path: sketching is observe-only,
            # so a failure degrades this worker's quality feed (note
            # shipped once; the parent warns) — it must never become
            # an ("err", ...) that kills the run.
            try:
                sketch.update_batch(
                    batch.ids, batch.vals, batch.weights
                )
                sketch_pending += 1
                if sketch_pending >= spec.sketch_every:
                    from fast_tffm_tpu.obs.sketch import SketchSet

                    delta = sketch.to_dict()
                    sketch = SketchSet()
                    sketch_pending = 0
            except Exception as e:  # noqa: BLE001 - observe only
                sketch = None  # this worker degrades for good
                if note is None:
                    note = ("sketch_failed",
                            f"{type(e).__name__}: {e}")
        shm_name = ship_batch(spec, batch, has_meta)
        if put(("batch", seq, shm_name, has_meta, trunc_delta, note,
                parse_s, tracer.take(), delta)):
            return True
        # Teardown raced the ship: the segment is already unregistered
        # from this worker's tracker and nobody will ever attach it —
        # unlink here or it outlives the run in /dev/shm.
        discard_segment(shm_name)
        return False

    while not stop.is_set():
        try:
            msg = work.get(timeout=0.1)
        except _queue.Empty:
            continue
        if msg is None:
            put((
                "done", tracer.take(),
                sketch.to_dict()
                if sketch is not None and sketch_pending else None,
            ))
            return
        try:
            kind = msg[0]
            if kind == "mark":
                if not put(msg):
                    return
                continue
            if kind == "rawslot":
                # Zero-copy window: parse straight out of the mapped
                # ring slot, then hand the slot back for reuse.
                _, seq0, slot, text_len, sizes = msg
                buf, starts, ends = ring.read(slot, text_len, sum(sizes))
                t_w0 = time.perf_counter()
                try:
                    pos = 0
                    for j, n in enumerate(sizes):
                        before = trunc()
                        t0 = time.perf_counter()
                        batch = parse_raw(
                            buf, starts[pos:pos + n], ends[pos:pos + n]
                        )
                        dt = time.perf_counter() - t0
                        tracer.emit("parse.batch", t0, dt,
                                    args={"seq": seq0 + j})
                        pos += n
                        if not emit(batch, seq0 + j, trunc() - before, dt):
                            return
                finally:
                    del buf, starts, ends  # drop the slot's buffer exports
                    ring_free.put(slot)
                    # The window span closes at slot release: its end IS
                    # the moment the slot went back on the free queue.
                    tracer.emit(
                        "parse.window", t_w0,
                        time.perf_counter() - t_w0,
                        args={"slot": slot, "seq0": seq0,
                              "n_batches": len(sizes)},
                    )
            elif kind == "raw":
                _, seq0, buf, starts_list, ends_list = msg
                for j, (s, e) in enumerate(zip(starts_list, ends_list)):
                    before = trunc()
                    t0 = time.perf_counter()
                    batch = parse_raw(buf, s, e)
                    dt = time.perf_counter() - t0
                    tracer.emit("parse.batch", t0, dt,
                                args={"seq": seq0 + j})
                    if not emit(batch, seq0 + j, trunc() - before, dt):
                        return
            else:  # lines
                _, seq, lines, weights = msg
                before = trunc()
                t0 = time.perf_counter()
                batch = parse_lines(lines, weights)
                dt = time.perf_counter() - t0
                tracer.emit("parse.batch", t0, dt, args={"seq": seq})
                if not emit(batch, seq, trunc() - before, dt):
                    return
        except BaseException as e:
            if not put(("err", _safe_exc(e))):
                return
