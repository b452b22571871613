"""Input pipeline: files -> shuffled, parsed, padded device batches.

Replaces the reference's TF queue-runner pipeline (``TextLineReader`` +
shuffle batch queues, SURVEY.md §2 #6) with a thread-based producer/consumer
design driven by the same config knobs (``thread_num``, ``queue_size``,
``shuffle_buffer``, ``epoch_num``), feeding numpy batches that the train
loop ships to the device while the next batch parses — host-side pipelining
in place of TF queues.

Parsing uses the C++ extension when available (multi-threaded tokenizer +
murmur hashing, like the reference's ``FmParser``) and falls back to the
pure-Python oracle.  ``parse_processes`` moves parsing into a spawned
worker-process pool (``data.procpool``) that ships parsed batches back over
POSIX shared memory — the GIL-free analogue of the reference's free-running
C++ parser threads, and the only way the pure-Python parse path scales.

One pipeline spans ALL epochs of a run (``epochs``/``start_epoch``): the
reader reseeds per epoch, emits :class:`EpochEnd` markers in-band
(``epoch_marks=True``), and — with ``cache_epochs`` — retains epoch 0's
parsed batches so later epochs replay from memory instead of re-parsing.
"""

from __future__ import annotations

import logging
import pickle
import random
import threading
import time
from collections import deque
from typing import Iterator, NamedTuple, Optional, Sequence

import numpy as np

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import libsvm

log = logging.getLogger(__name__)

# Raw-chunk read size for the fast ingest path.  Groups reference their
# window buffer (~shuffle_buffer lines when shuffling), so resident memory
# is bounded by the in-flight group count (work queue + parser threads)
# times the window byte size — a few windows in practice.
_CHUNK_BYTES = 4 << 20

_SENTINEL = object()
_CANCELLED = object()
_TIMEOUT = object()  # _ClosableQueue.get(timeout=...) expired empty


class EpochEnd(NamedTuple):
    """In-band epoch-boundary marker (``epoch_marks=True``).

    Yielded by BatchPipeline after the last batch of ``epoch``; the
    DevicePrefetcher flushes its pending super-batch group at a marker
    and forwards it, so super-batches never span epochs and the trainer
    can advance its checkpointed (epoch, batches_done) position without
    owning the epoch loop.
    """

    epoch: int


class SuperBatch(NamedTuple):
    """A pre-stacked ``[K, ...]`` group delivered in-band.

    With ``prestack_k > 0`` the pipeline stacks dispatch groups ONCE at
    epoch-0 group boundaries and delivers (and caches) them in this
    wrapper; :class:`DevicePrefetcher` recognizes it and ships the
    stacked batch straight to the device, skipping its own per-dispatch
    ``stack_batches`` — the replay epochs' host work drops to the
    permutation loop plus the H2D put.
    """

    batch: libsvm.Batch  # every leaf carries a leading K axis
    n: int  # batches stacked (K, or an epoch tail's K' < K)


class _Error:
    """Carries a worker/reader exception to the consuming thread."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _ClosableQueue:
    """Bounded queue whose ``cancel()`` wakes every blocked producer and
    consumer immediately — deterministic shutdown with no timed polling
    (the previous design's 0.1 s put/get polls could leave workers
    lingering a poll period after close).

    ``put`` returns False (instead of blocking) once cancelled; ``get``
    returns the module-level ``_CANCELLED`` sentinel.

    ``hist`` (an obs.DepthHist) records the depth every put/get saw —
    the full occupancy distribution, not a heartbeat-time point sample,
    so a queue flapping full↔empty between beats still shows up.
    """

    def __init__(self, maxsize: int, hist=None):
        self._items: deque = deque()
        self._max = max(1, maxsize)
        self._cv = threading.Condition()
        self._cancelled = False
        self._hist = hist if hist is not None else obs.NULL.depth_hist("")

    def put(self, item) -> bool:
        with self._cv:
            while len(self._items) >= self._max and not self._cancelled:
                self._cv.wait()
            if self._cancelled:
                return False
            self._items.append(item)
            self._hist.observe(len(self._items))
            self._cv.notify_all()
            return True

    def get(self, timeout: Optional[float] = None):
        """Next item; blocks until one arrives, the queue is cancelled
        (``_CANCELLED``), or — with ``timeout`` — the deadline passes
        with the queue still empty (``_TIMEOUT``).  The timed form is
        the serve batcher's coalescing wait: collect requests until the
        microbatch deadline, then dispatch whatever arrived."""
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        with self._cv:
            while not self._items and not self._cancelled:
                if deadline is None:
                    self._cv.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return _TIMEOUT
                self._cv.wait(remaining)
            if not self._items:
                return _CANCELLED
            self._hist.observe(len(self._items))
            item = self._items.popleft()
            self._cv.notify_all()
            return item

    def cancel(self):
        with self._cv:
            self._cancelled = True
            self._items.clear()
            self._cv.notify_all()

    def qsize(self) -> int:
        """Instantaneous depth (snapshot-time telemetry sample; a racy
        read of a deque length is exact enough for a gauge)."""
        return len(self._items)

    def snapshot(self) -> tuple:
        """The items queued now, oldest first, none taken: what a sole
        consumer's next ``get`` calls return without waiting (unless a
        ``cancel`` comes between)."""
        with self._cv:
            return tuple(self._items)


def _read_weight_file(path: str) -> list[str]:
    # Keep EVERY line (even blanks) so weight line i pairs with data line i;
    # parsing to float happens only for lines actually used.
    with open(path) as f:
        return [line.strip() for line in f]


def iter_lines(
    files: Sequence[str],
    weight_files: Optional[Sequence[str]] = None,
) -> Iterator[tuple[str, float]]:
    """Yield (line, weight) over all files; weights default to 1.0.

    ``weight_files`` parallels ``files`` line-for-line (reference
    ``weight_files`` cfg key, SURVEY.md §2 #6): weight-file line i belongs
    to data-file line i; blank/comment data lines are skipped along with
    their weight lines.
    """
    for i, path in enumerate(files):
        weights = None
        if weight_files:
            weights = _read_weight_file(weight_files[i])
        with open(path) as f:
            for lineno, line in enumerate(f):
                if not line.strip() or line.lstrip().startswith("#"):
                    continue
                if weights is None:
                    w = 1.0
                else:
                    try:
                        w = float(weights[lineno])
                    except (IndexError, ValueError) as e:
                        raise ValueError(
                            f"weight file {weight_files[i]} line {lineno + 1} "
                            f"does not pair with data file {path}: {e}"
                        ) from e
                yield line, w


def _shuffled(
    it: Iterator[tuple[str, float]], buffer_size: int, rng: random.Random
) -> Iterator[tuple[str, float]]:
    """Reservoir-style streaming shuffle (like TF's shuffle queue)."""
    buf: list[tuple[str, float]] = []
    for item in it:
        if len(buf) < buffer_size:
            buf.append(item)
            continue
        j = rng.randrange(buffer_size)
        yield buf[j]
        buf[j] = item
    rng.shuffle(buf)
    yield from buf


def _raw_chunk_stream(files: Sequence[str], chunk_bytes: int):
    """Binary chunks of all files as ONE stream; a '\\n' is injected at a
    file boundary when the file lacks a trailing newline, so lines never
    merge across files and batches pack across files like the line path."""
    for path in files:
        last = b"\n"
        with open(path, "rb") as f:
            while True:
                chunk = f.read(chunk_bytes)
                if not chunk:
                    break
                last = chunk[-1:]
                yield chunk
        if last != b"\n":
            yield b"\n"


def _iter_raw_windows(
    files: Sequence[str],
    batch_size: int,
    window_lines: int,
    chunk_bytes: int = _CHUNK_BYTES,
):
    """Yield (buf, starts[n], ends[n]) windows of complete raw text lines.

    The fast ingest path: files are read in binary chunks, accumulated to
    a byte target predicted from a running bytes-per-line estimate, and
    scanned ONCE by the C++ line scanner — the previous design counted
    newlines with bytes.count() first and then re-scanned with memchr,
    paying two passes over every byte.  Windows reference the joined
    buffer directly; no Python string is ever created per line.

    Mid-stream windows hold a multiple of ``batch_size`` lines so the
    caller can slice exact groups; leftover lines (plus any incomplete
    tail) are carried into the next buffer as bytes, including across
    file boundaries.  The final window flushes everything.
    """
    from fast_tffm_tpu.data import native

    window_lines = max(window_lines, batch_size)
    stream = _raw_chunk_stream(files, chunk_bytes)
    pending = b""
    est_bpl = 80.0  # running bytes-per-line estimate
    guess = 0  # line-count guess for the scanner (stable density)
    at_eof = False
    while not at_eof:
        target = int(window_lines * est_bpl) + 1
        parts = [pending]
        size = len(parts[0])
        first = True
        # Read at least one chunk per round (guarantees progress when the
        # carried-over pending bytes alone held < one batch of lines).
        while size < target or first:
            first = False
            chunk = next(stream, None)
            if chunk is None:
                at_eof = True
                break
            parts.append(chunk)
            size += len(chunk)
        buf = b"".join(parts)
        pending = b""
        if not buf:
            continue  # at_eof: the while condition ends the loop
        buf_end = len(buf) if at_eof else buf.rfind(b"\n") + 1
        if buf_end == 0:  # not a single complete line yet; need more bytes
            pending = buf
            est_bpl *= 2.0
            continue
        starts = native.find_line_offsets(buf, buf_end, guess=guess or None)
        n = len(starts)
        if n == 0:
            if at_eof:
                return
            pending = buf
            continue
        est_bpl = buf_end / n
        guess = n + 2
        ends = np.append(starts[1:], buf_end)
        if at_eof:
            n_keep = n  # flush everything, partial group included
        else:
            n_keep = (n // batch_size) * batch_size
            if n_keep == 0:  # window bytes held < one batch of lines
                pending = buf
                continue
            if n_keep < n:
                pending = buf[int(starts[n_keep]):]
            elif buf_end < len(buf):
                pending = buf[buf_end:]
        yield buf, starts[:n_keep], ends[:n_keep]


def _item_len(item) -> int:
    """Number of lines in a work item (line chunk or raw group)."""
    if isinstance(item, tuple):
        return len(item[1])
    return len(item)


def _batch_nbytes(batch: libsvm.Batch) -> int:
    arrays = [batch.labels, batch.ids, batch.vals, batch.fields,
              batch.weights]
    if batch.sort_meta is not None:
        arrays.extend(batch.sort_meta)  # ~doubles a batch
    return sum(a.nbytes for a in arrays)


def _msg_bytes(msg) -> int:
    """Serialized size of a work message for the ``ingest.work_msg_bytes``
    counter.  Descriptor messages (rawslot/mark) are measured exactly —
    they are ~200 B and their smallness is the claim a tier-1 test pins;
    payload-bearing fallbacks (raw windows, line chunks) are ESTIMATED
    from their content lengths instead of pickled a second time — with
    them, mp.Queue's feeder already pays the full serialization once,
    and doubling that cost to count it would re-add the parent-side tax
    the ring exists to remove."""
    kind = msg[0]
    if kind in ("rawslot", "mark"):
        return len(pickle.dumps(msg, pickle.HIGHEST_PROTOCOL))
    if kind == "raw":
        _, _, buf, starts_list, ends_list = msg
        return len(buf) + sum(
            a.nbytes for a in starts_list
        ) + sum(a.nbytes for a in ends_list)
    if kind == "lines":
        _, _, lines, weights = msg
        return sum(len(s) for s in lines) + 8 * len(weights)
    return 0  # pragma: no cover - shutdown sentinel


def _strided_rounds(it, shard_id: int, num_shards: int):
    """Yield every num_shards-th item, but only from COMPLETE rounds.

    Multi-host input sharding: shard s takes items s, n+s, 2n+s, ... of the
    (identically seeded, hence identical) global stream.  Every shard must
    emit the SAME number of items — a host running one extra step would
    deadlock the others in the step's collectives — so an item is held back
    until its round is known complete (an item of the next round arrives)
    and the tail round is dropped at EOF if partial.
    """
    pending = None  # (round, item) candidate from this shard's slot
    last_idx = -1
    for idx, item in enumerate(it):
        last_idx = idx
        r = idx // num_shards
        if pending is not None and r > pending[0]:
            yield pending[1]
            pending = None
        if idx % num_shards == shard_id:
            pending = (r, item)
    if pending is not None and last_idx >= pending[0] * num_shards + num_shards - 1:
        yield pending[1]


class BatchPipeline:
    """Background parse/batch pipeline spanning a whole training run.

    One reader thread streams work items into a queue; ``thread_num``
    parser threads (or, with ``parse_processes > 0``, that many spawned
    worker PROCESSES — see :mod:`fast_tffm_tpu.data.procpool`) turn them
    into padded :class:`Batch` objects pushed to a bounded output queue
    (``queue_size``).  Batch order is nondeterministic across parser
    workers (like the reference's async queues) unless ``ordered=True``,
    which keeps the parallel parse but reorders delivery by sequence
    number (deterministic given the seed).

    The pipeline owns the EPOCH loop: ``epochs`` is the run's total epoch
    count, epoch e reseeds with ``seed + e``, and ``start_epoch`` /
    ``skip_batches`` name a resume position ("skip to (epoch, batch)").
    With ``epoch_marks=True`` an :class:`EpochEnd` marker is yielded
    in-band after each epoch's last batch (exact under ``ordered=True``;
    with free-running workers it can arrive up to the in-flight batch
    count early).
    """

    def __init__(
        self,
        files: Sequence[str],
        cfg: FmConfig,
        *,
        weight_files: Optional[Sequence[str]] = None,
        epochs: int = 1,
        shuffle: bool = True,
        drop_remainder: bool = False,
        seed: Optional[int] = None,
        ordered: bool = False,
        start_epoch: int = 0,
        skip_batches: int = 0,
        shard: tuple[int, int] = (0, 1),
        sort_meta_spec=None,
        cache_epochs: bool = False,
        cache_max_bytes: int = 1 << 30,
        prestack_k: int = 0,
        epoch_marks: bool = False,
        telemetry: Optional[obs.Telemetry] = None,
        tracer: Optional[obs.Tracer] = None,
        quality: Optional["obs.StreamSketch"] = None,
    ):
        self.files = list(files)
        # Telemetry instruments (obs.NULL when not passed: every call
        # below is a no-op, so instrumentation never branches).  Stage
        # naming: ingest.* covers reader + parse workers + delivery.
        self.telemetry = telemetry if telemetry is not None else obs.NULL
        tel = self.telemetry
        # Causal batch tracing (obs.NULL_TRACER = no-op): spans per read
        # window / ring-slot acquire / parse, plus an ``ingest.deliver``
        # point at the single delivery exit that bridges the reader's
        # work-item ``seq`` to the delivered ``batch`` index — the join
        # key the prefetcher's super-batch grouping continues from.
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        # Model-quality drift sketches (obs.StreamSketch, None = off):
        # maintained ON the parse path — thread workers fold each
        # parsed batch in directly (the accumulator locks internally),
        # process workers keep a local SketchSet and ship serialized
        # deltas back on their result messages exactly like parse
        # timings.  Cached replay epochs re-deliver epoch-0 batches and
        # are deliberately NOT re-sketched: a replay's distribution is
        # epoch 0's by construction, so re-adding it would only inflate
        # counts without moving any distribution.
        self._quality = quality
        # seq of the batch most recently yielded by the streaming core
        # (generator chains are synchronous, so at the __iter__ exit this
        # names exactly the item that just bubbled up); None for cached
        # replays, which have no fresh parse to correlate with.
        self._last_seq: Optional[int] = None
        self._deliver_idx = 0
        self._c_batches = tel.counter("ingest.batches")
        self._c_examples = tel.counter("ingest.examples")
        self._c_cache_replays = tel.counter("ingest.cache_replay_batches")
        self._t_parse = tel.timer("ingest.parse")
        # The drift-sketch fold on the thread-worker path (of it,
        # StreamSketch times its own lock: ingest.sketch_lock).
        self._t_sketch = tel.timer("ingest.sketch")
        self._t_reader_block = tel.timer("ingest.reader_block")
        self._t_out_block = tel.timer("ingest.out_block")
        # Prestacked-cache + inbound-ring instruments: how many windows
        # went through the SHM ring vs fell back to the pickled queue
        # path, the descriptor bytes that DID cross the queue, and the
        # once-per-group stack time of the prestacked cache.
        self._t_prestack = tel.timer("ingest.prestack")
        self._c_ring_windows = tel.counter("ingest.ring_windows")
        self._c_ring_fallback = tel.counter("ingest.ring_fallback_windows")
        self._c_ring_bytes = tel.counter("ingest.ring_window_bytes")
        self._c_q_msg_bytes = tel.counter("ingest.work_msg_bytes")
        # Component memory ledger (resource plane): the bytes this
        # pipeline is RESPONSIBLE for right now — the epoch cache's
        # retained batches (raw or prestacked; drops to 0 on overflow)
        # and the SHM ring's fixed slot allocation (0 once torn down).
        self._g_cache_bytes = tel.gauge("ingest.cache_bytes")
        self._g_ring_bytes = tel.gauge("ingest.ring_bytes")
        # Always-real counter (not gated on telemetry): out-of-range-id
        # batches are a data/vocabulary integrity signal the trainer
        # surfaces in its RESULTS, not just in logs or optional stages.
        self._oor_counter = obs.Counter()
        tel.sample("ingest.oor_batches", lambda: self._oor_counter.value)
        tel.sample(
            "ingest.truncated_features", lambda: self.truncated_features
        )
        self.cfg = cfg
        self.weight_files = list(weight_files) if weight_files else None
        self.epochs = epochs
        self.shuffle = shuffle
        self.drop_remainder = drop_remainder
        self.seed = cfg.seed if seed is None else seed
        # Resume position: deliver epochs [start_epoch, epochs), skipping
        # the first skip_batches of epoch start_epoch WITHOUT parsing them
        # (the cached path re-parses epoch 0 to rebuild the replay cache —
        # see __iter__).  Skipping happens after shuffling, so the stream
        # continues exactly where a run with the same seed left off.
        if not 0 <= start_epoch < max(1, epochs):
            raise ValueError(
                f"start_epoch {start_epoch} outside [0, {epochs})"
            )
        self.start_epoch = start_epoch
        self.skip_batches = skip_batches
        # Multi-host input sharding (shard_id, num_shards): this pipeline
        # emits only its strided share of the global stream, round-complete
        # (see _strided_rounds).  Skip counts apply AFTER sharding.
        if not (0 <= shard[0] < shard[1]):
            raise ValueError(f"bad shard {shard}")
        self.shard = shard
        # ordered=True delivers batches in input order (the predict path
        # needs score/line alignment; model-axis-spanning hosts need
        # identical order).  Parsing still runs on thread_num workers —
        # items carry sequence numbers and the consumer reorders.
        self.ordered = ordered
        self.epoch_marks = epoch_marks
        self._native, self._parser = _make_parser(cfg)
        # (vocab, chunk, tile) or None: when set, workers attach host-
        # computed sparse-apply prep (native.sort_meta) to each batch,
        # moving the device step's id sort onto these threads.
        self._sort_meta_spec = sort_meta_spec
        self._sort_meta_warned = False
        # Truncation counted OUTSIDE the in-process native parser: process
        # workers ship their per-batch drop counts back with each batch,
        # and cached-epoch replays re-add epoch 0's total per replay (the
        # same features a re-parse would have dropped again), so the
        # trainer's periodic warning stays truthful in every ingest mode.
        self._trunc_extra = 0
        # Fast ingest: raw binary chunks + C++ line scan, no Python string
        # per line. weight_files need per-line pairing so they stay on
        # the line path. Shuffling permutes LINES
        # within shuffle_buffer-line windows (matching the line path's
        # reservoir window).
        self._raw = cfg.fast_ingest and not self.weight_files
        # Multi-epoch parsed-batch cache (the tf.data ``.cache()``
        # pattern): epoch 0 parses normally while retaining every
        # delivered Batch; epochs 1..E-1 replay the cached batches in a
        # seeded per-epoch permutation instead of re-reading and
        # re-parsing the same text.  Batch contents are preserved exactly
        # (so attached sort_meta stays valid); cross-epoch remixing drops
        # to batch granularity — the documented tradeoff, opt-in only.
        # A byte budget guards host memory (overflow falls back to
        # re-parsing); resume positions are honored (cache-aware: epoch 0
        # re-parses once to rebuild the cache, later epochs replay).
        self._cache_epochs = (
            cache_epochs and epochs > 1 and shard == (0, 1)
        )
        self._cache_max_bytes = cache_max_bytes
        # Prestacked cache storage (cache_prestacked): dispatch groups of
        # prestack_k batches are stacked ONCE at epoch-0 group boundaries
        # and delivered/cached as SuperBatch items; replay epochs permute
        # at super-batch granularity and the transfer stage skips its
        # per-dispatch stack.  Only meaningful when the cache engages.
        self._prestack_k = prestack_k if self._cache_epochs else 0
        # Outcome of the cache for observability: "off" | "cached" |
        # "overflow" (budget blown during epoch 0; later epochs re-parsed).
        self.cache_result = "off"

    @property
    def truncated_features(self) -> int:
        """Feature occurrences dropped by max_features so far (reference
        FmParser warned about truncation, SURVEY.md §2 #1); the trainer
        surfaces this periodically.  Includes process-worker drops and
        cached-epoch replays (each replay re-adds epoch 0's total)."""
        base = self._native.truncated_features
        return base + self._trunc_extra

    @property
    def oor_batches(self) -> int:
        """Batches whose host sort prep hit out-of-range feature ids — a
        data/vocabulary_size integrity bug (the device-sort path silently
        drops those updates).  Counted across thread AND process workers;
        the trainer surfaces it in train results and the final record."""
        return self._oor_counter.value

    def stats(self) -> dict:
        """Point-in-time data-integrity snapshot: the counters every
        self-report (heartbeat, final record, /status endpoint) carries.
        Thread-safe and callable at any time, including after shutdown —
        the live status endpoint reads it from HTTP handler threads
        while the pipeline runs."""
        return {
            "truncated_features": int(self.truncated_features),
            "out_of_range_batches": int(self.oor_batches),
            "ingest_cache": self.cache_result,
        }

    def __iter__(self) -> Iterator:
        E, e0 = self.epochs, self.start_epoch
        if not self._cache_epochs:
            inner = self._emit_stream(E - e0, e0, self.skip_batches)
        else:
            inner = self._iter_cached(E, e0)
        # Delivery accounting happens at the single exit point so every
        # path (threads, procpool, cached replay) counts identically.
        # The O(batch) example count only runs when telemetry is live —
        # "disabled" must mean no per-batch work at all.
        counting = self.telemetry.enabled
        tracing = self.tracer.enabled
        for item in inner:
            if isinstance(item, SuperBatch):
                self._c_batches.add(item.n)
                if counting:
                    self._c_examples.add(
                        int(np.count_nonzero(item.batch.weights > 0))
                    )
                if tracing:
                    self.tracer.point("ingest.deliver", args={
                        "batch": self._deliver_idx, "n": item.n,
                        "seq": self._last_seq,
                    })
                self._deliver_idx += item.n
            elif not isinstance(item, EpochEnd):
                self._c_batches.add(1)
                if counting:
                    self._c_examples.add(
                        int(np.count_nonzero(item.weights > 0))
                    )
                if tracing:
                    self.tracer.point("ingest.deliver", args={
                        "batch": self._deliver_idx, "n": 1,
                        "seq": self._last_seq,
                    })
                self._deliver_idx += 1
            yield item

    def _emit_stream(self, n_epochs: int, first_epoch: int, skip: int):
        """_iter_stream with EpochEnd markers filtered per epoch_marks."""
        for item in self._iter_stream(n_epochs, first_epoch, skip):
            if isinstance(item, EpochEnd) and not self.epoch_marks:
                continue
            yield item

    def _iter_cached(self, E: int, e0: int):
        """cache_epochs delivery: parse epoch 0 once (caching every
        batch), then replay epochs 1..E-1 as seeded permutations of the
        cache.  A resume past the start of epoch 0 re-parses epoch 0 to
        REBUILD the cache (delivering nothing for already-trained
        batches), then replays from the resume position — later epochs
        come from memory instead of a per-epoch re-parse."""
        if self._prestack_k > 0:
            yield from self._iter_cached_prestacked(E, e0)
            return
        cache: Optional[list] = []
        size = 0
        self.cache_result = "cached"
        deliver = e0 == 0
        skip = self.skip_batches
        trunc_start = self.truncated_features
        n_seen = 0
        stream = self._iter_stream(1, 0, 0)
        try:
            for item in stream:
                if isinstance(item, EpochEnd):
                    if deliver and self.epoch_marks:
                        yield item
                    continue
                if cache is not None:
                    size += _batch_nbytes(item)
                    if size > self._cache_max_bytes:
                        log.info(
                            "ingest cache over budget (%d > %d bytes); "
                            "re-parsing later epochs", size,
                            self._cache_max_bytes,
                        )
                        cache = None
                        self.cache_result = "overflow"
                        self._g_cache_bytes.set(0)  # retained nothing
                        if not deliver:
                            break  # rebuild-only parse: stop early
                    else:
                        cache.append(item)
                        self._g_cache_bytes.set(size)
                n_seen += 1
                if deliver and n_seen > skip:
                    yield item
        finally:
            stream.close()
        if cache is None:  # budget blown: stream the remaining epochs
            if deliver:
                if E > 1:
                    yield from self._emit_stream(E - 1, 1, 0)
            else:
                # The resumed epoch streams from ITS seed with the skip —
                # identical to what the uninterrupted overflow run
                # delivered for that epoch.
                yield from self._emit_stream(E - e0, e0, skip)
            return
        epoch0_trunc = self.truncated_features - trunc_start
        self._last_seq = None  # replays have no fresh parse to trace
        for epoch in range(max(1, e0), E):
            order = list(range(len(cache)))
            if self.shuffle:
                random.Random(self.seed + epoch).shuffle(order)
            start = skip if epoch == e0 else 0
            for i in order[start:]:
                self._c_cache_replays.add(1)
                yield cache[i]
            # A re-parse of this epoch would have dropped the same
            # features again; keep the running counter truthful.
            self._trunc_extra += epoch0_trunc
            if self.epoch_marks:
                yield EpochEnd(epoch)

    @staticmethod
    def _slice_super(sb: SuperBatch, start: int) -> SuperBatch:
        """Leading-axis tail slice of a stacked group (views, no copy):
        a resume position that lands inside a group delivers only the
        group's untrained suffix."""
        b = sb.batch
        meta = b.sort_meta
        if meta is not None:
            meta = type(meta)(*(x[start:] for x in meta))
        return SuperBatch(
            libsvm.Batch(
                b.labels[start:], b.ids[start:], b.vals[start:],
                b.fields[start:], b.weights[start:], sort_meta=meta,
            ),
            sb.n - start,
        )

    def _iter_cached_prestacked(self, E: int, e0: int):
        """cache_prestacked delivery: epoch 0 streams as usual but every
        ``prestack_k`` delivered batches are stacked ONCE into a [K, ...]
        SuperBatch (the epoch tail stacks at K' = leftover) which is
        both delivered and cached; epochs 1..E-1 replay the cached
        super-batches in a seeded per-epoch permutation.  The batches
        inside every group are byte-identical to the plain cached path —
        only the replay permutation granularity changes (super-batch
        instead of batch, the documented tradeoff).  Resume mirrors
        ``_iter_cached``: epoch 0 re-parses to rebuild, the skip count
        is consumed in whole groups (a trainer position always lands on
        a group boundary; a foreign mid-group skip delivers the group's
        sliced tail)."""
        k = self._prestack_k
        cache: Optional[list] = []
        size = 0
        self.cache_result = "cached"
        deliver = e0 == 0
        skip = self.skip_batches
        trunc_start = self.truncated_features
        n_seen = 0  # batches consumed from the epoch-0 stream
        group: list = []
        stream = self._iter_stream(1, 0, 0)

        def flush_group():
            """Stack the pending group once; cache + deliver decisions."""
            nonlocal size, cache, group
            if not group:
                return None
            with self._t_prestack.time():
                sb = SuperBatch(stack_batches(group), len(group))
            start_idx = n_seen - len(group)
            group = []
            if cache is not None:
                size += _batch_nbytes(sb.batch)
                if size > self._cache_max_bytes:
                    log.info(
                        "ingest cache over budget (%d > %d bytes); "
                        "re-parsing later epochs", size,
                        self._cache_max_bytes,
                    )
                    cache = None
                    self.cache_result = "overflow"
                    self._g_cache_bytes.set(0)  # retained nothing
                else:
                    cache.append(sb)
                    self._g_cache_bytes.set(size)
            if not deliver:
                return None
            if start_idx >= skip:
                return sb
            if n_seen > skip:  # mid-group resume: deliver the tail
                return self._slice_super(sb, skip - start_idx)
            return None

        try:
            for item in stream:
                if isinstance(item, EpochEnd):
                    out = flush_group()  # epoch tail: K' = leftover
                    if out is not None:
                        yield out
                    if deliver and self.epoch_marks:
                        yield item
                    if cache is None and not deliver:
                        break  # rebuild-only parse overflowed: stop early
                    continue
                group.append(item)
                n_seen += 1
                if len(group) == k:
                    out = flush_group()
                    if out is not None:
                        yield out
                    if cache is None and not deliver:
                        break
        finally:
            stream.close()
        if cache is None:  # budget blown: stream the remaining epochs
            if deliver:
                if E > 1:
                    yield from self._emit_stream(E - 1, 1, 0)
            else:
                yield from self._emit_stream(E - e0, e0, skip)
            return
        epoch0_trunc = self.truncated_features - trunc_start
        self._last_seq = None  # replays have no fresh parse to trace
        for epoch in range(max(1, e0), E):
            order = list(range(len(cache)))
            if self.shuffle:
                random.Random(self.seed + epoch).shuffle(order)
            rem = skip if epoch == e0 else 0
            for gi in order:
                sb = cache[gi]
                if rem >= sb.n:
                    rem -= sb.n
                    continue
                self._c_cache_replays.add(sb.n - rem)
                yield self._slice_super(sb, rem) if rem else sb
                rem = 0
            self._trunc_extra += epoch0_trunc
            if self.epoch_marks:
                yield EpochEnd(epoch)

    # ------------------------------------------------------------------
    # Streaming core: reader -> parse workers (threads or processes)
    # ------------------------------------------------------------------

    def _line_chunks(self, rng):
        """Line path: line-level shuffle, then fixed-size chunks."""
        cfg = self.cfg
        it = iter_lines(self.files, self.weight_files)
        if self.shuffle:
            it = _shuffled(it, max(1, cfg.shuffle_buffer), rng)
        chunk: list[tuple[str, float]] = []
        for item in it:
            chunk.append(item)
            if len(chunk) == cfg.batch_size:
                yield chunk
                chunk = []
        if chunk:
            yield chunk

    def _raw_groups(self, rng):
        """Fast path: scan-once windows -> line-level shuffle ->
        groups.  The shuffle window is ``shuffle_buffer`` LINES (like
        the line path's reservoir), permuted with numpy — each group
        then references a shuffled, non-contiguous view of the window
        buffer, which parse_raw gathers zero-copy."""
        cfg = self.cfg
        window = (
            max(cfg.shuffle_buffer, cfg.batch_size)
            if self.shuffle else cfg.batch_size
        )
        for buf, starts, ends in _iter_raw_windows(
            self.files, cfg.batch_size, window
        ):
            n = len(starts)
            if self.shuffle and n > 1:
                perm = np.random.default_rng(
                    rng.getrandbits(63)
                ).permutation(n)
                starts, ends = starts[perm], ends[perm]
            for i in range(0, n, cfg.batch_size):
                yield buf, starts[i:i + cfg.batch_size], ends[
                    i:i + cfg.batch_size
                ]

    def _epoch_items(self, n_epochs: int, first_epoch: int, skip: int):
        """(seq, work-item-or-EpochEnd) across epochs — the reader-side
        epoch loop: per-epoch reseeding (``seed + epoch``, matching what
        a fresh per-epoch pipeline would draw), drop_remainder filtering
        BEFORE sharding (all shards must see the same global item
        indexing), strided multi-host sharding, and the resume skip
        (first epoch only, post-shard)."""
        cfg = self.cfg
        seq = 0
        for epoch in range(first_epoch, first_epoch + n_epochs):
            rng = random.Random(self.seed + epoch)
            to_skip = skip if epoch == first_epoch else 0
            if self._raw:
                # Line-level shuffle happens inside _raw_groups over
                # shuffle_buffer-line windows — the same mixing window as
                # the line path's reservoir, so no group-order reservoir
                # on top (stacking one would pin many window buffers).
                it = self._raw_groups(rng)
            else:
                it = self._line_chunks(rng)
            if self.drop_remainder:
                it = (x for x in it if _item_len(x) >= cfg.batch_size)
            if self.shard[1] > 1:
                it = _strided_rounds(it, *self.shard)
            for item in it:
                if to_skip > 0:
                    to_skip -= 1
                    continue
                yield seq, item
                seq += 1
            yield seq, EpochEnd(epoch)
            seq += 1

    def _traced_items(self, it):
        """Wrap the reader's work-item stream with ``read.item`` spans:
        each span covers the time to PRODUCE one item (file read, window
        scan, shuffle) — generator chains run synchronously, so nothing
        else can hide inside it.  No-op (plain passthrough) when tracing
        is off."""
        tracer = self.tracer
        if not tracer.enabled:
            yield from it
            return
        tracer.name_thread("ingest-reader")
        while True:
            t0 = time.perf_counter()
            nxt = next(it, None)
            if nxt is None:
                return
            seq, item = nxt
            if not isinstance(item, EpochEnd):
                tracer.emit("read.item", t0, time.perf_counter() - t0,
                            args={"seq": seq})
            yield seq, item

    def _iter_stream(
        self, n_epochs: int, first_epoch: int = 0, skip: int = 0
    ) -> Iterator:
        if n_epochs <= 0:
            return
        if self.cfg.parse_processes > 0:
            yield from self._iter_stream_procs(n_epochs, first_epoch, skip)
        else:
            yield from self._iter_stream_threads(n_epochs, first_epoch, skip)

    def _attach_meta(self, batch: libsvm.Batch) -> libsvm.Batch:
        """Host sort prep for one batch (thread-mode workers)."""
        from fast_tffm_tpu.data import native as _native

        # Metadata is an optimization, not a correctness requirement:
        # the device-sort path handles sort_meta=None.  A native failure
        # here must degrade, not kill the epoch — same contract as
        # Trainer._put's fallback.  But the two failure classes degrade
        # differently (ADVICE r5): out-of-range ids are a
        # data/vocabulary_size integrity bug whose updates the device
        # path SILENTLY drops, so that warning repeats per bad batch;
        # any other native failure disables the spec once and goes quiet.
        try:
            return batch._replace(
                sort_meta=_native.sort_meta(
                    batch.ids, *self._sort_meta_spec
                )
            )
        except _native.OutOfRangeIdsError as e:
            self._oor_counter.add(1)
            log.warning(
                "host sort_meta rejected a batch (%s); the input data or "
                "vocabulary_size is wrong — the device-sort path will "
                "silently drop updates for ids >= vocabulary_size", e,
            )
        except Exception as e:
            self._sort_meta_spec = None
            if not self._sort_meta_warned:
                self._sort_meta_warned = True
                log.warning(
                    "host sort_meta failed (%s: %s); falling back to "
                    "device sort for the rest of the run",
                    type(e).__name__, e,
                )
        return batch

    def _iter_stream_threads(
        self, n_epochs: int, first_epoch: int, skip: int
    ) -> Iterator:
        cfg = self.cfg
        # Per-put/get depth histograms (not heartbeat-time point samples:
        # a flapping queue shows its full occupancy distribution).  work
        # deep + out shallow = parse-bound; work shallow + out deep = the
        # consumer (training) is the bottleneck.
        work = _ClosableQueue(
            max(2, cfg.queue_size),
            hist=self.telemetry.depth_hist("ingest.work_q_depth"),
        )
        out = _ClosableQueue(
            max(2, cfg.queue_size),
            hist=self.telemetry.depth_hist("ingest.out_q_depth"),
        )
        n_workers = max(1, cfg.thread_num)

        tracer = self.tracer
        tracing = tracer.enabled
        timed = self.telemetry.enabled or tracing

        def reader():
            try:
                for seq, item in self._traced_items(self._epoch_items(
                    n_epochs, first_epoch, skip
                )):
                    # Producer-block time: how long the reader waits for
                    # a work-queue slot.  Large totals mean parsing (not
                    # reading) limits ingest.
                    t0 = time.perf_counter()
                    ok = work.put((seq, item))
                    self._t_reader_block.observe(time.perf_counter() - t0)
                    if not ok:
                        return
            except BaseException as e:  # surfaces in the consumer
                out.put(_Error(e))
            finally:
                for _ in range(n_workers):
                    if not work.put(_SENTINEL):
                        break

        def parse_worker():
            if tracing:
                tracer.name_thread("parse-worker")
            while True:
                got = work.get()
                if got is _CANCELLED:
                    return
                if got is _SENTINEL:
                    out.put(_SENTINEL)
                    return
                seq, chunk = got
                if isinstance(chunk, EpochEnd):
                    out.put((seq, chunk))
                    continue
                try:
                    # Per-batch timing only when someone consumes it:
                    # "disabled" must mean no per-batch work at all
                    # (same invariant as delivery counting above).
                    t0p = time.perf_counter() if timed else 0.0
                    if isinstance(chunk, tuple):  # raw (buf,starts,ends)
                        batch = self._native.parse_raw(
                            chunk[0], chunk[1], chunk[2], cfg.batch_size
                        )
                    else:
                        lines = [c[0] for c in chunk]
                        weights = [c[1] for c in chunk]
                        batch = self._parser(lines, weights)
                    if self._sort_meta_spec is not None:
                        batch = self._attach_meta(batch)
                    if timed:
                        dtp = time.perf_counter() - t0p
                        self._t_parse.observe(dtp)
                        if tracing:
                            tracer.emit("parse.batch", t0p, dtp,
                                        args={"seq": seq})
                    if self._quality is not None:
                        # Drift sketches ride the parse threads (batch
                        # cadence, lock inside the accumulator) so the
                        # delivery path pays nothing.  Guarded: a
                        # sketching failure is an OBSERVER failure —
                        # it degrades the quality plane, it must never
                        # surface through the worker's fatal error
                        # path and kill the training it observes.
                        try:
                            with obs.Phase(self._t_sketch,
                                           "tffm:ingest.sketch",
                                           n=len(batch.labels)):
                                self._quality.update_batch(
                                    batch.ids, batch.vals, batch.weights
                                )
                        except Exception as e:  # noqa: BLE001
                            self._quality = None  # degrade for good
                            log.warning(
                                "quality sketching disabled: "
                                "update_batch failed (%s: %s); "
                                "training continues without ingest "
                                "drift sketches",
                                type(e).__name__, e,
                            )
                except BaseException as e:
                    out.put(_Error(e))
                    continue
                # Worker-block time on delivery: the consumer (transfer
                # stage / training) isn't draining fast enough.
                t0 = time.perf_counter()
                out.put((seq, batch))
                self._t_out_block.observe(time.perf_counter() - t0)

        threads = [threading.Thread(target=reader, daemon=True)]
        threads += [
            threading.Thread(target=parse_worker, daemon=True)
            for _ in range(n_workers)
        ]
        for t in threads:
            t.start()
        finished = 0
        next_seq = 0
        held: dict = {}  # ordered mode: out-of-order batches by seq
        try:
            while finished < n_workers:
                item = out.get()
                if item is _CANCELLED:
                    return  # torn down externally
                if item is _SENTINEL:
                    finished += 1
                    continue
                if isinstance(item, _Error):
                    raise item.exc
                seq, obj = item
                if not self.ordered:
                    self._last_seq = seq
                    yield obj
                    continue
                # Reorder by sequence number: parsing is parallel but
                # delivery follows reader order (bounded by in-flight
                # items: work queue + workers + out queue).
                held[seq] = obj
                while next_seq in held:
                    self._last_seq = next_seq
                    yield held.pop(next_seq)
                    next_seq += 1
            # Workers exited; whatever is held is contiguous from
            # next_seq (an error would have raised above).
            for seq in sorted(held):
                self._last_seq = seq
                yield held[seq]
        finally:
            # Deterministic shutdown: cancel wakes every blocked put/get
            # at once, so joins complete without timed polling.
            work.cancel()
            out.cancel()
            for t in threads:
                t.join()

    def _ring_slot_bytes(self) -> int:
        """Ring slot capacity for this config's raw windows: text bytes
        (window lines at a generous 1 KB/line, plus one read chunk of
        accumulation overshoot) + the 16 B/line offset arrays.  A window
        that still outgrows this falls back to the pickled queue path —
        counted, never wrong — so the estimate only has to be right for
        the common case."""
        cfg = self.cfg
        window_lines = (
            max(cfg.shuffle_buffer, cfg.batch_size)
            if self.shuffle else cfg.batch_size
        )
        want = window_lines * (1024 + 16) + 2 * _CHUNK_BYTES
        return min(max(want, 1 << 20), 64 << 20)

    def _iter_stream_procs(
        self, n_epochs: int, first_epoch: int, skip: int
    ) -> Iterator:
        """Multiprocess parse: the reader thread coalesces work by raw
        window and a spawned worker pool parses + preps batches, shipping
        them back as shared memory segments (data.procpool) — parsing
        never touches this process's GIL, which is what makes
        ``thread_num`` useless on the pure-Python parse path.

        With ``ring_slots > 0`` the raw direction is zero-copy too: the
        reader writes each window (text + offsets) into a slot of an
        inbound SHM ring and only slot DESCRIPTORS cross the work queue;
        workers parse in place and recycle slots over a free queue.
        Windows larger than a slot (and the line path) fall back to
        pickling through the queue, exactly as before."""
        import multiprocessing as mp
        import queue as _q

        from fast_tffm_tpu.data import procpool

        cfg = self.cfg
        ctx = mp.get_context("spawn")
        n_workers = max(1, cfg.parse_processes)
        # Raw work items are whole windows (many batches each); a couple
        # per worker bounds resident window bytes without starving.
        work = ctx.Queue(maxsize=max(2, min(cfg.queue_size, 2 * n_workers)))
        out = ctx.Queue(maxsize=max(2, cfg.queue_size))
        stop = ctx.Event()
        shm_tag = procpool.make_shm_tag()
        ring = None
        ring_free = None
        if self._raw and cfg.ring_slots > 0:
            ring = procpool.ShmRing.create(
                shm_tag, cfg.ring_slots, self._ring_slot_bytes()
            )
            # Ledger: the ring is a fixed allocation for its lifetime.
            self._g_ring_bytes.set(cfg.ring_slots * ring.slot_bytes)
            ring_free = ctx.Queue(maxsize=cfg.ring_slots + 1)
            for i in range(cfg.ring_slots):
                ring_free.put(i)
        spec = procpool.WorkerSpec(
            vocabulary_size=cfg.vocabulary_size,
            max_features=cfg.max_features,
            hash_feature_id=cfg.hash_feature_id,
            field_num=cfg.field_num,
            batch_size=cfg.batch_size,
            sort_meta_spec=self._sort_meta_spec,
            shm_tag=shm_tag,
            ring_name=ring.name if ring is not None else None,
            ring_slots=cfg.ring_slots,
            ring_slot_bytes=ring.slot_bytes if ring is not None else 0,
            trace=self.tracer.enabled,
            sketch_every=(
                procpool.SKETCH_SHIP_EVERY
                if self._quality is not None else 0
            ),
        )
        procs = [
            ctx.Process(
                target=procpool.parse_worker_main,
                args=(spec, work, out, stop, ring_free), daemon=True,
            )
            for _ in range(n_workers)
        ]
        for p in procs:
            p.start()
        # Depth histograms around the parent-side queue ends (mp.Queue
        # qsize is approximate, and can raise on exotic platforms — the
        # helper degrades to not observing).
        h_work = self.telemetry.depth_hist("ingest.work_q_depth")
        h_out = self.telemetry.depth_hist("ingest.out_q_depth")
        h_ring = self.telemetry.depth_hist("ingest.ring_free_slots")

        def observe_depth(hist, q):
            try:
                hist.observe(q.qsize())
            except (NotImplementedError, OSError):  # pragma: no cover
                pass

        def put_mp(q, item) -> bool:
            return procpool.put_with_stop(q, item, stop)

        # Descriptor-size accounting only when telemetry is live: the
        # whole point of the ring is that work messages shrink to slot
        # descriptors, and the counter is what proves it (tier-1 test).
        counting = self.telemetry.enabled
        tracer = self.tracer

        reader_err: list = []

        def reader():
            pend = None  # (buf, seq0, [starts...], [ends...])

            def put_work(msg) -> bool:
                # Same producer-block accounting as the thread path: time
                # waiting for a work-queue slot (parse-bound signal).
                if counting:
                    self._c_q_msg_bytes.add(_msg_bytes(msg))
                observe_depth(h_work, work)
                t0 = time.perf_counter()
                ok = put_mp(work, msg)
                self._t_reader_block.observe(time.perf_counter() - t0)
                return ok

            def flush() -> bool:
                nonlocal pend
                if pend is None:
                    return True
                buf, seq0, starts_list, ends_list = (
                    pend[0], pend[1], pend[2], pend[3]
                )
                pend = None
                n_lines = sum(len(s) for s in starts_list)
                if (
                    ring is not None
                    and procpool.ShmRing.need_bytes(len(buf), n_lines)
                    <= ring.slot_bytes
                ):
                    observe_depth(h_ring, ring_free)
                    # Slot-acquire wait: all slots in flight = the ring's
                    # backpressure; a long span here means parse workers
                    # (not the reader) limit ingest.
                    t0s = time.perf_counter()
                    slot = procpool.get_with_stop(ring_free, stop)
                    if slot is None:
                        return False
                    if tracer.enabled:
                        tracer.emit(
                            "ring.slot_acquire", t0s,
                            time.perf_counter() - t0s,
                            args={"slot": slot, "seq": seq0},
                        )
                    ring.write(
                        slot, buf,
                        np.concatenate(starts_list),
                        np.concatenate(ends_list),
                    )
                    self._c_ring_windows.add(1)
                    self._c_ring_bytes.add(len(buf))
                    return put_work((
                        "rawslot", seq0, slot, len(buf),
                        [len(s) for s in starts_list],
                    ))
                # Oversized window (or ring off): the window's bytes
                # cross the queue pickled, exactly the old contract.
                self._c_ring_fallback.add(1)
                return put_work(
                    ("raw", seq0, bytes(buf), starts_list, ends_list)
                )

            try:
                for seq, item in self._traced_items(self._epoch_items(
                    n_epochs, first_epoch, skip
                )):
                    if isinstance(item, EpochEnd):
                        if not flush():
                            return
                        if not put_work(("mark", seq, item.epoch)):
                            return
                    elif isinstance(item, tuple):  # raw group
                        buf, s, e = item
                        if pend is not None and pend[0] is not buf:
                            if not flush():
                                return
                        if pend is None:
                            pend = (buf, seq, [s], [e])
                        else:
                            pend[2].append(s)
                            pend[3].append(e)
                    else:  # line chunk
                        if not flush():
                            return
                        lines = [c[0] for c in item]
                        weights = [c[1] for c in item]
                        if not put_work(("lines", seq, lines, weights)):
                            return
                if not flush():
                    return
            except BaseException as e:
                reader_err.append(e)
            finally:
                for _ in range(n_workers):
                    if not put_mp(work, None):
                        break

        rt = threading.Thread(target=reader, daemon=True)
        rt.start()
        expect_done = n_workers
        next_seq = 0
        held: dict = {}
        try:
            while expect_done > 0:
                if reader_err:
                    raise reader_err.pop()
                observe_depth(h_out, out)
                try:
                    msg = out.get(timeout=0.1)
                except _q.Empty:
                    dead = [p for p in procs
                            if p.exitcode not in (None, 0)]
                    if dead:
                        raise RuntimeError(
                            f"parse worker died (exitcode "
                            f"{dead[0].exitcode})"
                        )
                    continue
                kind = msg[0]
                if kind == "done":
                    expect_done -= 1
                    # Trailing span shipment: worker events that ended
                    # after its last batch (e.g. the final window span)
                    # — and the worker's final quality-sketch delta
                    # (batches sketched since its last periodic ship).
                    if len(msg) > 1:
                        tracer.add_raw(msg[1])
                    if (
                        len(msg) > 2 and msg[2] is not None
                        and self._quality is not None
                    ):
                        self._quality.absorb(msg[2])
                    continue
                if kind == "err":
                    raise msg[1]
                if kind == "mark":
                    seq, obj = msg[1], EpochEnd(msg[2])
                else:  # ("batch", seq, shm, meta, trunc, note, t,
                    #    spans, sketch_delta)
                    seq = msg[1]
                    obj = procpool.attach_batch(spec, msg[2], msg[3])
                    self._trunc_extra += msg[4]
                    self._log_worker_note(msg[5])
                    # Workers can't reach this process's registry; they
                    # ship their parse wall time with each batch instead
                    # — and their trace spans and quality-sketch deltas
                    # the same way (deltas every SKETCH_SHIP_EVERY
                    # batches; None in between).
                    self._t_parse.observe(msg[6])
                    tracer.add_raw(msg[7])
                    if (
                        len(msg) > 8 and msg[8] is not None
                        and self._quality is not None
                    ):
                        self._quality.absorb(msg[8])
                if not self.ordered:
                    self._last_seq = seq
                    yield obj
                    continue
                held[seq] = obj
                while next_seq in held:
                    self._last_seq = next_seq
                    yield held.pop(next_seq)
                    next_seq += 1
            if reader_err:
                raise reader_err.pop()
            for seq in sorted(held):
                self._last_seq = seq
                yield held[seq]
        finally:
            stop.set()
            # Reap the pool first (workers give up their blocked puts
            # within one poll period; their queue feeders flush on
            # exit), THEN drain: every shipped-but-unconsumed segment is
            # guaranteed visible by the time the workers are gone, so
            # none outlives the run in /dev/shm.  A terminated straggler
            # can still lose in-flight messages — the worker-side emit()
            # fallback covers its own unsent segment.
            rt.join()
            for p in procs:
                p.join(timeout=5)
            for p in procs:
                if p.is_alive():  # pragma: no cover - stuck worker
                    p.terminate()
                    p.join(timeout=5)
            try:
                while True:
                    msg = out.get_nowait()
                    if msg and msg[0] == "batch":
                        procpool.discard_segment(msg[2])
            except _q.Empty:
                pass
            if ring is not None:
                ring.destroy()
                self._g_ring_bytes.set(0)  # allocation gone
            qs = (work, out) if ring_free is None else (
                work, out, ring_free
            )
            for q in qs:
                q.close()
                q.cancel_join_thread()
            # Backstop for segments a crashed worker created but never
            # shipped: everything this pipeline tagged is garbage now.
            leaked = procpool.sweep_segments(shm_tag)
            if leaked:
                log.warning(
                    "swept %d orphaned /dev/shm segment(s) tagged %s "
                    "(a parse worker died mid-ship)", leaked, shm_tag,
                )

    def _log_worker_note(self, note) -> None:
        """Mirror thread-mode sort_meta degradation logging for notes a
        process worker shipped back with a batch."""
        if note is None:
            return
        kind, msg = note
        if kind == "oor":
            self._oor_counter.add(1)
            log.warning(
                "host sort_meta rejected a batch (%s); the input data or "
                "vocabulary_size is wrong — the device-sort path will "
                "silently drop updates for ids >= vocabulary_size", msg,
            )
        elif kind == "sketch_failed":
            if not getattr(self, "_sketch_warned", False):
                self._sketch_warned = True
                log.warning(
                    "quality sketching failed in a parse worker (%s); "
                    "that worker's drift feed is disabled, training "
                    "continues", msg,
                )
        elif not self._sort_meta_warned:
            self._sort_meta_warned = True
            log.warning(
                "host sort_meta failed in a parse worker (%s); those "
                "workers fall back to device sort", msg,
            )


def stack_batches(
    batches: Sequence[libsvm.Batch], out: Optional[libsvm.Batch] = None
) -> libsvm.Batch:
    """Stack K parsed batches into one [K, batch, ...] super-batch.

    The stacked Batch feeds the K-step scan train step (train.loop.
    make_scan_train_step), which consumes the leading axis one step at a
    time.  Host-computed ``sort_meta`` rides along leaf-wise when EVERY
    batch carries it (shapes agree by construction: all meta derives from
    the same (batch_size * max_features, CHUNK, TILE, vocab)); a group
    with any meta-less batch drops it entirely — the device-sort path
    handles meta-less batches, and a per-step mix would change the scan
    xs pytree mid-run.

    ``out`` (a Batch of preallocated [K, ...] arrays, sort_meta arrays
    included iff this group stacks meta) receives the stacked data in
    place and is returned — the transfer stage's staging-buffer pool
    recycles these so steady-state stacking allocates nothing.  Callers
    passing ``out`` must not reuse the buffers until the consumer is
    done with the returned Batch.
    """
    if not batches:
        raise ValueError("stack_batches needs at least one batch")
    if len(batches) == 1:  # K=1 (or an epoch tail of 1): zero-copy views
        b = batches[0]
        meta = b.sort_meta
        if meta is not None:
            meta = type(meta)(*(x[None] for x in meta))
        return libsvm.Batch(
            b.labels[None], b.ids[None], b.vals[None], b.fields[None],
            b.weights[None], sort_meta=meta,
        )
    metas = [b.sort_meta for b in batches]
    has_meta = all(m is not None for m in metas)
    if out is None:
        core = (
            np.stack([b.labels for b in batches]),
            np.stack([b.ids for b in batches]),
            np.stack([b.vals for b in batches]),
            np.stack([b.fields for b in batches]),
            np.stack([b.weights for b in batches]),
        )
        meta = None
        if has_meta:
            meta = type(metas[0])(
                *(np.stack(cols) for cols in zip(*metas))
            )
        return libsvm.Batch(*core, sort_meta=meta)
    for name in ("labels", "ids", "vals", "fields", "weights"):
        np.stack(
            [getattr(b, name) for b in batches], out=getattr(out, name)
        )
    if has_meta:
        if out.sort_meta is None:
            raise ValueError("out has no sort_meta arrays for this group")
        for cols, dst in zip(zip(*metas), out.sort_meta):
            np.stack(cols, out=dst)
        return out
    return out._replace(sort_meta=None)


class _StagingPool:
    """Reusable pre-allocated host staging buffers for super-batch
    stacking (single-threaded: only the transfer thread touches it).

    Steady-state stacking writes into recycled [K, ...] arrays instead
    of allocating ~super-batch bytes per dispatch.  A buffer is only
    recycled after the device transfer that read from it is COMPLETE:
    retired buffers queue behind their device super-batch and the pool
    blocks on the oldest transfer (``jax.block_until_ready``, resolved
    lazily so the data layer stays importable without jax) before
    handing its buffers out again.  By the time super-batch n + depth
    stacks, transfer n has long finished, so the wait is ~0 in steady
    state.  Keyed by (K, batch shape, has-meta) — epoch tails at
    K' < K get their own small slot.
    """

    def __init__(self, limit: int, reuse_counter=None, tracer=None,
                 bytes_gauge=None):
        self._free: dict = {}  # key -> [Batch bufset, ...]
        self._inflight: deque = deque()  # (dev, key, bufset)
        self._limit = max(1, limit)
        self._c_reuse = (
            reuse_counter if reuse_counter is not None
            else obs.NULL.counter("")
        )
        self._tracer = tracer if tracer is not None else obs.NULL_TRACER
        # Ledger: bytes of staging buffers this pool OWNS (free +
        # in-flight).  Alias mode hands ownership to the zero-copy
        # device array, so those bytes leave the ledger at retire.
        self._bytes = 0
        self._g_bytes = (
            bytes_gauge if bytes_gauge is not None
            else obs.NULL.gauge("")
        )
        # Whether put_fn's device arrays ALIAS the host staging buffers
        # (None = not yet probed).  jax.device_put on a single-device
        # CPU mesh is zero-copy: the "device" array shares memory with
        # the numpy buffer, so recycling the buffer would rewrite
        # super-batches still queued for dispatch.  The first retire()
        # probes once; aliasing permanently disables reuse (fresh
        # allocations per group — correct, just not recycled).
        self._alias_mode: Optional[bool] = None

    @staticmethod
    def _key(group):
        b = group[0]
        has_meta = all(x.sort_meta is not None for x in group)
        return (len(group), b.ids.shape, has_meta)

    @staticmethod
    def _alloc(group, has_meta):
        k = len(group)
        b = group[0]

        def empty(x):
            return np.empty((k,) + x.shape, x.dtype)

        meta = None
        if has_meta:
            meta = type(b.sort_meta)(*(empty(x) for x in b.sort_meta))
        return libsvm.Batch(
            empty(b.labels), empty(b.ids), empty(b.vals),
            empty(b.fields), empty(b.weights), sort_meta=meta,
        )

    @staticmethod
    def _wait(dev) -> None:
        """Block until a shipped super-batch's H2D transfers finished —
        only then are its staging buffers safe to overwrite.  jax is
        resolved lazily (and only if already imported): a numpy-only
        put_fn has nothing to wait for."""
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return
        try:
            jax.block_until_ready(dev)
        except Exception:  # pragma: no cover - non-array put results
            pass

    def acquire(self, group) -> libsvm.Batch:
        key = self._key(group)
        if len(self._inflight) >= self._limit:
            # Block-on-oldest-transfer before recycling: the span makes
            # the ROADMAP question "is the prefetcher thread blocked on
            # staging reuse?" directly visible in a trace.
            with self._tracer.span(
                "prefetch.staging_wait",
                args={"inflight": len(self._inflight)},
            ):
                while len(self._inflight) >= self._limit:
                    dev, k2, bufs = self._inflight.popleft()
                    self._wait(dev)
                    self._free.setdefault(k2, []).append(bufs)
        free = self._free.get(key)
        if free:
            self._c_reuse.add(1)
            return free.pop()
        bufs = self._alloc(group, key[2])
        self._bytes += _batch_nbytes(bufs)
        self._g_bytes.set(self._bytes)
        return bufs

    @staticmethod
    def _probe_alias(dev, bufs: libsvm.Batch) -> bool:
        """True when any leaf of ``dev`` may share memory with a staging
        buffer — the zero-copy device_put case where reuse would corrupt
        in-flight data.  Only probed on the CPU backend (accelerator puts
        always copy across the host/device boundary); errs toward True
        (no reuse) on any surprise.

        Multi-device leaves are unconditionally treated as aliasing: the
        CPU client may zero-copy individual shards at the PJRT-buffer
        level, but ``np.asarray`` on a sharded array assembles a fresh
        copy, so ``np.shares_memory`` cannot observe the alias from
        Python.  Recycling under a (1, N) mesh provably rewrites queued
        super-batches (rare bimodal loss flips under host load), so the
        probe refuses reuse rather than trusting an unverifiable copy."""
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return False
        try:
            if jax.default_backend() != "cpu":
                return False
            host = [x for x in bufs[:5]]
            if bufs.sort_meta is not None:
                host.extend(bufs.sort_meta)
            for leaf in jax.tree_util.tree_leaves(dev):
                if isinstance(leaf, np.ndarray):
                    # A shipped object retaining host numpy (e.g. host
                    # sort_meta) references the staging buffers directly.
                    if any(np.shares_memory(leaf, h) for h in host):
                        return True
                elif isinstance(leaf, jax.Array):
                    if len(leaf.sharding.device_set) > 1:
                        return True
                    a = np.asarray(leaf)
                    if any(np.shares_memory(a, h) for h in host):
                        return True
        except Exception:  # pragma: no cover - be safe, not fast
            return True
        return False

    def retire(self, dev, group, bufs: libsvm.Batch) -> None:
        """Queue the buffers behind their device transfer for reuse."""
        if self._alias_mode is None:
            self._alias_mode = self._probe_alias(dev, bufs)
            if self._alias_mode:
                log.info(
                    "staging-buffer reuse disabled: device_put may alias "
                    "host memory on this backend (CPU zero-copy; "
                    "unverifiable for sharded arrays), so recycling "
                    "would corrupt in-flight super-batches; stacking "
                    "allocates fresh buffers"
                )
        if self._alias_mode:
            # The device array owns this memory now — it left the pool.
            self._bytes = max(0, self._bytes - _batch_nbytes(bufs))
            self._g_bytes.set(self._bytes)
            return
        self._inflight.append((dev, self._key(group), bufs))


class DevicePrefetcher:
    """Double-buffered transfer stage between BatchPipeline and the loop.

    A background thread pulls parsed batches from ``source``, stacks
    ``steps_per_dispatch`` of them into a [K, ...] super-batch
    (:func:`stack_batches`, carrying host ``sort_meta``), and ships it to
    the device with ``put_fn`` (shard + device_put; the dispatch is
    async, so super-batch n+1's H2D copies overlap super-batch n's
    training).  At most ``depth`` shipped super-batches wait in the
    bounded output queue — host/device memory for staged input stays
    capped at ~(depth + 1) super-batches.  The source's tail yields a
    short super-batch at K' = leftover.

    Iterating yields ``(device_super_batch, n_batches)``.  An
    :class:`EpochEnd` marker from the source flushes the pending group
    (so super-batches never span epochs — the epoch tail dispatches at
    K' = leftover, exactly like before) and is forwarded verbatim.
    A :class:`SuperBatch` from the source (the pre-stacked epoch cache)
    skips ``stack_batches`` entirely and ships as-is; with
    ``staging=True`` the stacking path writes into a small pool of
    recycled pre-allocated host buffers (safe only when ``put_fn``
    copies out of host memory, as device_put does).
    Exceptions from the source or the transfer re-raise in the consumer;
    ``close()`` cancels the output queue (waking a blocked producer
    immediately — no poll latency) and joins the thread; it is
    idempotent (iteration calls it on exit).
    """

    def __init__(self, source, steps_per_dispatch: int, put_fn,
                 depth: int = 2, telemetry: Optional[obs.Telemetry] = None,
                 staging: bool = False,
                 tracer: Optional[obs.Tracer] = None,
                 ship_fn=None):
        self._k = max(1, steps_per_dispatch)
        self._put_fn = put_fn
        # Optional fused stack+H2D: ship_fn takes the raw batch group
        # and returns the device super-batch in ONE transfer (parallel.
        # mesh.FusedShipper), or None to decline — then the classic
        # stack_batches + put_fn path below runs unchanged.
        self._ship_fn = ship_fn
        # Transfer-stage instruments: stack vs H2D vs output-block time.
        # out_block large = the device is the bottleneck (healthy);
        # out_q_depth pinned low with the trainer starving = ingest-bound.
        tel = telemetry if telemetry is not None else obs.NULL
        self._out = _ClosableQueue(
            max(1, depth), hist=tel.depth_hist("prefetch.out_q_depth")
        )
        self._t_stack = tel.timer("prefetch.stack")
        self._t_put = tel.timer("prefetch.device_put")
        self._t_out_block = tel.timer("prefetch.out_block")
        self._c_super = tel.counter("prefetch.super_batches")
        self._c_prestack = tel.counter("prefetch.prestack_hits")
        self._c_fused = tel.counter("prefetch.fused_ships")
        # Trace correlation: this stage ASSIGNS the super-batch id (sb
        # = emission order, which the bounded FIFO output queue carries
        # unchanged to the consumer, so the train loop's own dispatch
        # counter names the same super-batch) and carries the delivered
        # batch index forward (counted here in source order — the same
        # order the pipeline's ``ingest.deliver`` points counted).
        self._tracer = tracer if tracer is not None else obs.NULL_TRACER
        self._sb_id = 0
        self._batch_idx = 0
        # Staging-buffer reuse is opt-in: it requires put_fn to COPY out
        # of the host arrays (device_put does; an identity put_fn, as
        # tests use, hands the arrays downstream, where a recycled
        # buffer would be overwritten under the consumer).
        self._pool = (
            _StagingPool(
                max(1, depth) + 1,
                reuse_counter=tel.counter("prefetch.staging_reuse"),
                tracer=self._tracer,
                bytes_gauge=tel.gauge("prefetch.staging_bytes"),
            )
            if staging else None
        )
        self._thread = threading.Thread(
            target=self._run, args=(iter(source),), daemon=True
        )
        self._thread.start()

    def _run(self, it):
        try:
            self._tracer.name_thread("prefetch")
            group: list = []
            while True:
                batch = next(it, _SENTINEL)
                if batch is _SENTINEL:
                    break
                if isinstance(batch, EpochEnd):
                    if group:
                        if not self._emit(group):
                            return
                        group = []
                    if not self._out.put(batch):
                        return
                    continue
                if isinstance(batch, SuperBatch):
                    # Pre-stacked fast path (cache_prestacked replay —
                    # and epoch 0, which the pipeline stacks once at
                    # group boundaries): no stack here, straight to the
                    # device.  A pending partial group (mid-group
                    # resume tail) flushes first to keep order.
                    if group:
                        if not self._emit(group):
                            return
                        group = []
                    if not self._emit_prestacked(batch):
                        return
                    continue
                group.append(batch)
                if len(group) == self._k:
                    if not self._emit(group):
                        return
                    group = []
            if group:
                self._emit(group)  # epoch tail: K' = leftover
        except BaseException as e:  # surfaces in the consumer
            self._out.put(_Error(e))
        finally:
            self._out.put(_SENTINEL)
            # Deterministically release the source's own resources (a
            # BatchPipeline generator holds parser threads + queues).
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # pragma: no cover - best effort
                    pass

    def _emit(self, group) -> bool:
        sb_id, batch0 = self._sb_id, self._batch_idx
        self._sb_id += 1
        self._batch_idx += len(group)
        if self._ship_fn is not None:
            # Fused stack+H2D: one staging copy, ONE device transfer,
            # on-device carve.  Timed under the H2D instrument (it IS
            # the transfer; there is no separate stack phase to time).
            with self._t_put.time(), obs.trace_span("tffm:h2d"), \
                    self._tracer.span(
                        "prefetch.fused_ship",
                        args={"sb": sb_id, "batch0": batch0,
                              "n": len(group)},
                        flow=("s", f"sb{sb_id}"),
                    ):
                dev = self._ship_fn(group)
            if dev is not None:
                self._c_fused.add(1)
                self._c_super.add(1)
                t0 = time.perf_counter()
                ok = self._out.put((dev, len(group)))
                self._t_out_block.observe(time.perf_counter() - t0)
                return ok
        bufs = None
        with self._t_stack.time(), obs.trace_span("tffm:stack"), \
                self._tracer.span(
                    "prefetch.stack",
                    args={"sb": sb_id, "batch0": batch0, "n": len(group)},
                    flow=("s", f"sb{sb_id}"),
                ):
            if self._pool is not None and len(group) > 1:
                bufs = self._pool.acquire(group)
                stacked = stack_batches(group, out=bufs)
            else:
                stacked = stack_batches(group)
        with self._t_put.time(), obs.trace_span("tffm:h2d"), \
                self._tracer.span(
                    "prefetch.h2d", args={"sb": sb_id},
                    flow=("t", f"sb{sb_id}"),
                ):
            dev = self._put_fn(stacked)
        if bufs is not None:
            self._pool.retire(dev, group, bufs)
        self._c_super.add(1)
        t0 = time.perf_counter()
        ok = self._out.put((dev, len(group)))
        self._t_out_block.observe(time.perf_counter() - t0)
        return ok

    def _emit_prestacked(self, sb: SuperBatch) -> bool:
        """Ship an already-stacked group: zero stacking work, one put."""
        sb_id, batch0 = self._sb_id, self._batch_idx
        self._sb_id += 1
        self._batch_idx += sb.n
        with self._t_put.time(), obs.trace_span("tffm:h2d"), \
                self._tracer.span(
                    "prefetch.h2d",
                    args={"sb": sb_id, "batch0": batch0, "n": sb.n,
                          "prestacked": True},
                    flow=("s", f"sb{sb_id}"),
                ):
            dev = self._put_fn(sb.batch)
        self._c_super.add(1)
        self._c_prestack.add(1)
        t0 = time.perf_counter()
        ok = self._out.put((dev, sb.n))
        self._t_out_block.observe(time.perf_counter() - t0)
        return ok

    def __iter__(self):
        try:
            while True:
                item = self._out.get()
                if item is _SENTINEL or item is _CANCELLED:
                    return
                if isinstance(item, _Error):
                    raise item.exc
                yield item
        finally:
            self.close()

    def close(self):
        """Stop the transfer thread and reap it (idempotent)."""
        self._out.cancel()
        self._thread.join()


def _make_parser(cfg: FmConfig):
    """Returns (native_parser, (lines, weights) -> Batch).

    The C++ parser is the only production parser: a failed build or
    load raises here instead of handing over to the pure-Python
    ``data.libsvm`` parser (the tests' oracle), which is orders of
    magnitude slower and would also switch off fast_ingest and the
    host sort metadata without a trace in the result."""
    from fast_tffm_tpu.data import native as _native

    # Parallelism comes from the pipeline's thread_num WORKERS (each
    # parses a different group with the GIL released); internal C++
    # threads on top would oversubscribe cores (thread_num^2) and a
    # per-group fork/join barrier pipelines worse than independent
    # groups anyway.
    native = _native.NativeParser(
        vocabulary_size=cfg.vocabulary_size,
        max_features=cfg.max_features,
        hash_feature_id=cfg.hash_feature_id,
        field_num=cfg.field_num,
        num_threads=1,
    )

    def parse(lines, weights):
        return native.parse_batch(lines, cfg.batch_size, weights)

    return native, parse
