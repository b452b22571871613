"""Request-batching front end: coalesce concurrent requests into
fixed-shape microbatches.

One scoring dispatch amortizes over every example in it, so serving
throughput lives or dies on batch fill — but a request must not wait
forever for company.  :class:`ServeBatcher` is the standard tradeoff
dial: concurrent requests land in a bounded queue (the ingest layer's
``_ClosableQueue``, depth-histogrammed as ``serve.queue_depth``), and a
single dispatcher thread coalesces them into one microbatch until
either the largest ladder rung fills or ``max_batch_wait_ms`` expires
— whichever comes first.  An idle server costs a lone request at most
the deadline; a saturated server fills rungs and the deadline never
fires.

The dispatcher fills its own recycled per-rung staging buffers
directly (one row copy per example, no per-request concatenation —
the prefetcher's staging-pool discipline applied to requests),
LAUNCHES one scorer dispatch, and later LANDS it: reads the scores
back, splits them per request and releases the waiting client threads.
Because launches are serial and the scorer resolves its model reference
once per launch, a hot swap can never interleave old and new params
inside one microbatch.

One group in flight.  The two halves of a dispatch
(``scorer.launch_rung`` / ``scorer.read_rung``) need not be adjacent:
with group n launched, the dispatcher looks at what the queue holds
(without taking or waiting).  If that closes a group at once — it fills
the max rung, or holds a request that does not fit behind those before
it — group n+1 is coalesced, filled and launched FIRST and n is landed
while the device runs n+1.  If not, n is landed first and only then
does the dispatcher wait (batch deadline or empty queue): an answer that
is ready never waits behind a wait.  At most one group is ever in
flight when the dispatcher turns to the queue, replies leave in launch
order, a failure in one group's read fails that group alone, and an
oversized lone request (scored by ``scorer.score()``, blocking) and
``close()`` land the group in flight first.  The staging buffers come
in pairs a rung, taken in turn: the set filled last may still be feeding
the group in flight.

Instruments (all ``serve.*``, documented in OBSERVABILITY.md):
``requests`` / ``examples`` / ``batches`` counters, ``overlapped``
(groups launched while another was in flight; over ``batches`` it is the
share of dispatches whose device time was hidden), the ``latency``
timer (enqueue -> scores delivered; p50/p95/p99 ride every snapshot),
the ``queue_wait`` timer (enqueue -> picked, every request), the
``batch_fill`` gauge (cumulative filled/dispatched slots), and the
``queue_depth`` histogram.

The dispatcher's work is tiled by phases, each an ``obs.Phase`` (a
``serve.<phase>`` timer and a ``tffm:serve.<phase>`` annotation on the
device trace's clock): ``coalesce`` (first request picked -> group
closed: the batcher's own deliberate wait), ``fill`` (group -> staging
buffers), the scorer's ``launch`` and — for this group or, with one in
flight, the group before — ``readback``, ``deliver`` (scores split,
every waiter released) and ``quality`` (the skew-sketch fold).
The wait on an empty queue carries no annotation: a span over a wait
for another thread would cover whole idle gaps of the device and hide
what the working thread did.

Distributed tracing: a request carrying a request id (``rid``, from
the ``X-Request-Id`` header or the binary frame's trailer on a SAMPLED
request) gets per-request spans — ``serve.queue_wait`` (enqueue ->
picked by the dispatcher), ``serve.coalesce`` (picked -> its group
closed) and ``serve.dispatch`` (group closed -> its scores in host
memory: fill, the rung's two halves and, with a group in flight, the
landing of the group before; ``launch_ms`` / ``readback_ms`` of ITS
group in the args and a flow step on the rid) — emitted AFTER the
dispatch from the timestamps the phases already took.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import numpy as np

from fast_tffm_tpu import obs
from fast_tffm_tpu.data.pipeline import (
    _CANCELLED, _TIMEOUT, _ClosableQueue,
)
from fast_tffm_tpu.obs.trace import NULL_TRACER

log = logging.getLogger(__name__)

__all__ = ["ScoreRequest", "ServeBatcher"]


class ScoreRequest:
    """One in-flight scoring request (a future the client waits on).

    ``rid`` is the distributed-trace request id (None = unsampled);
    ``t_picked`` is stamped by the dispatcher when the request leaves
    the queue (``serve.queue_wait`` = ``t_picked - t0``, every request).

    ``on_done`` is the scratch-release hook for pooled parse buffers
    (serve/textparse.py): the batcher fires it exactly once when it is
    DONE READING ``ids``/``vals``/``fields`` — after the microbatch
    copy and the quality fold on the success path, after stamping the
    error on every failure path.  The client's ``result()`` wait is
    NOT the release point: a client timeout abandons a request the
    dispatcher still holds, and releasing then would let the pool hand
    the buffer to a new request while the dispatcher reads it."""

    __slots__ = ("ids", "vals", "fields", "n", "event", "scores",
                 "error", "t0", "rid", "t_picked", "on_done")

    def __init__(self, ids, vals, fields, rid=None, on_done=None):
        self.ids = ids
        self.vals = vals
        self.fields = fields
        self.n = len(ids)
        self.event = threading.Event()
        self.scores: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.t0 = time.perf_counter()
        self.rid = rid
        self.t_picked: Optional[float] = None
        self.on_done = on_done

    def finish(self) -> None:
        """Fire ``on_done`` exactly once (swap-to-None makes repeated
        calls from overlapping failure paths safe)."""
        cb, self.on_done = self.on_done, None
        if cb is not None:
            try:
                cb()
            except Exception as e:  # noqa: BLE001 - release must not
                log.warning("on_done release hook failed: %s", e)


class _Group:
    """One closed group on its way through the dispatcher: filled and
    launched (``flight``), later landed."""

    __slots__ = ("reqs", "total", "t_closed", "rung", "slots", "flight",
                 "launch_s", "readback_s")

    def __init__(self, reqs, total: int, t_closed: float):
        self.reqs = reqs
        self.total = total
        self.t_closed = t_closed
        self.flight = None


class ServeBatcher:
    """Coalesce requests into microbatches under a latency deadline,
    one group in flight (module docstring)."""

    def __init__(self, scorer, max_batch_wait_ms: float = 2.0,
                 queue_size: int = 1024, telemetry=None, tracer=None,
                 slo=None, quality=None):
        self._scorer = scorer
        self._wait_s = max(0.0, float(max_batch_wait_ms)) / 1e3
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self._slo = slo
        # Training→serving skew monitor (obs.ServeSkewMonitor, None =
        # quality off): the dispatcher folds every scored request's
        # feature arrays + served scores into the live traffic sketch
        # AFTER the scores are delivered — pure observation on the
        # dispatcher thread, so responses are byte-identical with it
        # on or off (pinned by test).
        self._quality = quality
        tel = telemetry if telemetry is not None else obs.NULL
        self._c_requests = tel.counter("serve.requests")
        self._c_examples = tel.counter("serve.examples")
        self._c_batches = tel.counter("serve.batches")
        self._c_overlapped = tel.counter("serve.overlapped")
        self._t_latency = tel.timer("serve.latency")
        self._t_queue_wait = tel.timer("serve.queue_wait")
        self._t_coalesce = tel.timer("serve.coalesce")
        self._t_fill = tel.timer("serve.fill")
        self._t_deliver = tel.timer("serve.deliver")
        self._t_quality = tel.timer("serve.quality")
        # The scorer's two phase timers (same registry, same names): an
        # OVERSIZED request's serve.dispatch span reads its chunks' share
        # as the difference of their totals (nothing is in flight then;
        # 0 with telemetry off).  A rung's group reads its own flight.
        self._t_launch = tel.timer("serve.launch")
        self._t_readback = tel.timer("serve.readback")
        self._g_fill = tel.gauge("serve.batch_fill")
        # Live in-flight count (accepted, scores not yet delivered):
        # the replica-side load signal the router's P2C dispatch and
        # the overload discipline reason about.
        self._g_inflight = tel.gauge("serve.inflight")
        self._q = _ClosableQueue(
            queue_size, hist=tel.depth_hist("serve.queue_depth")
        )
        # The batcher's OWN recycled per-rung staging buffers, two sets
        # a rung (_pool).  It must not borrow the scorer's pools: those
        # are guarded by the scorer's dispatch lock, and the dispatcher
        # fills buffers BEFORE taking that lock — sharing them would let
        # a direct scorer.score() caller race the fill.
        self._pools: dict = {}
        # Fill accounting (dispatcher thread only): real examples vs
        # padded slots over every dispatched rung.
        self._slots = 0
        self._filled = 0
        # Outstanding requests, so close() can fail the ones a queue
        # cancel() discards instead of leaving clients blocked forever.
        self._outstanding: set = set()
        self._out_lock = threading.Lock()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="tffm-serve-batcher", daemon=True
        )
        self._thread.start()

    # -- client side ---------------------------------------------------

    def submit(self, ids, vals, fields=None, rid=None,
               on_done=None) -> ScoreRequest:
        """Enqueue ``[n, max_features]`` arrays; returns the request
        future.  Raises RuntimeError once the batcher is closed.
        ``on_done`` (optional) fires exactly once when the batcher no
        longer reads the arrays — including on every rejection path of
        this call, so a pooled caller never leaks a lease.  NOTE the
        ``ascontiguousarray`` casts are no-copy for the parse pool's
        row views (C-contiguous slices of the right dtype), so the
        arrays the dispatcher reads ARE the pooled buffers."""
        req = ScoreRequest(
            np.ascontiguousarray(ids, np.int32),
            np.ascontiguousarray(vals, np.float32),
            (np.ascontiguousarray(fields, np.int32)
             if fields is not None else None),
            rid=rid,
            on_done=on_done,
        )
        with self._out_lock:
            if self._closed:
                req.finish()
                raise RuntimeError("ServeBatcher is closed")
            self._outstanding.add(req)
            self._g_inflight.set(len(self._outstanding))
        if not self._q.put(req):
            with self._out_lock:
                self._outstanding.discard(req)
                self._g_inflight.set(len(self._outstanding))
            req.finish()
            raise RuntimeError("ServeBatcher is closed")
        self._c_requests.add()
        return req

    @property
    def inflight(self) -> int:
        """Requests accepted but not yet answered (live load)."""
        with self._out_lock:
            return len(self._outstanding)

    def result(self, req: ScoreRequest,
               timeout: float = 30.0) -> np.ndarray:
        """Block until the request's scores arrive (or raise)."""
        if not req.event.wait(timeout):
            raise TimeoutError(
                f"scoring request ({req.n} examples) timed out after "
                f"{timeout}s"
            )
        if req.error is not None:
            raise req.error
        return req.scores

    def score(self, ids, vals, fields=None, timeout: float = 30.0,
              rid=None, on_done=None) -> np.ndarray:
        """submit + result in one call (the HTTP handler's path)."""
        return self.result(
            self.submit(ids, vals, fields, rid=rid, on_done=on_done),
            timeout,
        )

    @property
    def batch_fill(self) -> float:
        return self._filled / self._slots if self._slots else 0.0

    def _pool(self, b: int):
        """The staging buffers to fill for rung ``b``: the set NOT
        handed out last.  A launch returns while the transfer may still
        read its numpy arguments, and the group before may be in flight
        on the other set; with at most one group in flight, the set this
        returns was read back already."""
        pair = self._pools.get(b)
        if pair is None:
            F = self._scorer.cfg.max_features
            pair = self._pools[b] = [
                (
                    np.zeros((b, F), np.int32),
                    np.zeros((b, F), np.float32),
                    np.zeros((b, F), np.int32),
                )
                for _ in range(2)
            ]
        pair.reverse()
        return pair[0]

    # -- dispatcher thread ---------------------------------------------

    def _closes_at_once(self, pending: Optional[ScoreRequest]) -> bool:
        """Whether what is off the queue (``pending``) and on it closes
        a group with no wait: it fills the max rung (an oversized lone
        request does), or holds a request that does not fit behind
        those before it.  Looks, takes nothing."""
        max_b = self._scorer.max_rung
        total = 0
        waiting = self._q.snapshot()
        if pending is not None:
            waiting = (pending,) + waiting
        for req in waiting:
            if total and total + req.n > max_b:
                return True
            total += req.n
            if total >= max_b:
                return True
        return False

    def _run(self) -> None:
        max_b = self._scorer.max_rung
        pending: Optional[ScoreRequest] = None
        flying: Optional[_Group] = None  # launched, not yet read back
        while True:
            if flying is not None and not self._closes_at_once(pending):
                # Nothing to launch without waiting: the answer that is
                # ready leaves before the dispatcher waits for company.
                self._land(flying)
                flying = None
            # The wait on an empty queue: no annotation (module docstring).
            first = pending if pending is not None else self._q.get()
            if first is _CANCELLED:
                break
            with obs.Phase(self._t_coalesce, "tffm:serve.coalesce") as ph:
                if pending is None:
                    first.t_picked = ph.t0
                pending = None
                reqs = [first]
                total = first.n
                deadline = time.monotonic() + self._wait_s
                while total < max_b:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    nxt = self._q.get(timeout=remaining)
                    if nxt is _TIMEOUT:
                        break
                    if nxt is _CANCELLED:
                        break
                    nxt.t_picked = time.perf_counter()
                    if total + nxt.n > max_b:
                        # Doesn't fit this rung: dispatch what we have and
                        # seed the next microbatch (keeps every coalesced
                        # group within one dispatch).
                        pending = nxt
                        break
                    reqs.append(nxt)
                    total += nxt.n
                ph.set(reqs=len(reqs), n=total)
            flying = self._dispatch(_Group(reqs, total, ph.t1), flying)
        # Queue cancelled: land the group in flight, then fail whatever
        # is still outstanding (items the cancel discarded AND a pending
        # carry-over).
        if flying is not None:
            self._land(flying)
        self._fail_outstanding(RuntimeError("ServeBatcher closed"))

    def _trace_request(self, g: ScoreRequest, group: _Group,
                       t_scored: float) -> None:
        """Emit one sampled request's replica-side spans from the
        phases' timestamps (queue wait -> coalesce -> dispatch).  The
        flow step on the rid links the chain to the router's proxy
        span and the handler's respond span.  A request carried over
        from the group before was picked before that group closed: its
        coalesce span covers that dispatch too."""
        self._tracer.emit(
            "serve.queue_wait", g.t0, g.t_picked - g.t0,
            args={"rid": g.rid},
        )
        self._tracer.emit(
            "serve.coalesce", g.t_picked, group.t_closed - g.t_picked,
            args={"rid": g.rid, "group_n": group.total},
        )
        self._tracer.emit(
            "serve.dispatch", group.t_closed, t_scored - group.t_closed,
            args={"rid": g.rid, "rung": group.rung, "n": group.total,
                  "launch_ms": round(1e3 * group.launch_s, 4),
                  "readback_ms": round(1e3 * group.readback_s, 4)},
            flow=("t", g.rid),
        )

    def _dispatch(self, group: _Group,
                  flying: Optional[_Group]) -> Optional[_Group]:
        """Fill and launch ``group`` and land ``flying``, the group
        launched before it (if any), while the device runs this one.
        Returns the group now in flight: ``group``, or None if it failed
        or was oversized (scored in blocking chunks behind ``flying``
        and landed here)."""
        scorer = self._scorer
        reqs, total = group.reqs, group.total
        scores = None  # an oversized request's, scored here in chunks
        try:
            with obs.Phase(self._t_fill, "tffm:serve.fill") as ph:
                qwait = 0.0
                for g in reqs:
                    wait = g.t_picked - g.t0
                    self._t_queue_wait.observe(wait)
                    qwait += wait
                oversized = len(reqs) == 1 and total > scorer.max_rung
                if oversized:
                    # One oversized request: the scorer chunks and
                    # fills it itself ...
                    group.rung = scorer.max_rung
                else:
                    group.rung = b = scorer.rung_for(total)
                    bi, bv, bf = self._pool(b)
                    pos = 0
                    any_fields = any(g.fields is not None for g in reqs)
                    for g in reqs:
                        bi[pos:pos + g.n] = g.ids
                        bv[pos:pos + g.n] = g.vals
                        if any_fields:
                            bf[pos:pos + g.n] = (
                                g.fields if g.fields is not None else 0
                            )
                        pos += g.n
                    if pos < b:
                        bi[pos:] = 0
                        bv[pos:] = 0.0
                        if any_fields:
                            bf[pos:] = 0
                ph.set(reqs=len(reqs), n=total, rung=group.rung,
                       qwait_us=int(1e6 * qwait))
            if oversized:
                if flying is not None:
                    self._land(flying)
                    flying = None
                launch_s = self._t_launch.total_s
                readback_s = self._t_readback.total_s
                req = reqs[0]
                scores = scorer.score(req.ids, req.vals, req.fields)
                # ... and owns the matching slot accounting.
                group.slots = scorer.slots_for(total)
                group.launch_s = self._t_launch.total_s - launch_s
                group.readback_s = self._t_readback.total_s - readback_s
            else:
                group.flight = scorer.launch_rung(
                    bi, bv, bf if any_fields else None, b,
                    inflight=int(flying is not None),
                )
                group.slots = b
                if flying is not None:
                    self._c_overlapped.add()
        except BaseException as e:  # noqa: BLE001 - fail the CLIENTS
            self._fail(group, e)
            group = None
        if flying is not None:
            self._land(flying)
        if group is not None and scores is not None:
            self._land(group, scores)
            group = None
        return group

    def _land(self, group: _Group, scores=None) -> None:
        """Second half of a group's dispatch: its scores read back to
        host memory (unless handed in), every waiter released, the skew
        fold, the parse scratch freed.  A failure here fails this
        group's clients and no others."""
        reqs, total = group.reqs, group.total
        try:
            if scores is None:
                flight = group.flight
                scores = self._scorer.read_rung(flight)
                group.launch_s = flight.launch_s
                group.readback_s = flight.readback_s
            with obs.Phase(self._t_deliver, "tffm:serve.deliver",
                           reqs=len(reqs)) as ph:
                now = ph.t0
                self._slots += group.slots
                self._filled += total
                self._g_fill.set(round(self.batch_fill, 6))
                self._c_batches.add()
                self._c_examples.add(total)
                pos = 0
                for g in reqs:
                    g.scores = np.asarray(
                        scores[pos:pos + g.n], np.float32
                    )
                    pos += g.n
                    self._t_latency.observe(now - g.t0)
                    if self._slo is not None:
                        self._slo.observe(True, now - g.t0)
                    if g.rid is not None:
                        self._trace_request(g, group, now)
                    with self._out_lock:
                        self._outstanding.discard(g)
                        self._g_inflight.set(len(self._outstanding))
                    g.event.set()
            if self._quality is not None:
                # Skew sketching AFTER every waiter is released: the
                # request's own (unpadded) arrays and its served
                # scores — never the pool buffer, whose padded tail
                # would dilute the length/id distributions.  Its own
                # except: these requests were already ANSWERED, so a
                # sketching failure must not re-enter the outer
                # fail-the-clients handler (which would stamp errors
                # on delivered requests and double-count the SLO
                # window).
                try:
                    # ONE fold per dispatched group (concatenating the
                    # unpadded request arrays), not one per request:
                    # the dispatcher is serial, and per-request lock
                    # round-trips would add straight to the next
                    # group's queueing latency under many-small-
                    # request traffic.
                    with obs.Phase(self._t_quality, "tffm:serve.quality",
                                   n=total):
                        if len(reqs) == 1:
                            g = reqs[0]
                            self._quality.observe_batch(g.ids, g.vals)
                            self._quality.observe_scores(g.scores)
                        else:
                            self._quality.observe_batch(
                                np.concatenate([g.ids for g in reqs]),
                                np.concatenate([g.vals for g in reqs]),
                            )
                            self._quality.observe_scores(
                                np.concatenate(
                                    [g.scores for g in reqs]
                                )
                            )
                except Exception as e:  # noqa: BLE001 - observe only
                    log.warning("skew sketching failed: %s", e)
            # Last reader done (microbatch copy + quality fold both
            # read g.ids/g.vals): release pooled parse scratch.
            for g in reqs:
                g.finish()
        except BaseException as e:  # noqa: BLE001 - fail the CLIENTS
            self._fail(group, e)

    def _fail(self, group: _Group, e: BaseException) -> None:
        log.warning("serve dispatch failed: %s", e)
        for g in group.reqs:
            g.error = e
            if self._slo is not None:
                self._slo.observe(False)
            with self._out_lock:
                self._outstanding.discard(g)
                self._g_inflight.set(len(self._outstanding))
            g.event.set()
            g.finish()

    def _fail_outstanding(self, exc: BaseException) -> None:
        with self._out_lock:
            stale = list(self._outstanding)
            self._outstanding.clear()
            self._g_inflight.set(0)
        for req in stale:
            req.error = exc
            req.event.set()
            req.finish()

    def close(self) -> None:
        """Stop the dispatcher: the group in flight is landed, queued
        requests fail.  Idempotent."""
        with self._out_lock:
            self._closed = True
        self._q.cancel()
        self._thread.join()
