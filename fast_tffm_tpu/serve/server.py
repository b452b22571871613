"""The scoring endpoint: HTTP front door, hot-swap watcher, lifecycle.

:class:`ServeServer` mounts the batcher behind a stdlib
ThreadingHTTPServer (the same server discipline as ``obs/status.py`` —
daemon thread, read-only observability routes, degrade-don't-die):

- ``POST /score`` — body is libsvm/ffm text, one example per line in
  exactly the ``predict_files`` format (label column present but
  ignored; lines whose first token contains ``:`` are accepted
  label-less).  Response: one score per non-blank line, ``%.6f`` —
  byte-identical formatting to offline ``predict``'s ``score_path``.
- ``POST /score_bin`` — the binary request transport: one
  length-prefixed little-endian frame of id/value/field arrays (layout
  at the codec below and in SERVING.md), decoded by ``np.frombuffer``
  so the hot path skips text parsing entirely; scores come back as one
  binary frame.  Bitwise-identical scores to ``/score`` for the same
  examples.  ``serve_transport`` gates which of the two are enabled.
- ``POST /reload`` / ``/promote`` / ``/rollback`` — the admin swap
  surface the router's canary promotion drives: reload the current
  manifest's checkpoint keeping the replaced params restorable, close
  the rollback window, or restore them.
- ``GET /metrics`` / ``/status`` / ``/healthz`` — the live
  observability surface, rendered by the same
  ``obs.status.render_prometheus`` the trainer's endpoint uses; all
  ``serve.*`` instruments plus a ``serve`` record block (qps, latency
  percentiles, batch fill, swaps) show up as ``tffm_serve_*`` series.

:class:`CheckpointWatcher` is the warm hot-swap driver: it polls the
``serve_manifest.json`` the trainer's save path publishes (the manifest
is written AFTER the checkpoint files, so a published step is always a
complete checkpoint), reloads the params into standby buffers
off-traffic, and calls ``scorer.swap`` — zero recompiles (shapes
unchanged), zero dropped requests (one reference swap between
dispatches).  A reload that races the NEXT save simply fails, warns,
and retries at the next poll.

:func:`serve` builds the whole stack from an :class:`FmConfig`
(scorer -> warmup -> batcher -> watcher -> HTTP) and returns a
:class:`ServeHandle`; :func:`serve_forever` is the CLI entry
(``run_tffm.py serve <cfg>``).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from typing import Optional

import numpy as np

from fast_tffm_tpu import obs, platform
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.status import (
    ObsHTTPServer, PooledHTTPServer, QuietHandler,
)
from fast_tffm_tpu.obs.trace import NULL_TRACER, Tracer
from fast_tffm_tpu.serve import wire
from fast_tffm_tpu.serve.batcher import ServeBatcher
from fast_tffm_tpu.serve import scorer as scorer_lib
from fast_tffm_tpu.serve.slo import SloTracker
from fast_tffm_tpu.serve.textparse import ParseScratchPool, parse_request
from fast_tffm_tpu.train import checkpoint

log = logging.getLogger(__name__)

# Backward-compatible spellings: the codec (and the shared POST body
# cap) live in serve/wire.py — jax-free so the router process can
# decode frames without a jax import — and re-export here where the
# serving tests and clients historically found them.
_MAX_BODY_BYTES = wire.MAX_BODY_BYTES
BIN_MAGIC = wire.BIN_MAGIC
decode_bin_request = wire.decode_bin_request
decode_bin_response = wire.decode_bin_response
encode_bin_request = wire.encode_bin_request
encode_bin_response = wire.encode_bin_response

__all__ = [
    "BIN_MAGIC", "CheckpointWatcher", "ParseScratchPool", "ServeHandle",
    "ServeServer", "decode_bin_request", "decode_bin_response",
    "encode_bin_request", "encode_bin_response", "parse_request",
    "reload_scorer", "serve", "serve_forever",
]

# parse_request lives in serve/textparse.py now (the vectorized batch
# parser + its per-line fallback/oracle and the scratch pool); it is
# re-exported above because the serving tests and embedders have always
# imported it from here.


def reload_scorer(cfg: FmConfig, scorer, keep_prev: bool = False) -> int:
    """Reload ``cfg.model_file``'s checkpoint into the running scorer
    (standby buffers, then one reference swap — never torn).  Returns
    the new step.  Raises ValueError on a config<->checkpoint
    contradiction, including a dense<->tiered FORMAT flip a running
    scorer cannot cross.  Shared by the poll watcher, the ``/reload``
    admin route, and the router's canary protocol (which passes
    ``keep_prev=True`` to hold the rollback window open)."""
    fmt, step, model = scorer_lib.load_model(cfg, mesh=scorer.mesh)
    if fmt == "tiered" and isinstance(scorer, scorer_lib.OverlayScorer):
        scorer.swap(*model, step=step, keep_prev=keep_prev)
    elif fmt in ("dense", "quant") and isinstance(
        scorer, scorer_lib.FixedShapeScorer
    ):
        # A dense checkpoint swaps into any table dtype (a quantized
        # scorer re-quantizes it off-traffic); a quant checkpoint must
        # match the scorer's dtype/chunk — load_model/swap raise
        # ValueError on mismatch.
        scorer.swap(model, step=step, keep_prev=keep_prev)
    else:
        raise ValueError(
            f"checkpoint at {cfg.model_file} changed FORMAT ({fmt}) "
            "mid-serve; a running server cannot cross dense<->tiered "
            "— restart to pick it up"
        )
    return step


class CheckpointWatcher:
    """Poll the save-path manifest; hot-swap the scorer on a new step.

    ``seen`` is the baseline manifest the currently-served params came
    from; the owner should capture it BEFORE loading the checkpoint
    (serve() does), so a save landing during load/warmup is still
    picked up at the first poll instead of being silently baselined
    away.  Omitted -> read at construction (direct/test use).
    """

    def __init__(self, cfg: FmConfig, scorer, poll_secs: float,
                 on_swap=None, seen=None):
        self._cfg = cfg
        self._scorer = scorer
        self._poll = max(0.05, float(poll_secs))
        self._on_swap = on_swap
        self._seen = (
            seen if seen is not None
            else checkpoint.read_manifest(cfg.model_file)
        )
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="tffm-serve-watcher", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            try:
                self._check_once()
            except Exception as e:  # noqa: BLE001 - retry next poll
                log.warning(
                    "checkpoint watcher: reload failed (%s); will "
                    "retry next poll", e,
                )

    def _check_once(self) -> None:
        man = checkpoint.read_manifest(self._cfg.model_file)
        if man is None or man == self._seen:
            return
        try:
            step = reload_scorer(self._cfg, self._scorer)
        except ValueError as e:
            # A ValueError out of load_model/swap is a PERMANENT
            # config<->checkpoint contradiction (serve_table_dtype or
            # quant_chunk mismatch, shape mismatch, overlay descriptor
            # drift) — re-reading a multi-GB table every poll would
            # never fix it.  Baseline the manifest like the
            # format-flip branch: warn once, keep serving the current
            # params, pick up the NEXT save.
            log.warning(
                "checkpoint at %s cannot be served under this config "
                "(%s); keeping the current params — fix the config or "
                "republish, a restart is NOT needed for the next "
                "compatible save", self._cfg.model_file, e,
            )
            self._seen = man
            return
        self._seen = man
        if self._on_swap is not None:
            self._on_swap(step)

    def close(self) -> None:
        self._stop.set()
        self._thread.join()


class ServeServer:
    """HTTP front door: ``POST /score`` (libsvm text) + ``POST
    /score_bin`` (the binary frame transport, gated by
    ``serve_transport``), the admin routes the router's canary
    protocol drives (``/reload`` / ``/promote`` / ``/rollback``), and
    the observability routes."""

    def __init__(self, port: int, batcher: ServeBatcher, cfg: FmConfig,
                 build, telemetry=None, host: str = "127.0.0.1",
                 timeout_s: float = 30.0, scorer=None, tracer=None,
                 sampler=None, slo=None, on_reload=None,
                 on_rollback=None, capture=None, incident=None):
        tel = telemetry if telemetry is not None else obs.NULL
        tracer = tracer if tracer is not None else NULL_TRACER
        # Request-id mint + trace-sampling coin flip for DIRECT
        # traffic (a router stamps ids before they arrive; a
        # single-process server is its own front door).
        sampler = sampler if sampler is not None else wire.RequestSampler(
            cfg.serve_trace_sample, enabled=tracer.enabled, tag="s"
        )
        requests_c = tel.counter("serve.http_requests")
        truncated_c = tel.counter("serve.truncated_features")
        # Per-request libsvm-text parse time: PR 9 flagged text parsing
        # as measurable host latency at small requests — this timer
        # made it a measured number, and the binary transport's
        # serve.parse_bin twin is the datum that shows what removing
        # the text parse actually buys (PERF.md §5 has both per request).
        parse_t = tel.timer("serve.parse")
        parse_bin_t = tel.timer("serve.parse_bin")
        # The HTTP worker's two other phases (obs.Phase: a timer and a
        # tffm:serve.<phase> annotation over the same block).  Its wait
        # in batcher.score() is neither: it is a wait on another thread.
        read_body_t = tel.timer("serve.read_body")
        respond_t = tel.timer("serve.respond")
        # Recycled per-request parse scratch (textparse.py): the text
        # path's arrays come from here and go back via the batcher's
        # on_done hook — steady-state text scoring allocates near-zero
        # per request.  The binary transport decodes straight out of
        # the request body (np.frombuffer views) and stays unpooled.
        parse_pool = ParseScratchPool(cfg.max_features, telemetry=tel)
        # The admin swap surface is driven over HTTP by the router's
        # canary protocol; one at a time (a reload stages a whole
        # standby table — two concurrent ones would race the rollback
        # window).
        admin_lock = threading.Lock()
        server = self

        def score_arrays(handler, ids, vals, fields, n, truncated,
                         encode, parse, rid=None, on_done=None) -> None:
            """Shared tail of both transports: count integrity events,
            batch-score, encode the response.  ``rid`` (a sampled or
            client-supplied request id) is echoed in the response's
            ``X-Request-Id`` header; its span chain opens with
            ``serve.parse`` (from ``parse``, the finished parse phase)
            and closes with ``serve.respond``.  ``on_done`` is the
            pooled-scratch release hook: from here on the BATCHER owns
            firing it (exactly once, when its dispatcher stops reading
            the arrays — a client-side timeout must NOT release a
            buffer the dispatcher still holds); the n == 0 early-out
            never submits, so it releases directly."""
            if truncated:
                # Same integrity signal the ingest path counts: a
                # truncated example scores as a different example.
                truncated_c.add(truncated)
            rid_hdr = {"X-Request-Id": rid} if rid is not None else None
            if rid is not None:
                tracer.emit(
                    "serve.parse", parse.t0, parse.seconds,
                    args={"rid": rid, "n": n},
                )
            if n == 0:
                if on_done is not None:
                    on_done()
                ctype, body = encode(np.zeros((0,), np.float32))
                handler._send(200, body, ctype, headers=rid_hdr)
                return
            # Traffic capture (serve_capture_sample): the request frame
            # is encoded NOW, while this handler still owns the arrays
            # — the batcher releases pooled text-parse scratch the
            # moment its dispatcher stops reading it.  Canonical form
            # (post-pad, post-modulo) makes re-decoding idempotent, so
            # replaying the frame reproduces the response bitwise.
            # Unsampled requests pay one attribute compare.
            cap_req = None
            if capture is not None and capture.sample():
                try:
                    cap_req = wire.encode_bin_request(
                        ids[:n], vals[:n],
                        fields[:n]
                        if (cfg.field_num and fields is not None)
                        else None,
                    )
                except Exception as e:  # noqa: BLE001 - forensics only
                    log.warning("capture encode failed: %s", e)
            try:
                scores = batcher.score(
                    ids, vals,
                    fields if cfg.field_num else None,
                    timeout=timeout_s, rid=rid, on_done=on_done,
                )
            except Exception as e:  # noqa: BLE001 - report, don't die
                if slo is not None:
                    # The batcher's ledger only sees requests its
                    # dispatcher finishes; an HTTP-layer failure (a
                    # scoring timeout, a closed batcher) is a 503 the
                    # CLIENT saw — without this, a 503 storm would
                    # read as burn_rate 0.
                    slo.observe(False)
                handler._send(
                    503, f"scoring failed: {e}\n".encode(),
                    "text/plain", headers=rid_hdr,
                )
                return
            if cap_req is not None:
                capture.write(cap_req, encode_bin_response(scores))
            with obs.Phase(respond_t, "tffm:serve.respond", n=n) as ph:
                ctype, body = encode(scores)
                handler._send(200, body, ctype, headers=rid_hdr)
            if rid is not None:
                # Chain tail: scores -> encoded -> written back to the
                # client; the flow end ("f") binds the arrow from the
                # dispatch step to this span.
                tracer.emit(
                    "serve.respond", ph.t0, ph.seconds,
                    args={"rid": rid, "n": n}, flow=("f", rid),
                )

        def encode_text(scores):
            return "text/plain", "".join(
                f"{s:.6f}\n" for s in scores
            ).encode()

        def encode_bin(scores):
            return "application/octet-stream", encode_bin_response(scores)

        class Handler(QuietHandler):
            def do_POST(self) -> None:  # noqa: N802 - http.server API
                requests_c.add()
                path, _, query = self.path.partition("?")
                if path in ("/reload", "/promote", "/rollback"):
                    self._do_admin(path, query)
                    return
                if path == "/incident":
                    self._post_incident(query, incident)
                    return
                if path not in ("/score", "/score_bin"):
                    self._send(404, b"not found\n", "text/plain")
                    return
                want = "text" if path == "/score" else "bin"
                if cfg.serve_transport not in (want, "both"):
                    self._send(
                        404, f"transport {want!r} disabled "
                             f"(serve_transport="
                             f"{cfg.serve_transport})\n".encode(),
                        "text/plain",
                    )
                    return
                with obs.Phase(read_body_t,
                               "tffm:serve.read_body") as ph:
                    body = self._read_body(_MAX_BODY_BYTES)
                    if body is None:
                        return  # error response already sent
                    ph.set(bytes=len(body))
                # Request id: the X-Request-Id header (either
                # transport), overridden by the binary frame's own
                # trailer (the router stamps SAMPLED frames there).
                # Invalid ids (empty/oversized/control chars) are
                # ignored, not errors — tracing must never fail a
                # scoring request.
                rid = self.headers.get("X-Request-Id")
                if rid is not None and not wire.valid_request_id(rid):
                    rid = None
                on_done = None
                text = path == "/score"
                try:
                    # One phase, two timers: serve.parse times the text
                    # parse and serve.parse_bin the frame decode.
                    with obs.Phase(parse_t if text else parse_bin_t,
                                   "tffm:serve.parse",
                                   text=int(text)) as parse:
                        if text:
                            ids, vals, fields, n, truncated = parse_request(
                                body.decode(), cfg, pool=parse_pool
                            )
                        else:
                            (ids, vals, fields, n, truncated,
                             frame_rid) = decode_bin_request(body, cfg)
                        parse.set(n=n)
                    if text:
                        on_done = lambda i=ids: parse_pool.release(i)  # noqa: E731
                    elif frame_rid is not None and \
                            wire.valid_request_id(frame_rid):
                        # Same sanitization as the header path: the
                        # rid echoes into a response HEADER, so a
                        # trailer smuggling CR/LF (or non-latin-1
                        # bytes send_header can't write) must be
                        # dropped, never reflected.
                        rid = frame_rid
                except (ValueError, UnicodeDecodeError) as e:
                    self._send(
                        400, f"bad request: {e}\n".encode(), "text/plain"
                    )
                    return
                if rid is None and sampler.sample():
                    # Direct traffic with no upstream id: this server
                    # is the front door, so it mints (and samples)
                    # itself.  Unsampled requests never reach here
                    # with any id work done.
                    rid = sampler.mint()
                score_arrays(
                    self, ids, vals, fields, n, truncated,
                    encode_text if text else encode_bin, parse,
                    rid=rid, on_done=on_done,
                )

            def _do_admin(self, path: str, query: str) -> None:
                """The canary-protocol swap surface.  ``/reload``
                loads the CURRENT manifest's checkpoint into standby
                buffers and swaps; only ``/reload?keep_prev=1`` (the
                router's canary reload) retains the replaced params
                for ``/rollback`` — a plain reload must not pin a
                second table in device memory (nothing in a
                non-canary deployment would ever ``/promote`` it
                away), and without a retained window ``/rollback`` is
                a 409, so a stray admin call cannot flip the served
                model.  All three answer JSON with the served step."""
                if scorer is None:
                    self._send(
                        503, b"no admin scorer on this endpoint\n",
                        "text/plain",
                    )
                    return
                # Consume a (normally empty) body so keep-alive stays
                # intact for admin clients that send one.
                if self._read_body(_MAX_BODY_BYTES) is None:
                    return
                with admin_lock:
                    try:
                        if path == "/reload":
                            reload_scorer(
                                cfg, scorer,
                                keep_prev="keep_prev=1" in query,
                            )
                            if on_reload is not None:
                                # The served params now come from the
                                # current manifest; the skew reference
                                # follows (canary replicas run
                                # watcher-less, so this is their only
                                # reference-refresh path).
                                on_reload()
                        elif path == "/promote":
                            scorer.promote()
                            if on_reload is not None:
                                on_reload()
                        else:
                            if not scorer.rollback():
                                self._send(
                                    409, b"nothing to roll back to (no "
                                         b"keep-prev swap is open)\n",
                                    "text/plain",
                                )
                                return
                            if on_rollback is not None:
                                # The served params just reverted to
                                # the PRE-canary checkpoint; the skew
                                # reference reverts with them (its
                                # manifest is gone from disk, so this
                                # restores the stashed copy).
                                on_rollback()
                    except ValueError as e:
                        self._send(
                            409, f"{e}\n".encode(), "text/plain"
                        )
                        return
                    except Exception as e:  # noqa: BLE001 - report
                        self._send(
                            500, f"{path} failed: {e}\n".encode(),
                            "text/plain",
                        )
                        return
                    body = (json.dumps({"step": scorer.step}) + "\n"
                            ).encode()
                self._send(200, body, "application/json")

            def do_GET(self) -> None:  # noqa: N802 - http.server API
                requests_c.add()
                path = self.path.partition("?")[0]
                if self._get_observability(path, server._build):
                    return
                self._send(404, b"not found\n", "text/plain")

        self._build = build
        self.parse_pool = parse_pool
        # Pooled front end by default; serve_http_threads = 0 keeps
        # the r14 thread-per-connection server, byte-identical.  Two
        # plain assignments (not one conditional expression) so the
        # lifecycle lint sees both constructor bindings.
        if cfg.serve_http_threads > 0:
            self._httpd = PooledHTTPServer(
                (host, port), Handler,
                pool_size=cfg.serve_http_threads,
                acceptors=cfg.serve_http_acceptors,
            )
        else:
            self._httpd = ObsHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="tffm-serve-http",
            daemon=True,
        )
        self._thread.start()
        self._closed = False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._httpd.shutdown()
        self._thread.join()
        self._httpd.server_close()


class ServeHandle:
    """One running serving stack; ``close()`` tears it down in order
    (HTTP stops accepting, batcher drains/fails, watcher stops, final
    record written, trace dumped)."""

    def __init__(self, cfg, scorer, batcher, server, watcher, telemetry,
                 writer, heartbeat, build, tracer=None,
                 alert_engine=None, blackbox=None, capture=None):
        self.cfg = cfg
        self.scorer = scorer
        self.batcher = batcher
        self.server = server
        self.watcher = watcher
        self.telemetry = telemetry
        self.port = server.port
        self.alert_engine = alert_engine
        self.blackbox = blackbox
        self.capture = capture
        self.exception: Optional[BaseException] = None
        self._writer = writer
        self._heartbeat = heartbeat
        self._build = build
        self._tracer = tracer
        self._closed = False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.server.close()
        if self.watcher is not None:
            self.watcher.close()
        self.batcher.close()
        if self._heartbeat is not None:
            self._heartbeat.close()
        if self._writer is not None or self.blackbox is not None:
            try:
                final = self._build("final")
                if final is not None:
                    if self.exception is not None:
                        # Crash-truthful final: same contract as the
                        # trainer's try/finally final record.
                        final["exception"] = type(
                            self.exception
                        ).__name__
                        final["exception_msg"] = str(self.exception)
                    if self._writer is not None:
                        self._writer.write(final)
                    if self.blackbox is not None:
                        self.blackbox.observe_record(final)
            except Exception as e:  # noqa: BLE001 - teardown best-effort
                log.warning("serve final record write failed: %s", e)
        # Crash-truthful bundle: an AlertHaltError (or any crash) that
        # tears serving down leaves its forensics behind.  Dumped
        # BEFORE the writer closes so the manifest still reaches the
        # metrics stream; a clean close dumps nothing.
        if (
            self.blackbox is not None
            and self.exception is not None
            and not isinstance(self.exception, KeyboardInterrupt)
        ):
            self.blackbox.incident(
                "crash_" + type(self.exception).__name__
            )
        if self.capture is not None:
            self.capture.close()
        if self._writer is not None:
            self._writer.close()
        if self._tracer is not None and self._tracer.enabled:
            try:
                n = self._tracer.dump(self.cfg.trace_file)
                self._tracer.close()
                log.info(
                    "serve trace written to %s (%d events)",
                    self.cfg.trace_file, n,
                )
            except Exception as e:  # noqa: BLE001 - teardown best-effort
                log.warning("serve trace dump failed: %s", e)


def _serve_block(snap: dict, scorer, batcher, wall: float) -> dict:
    """The ``serve`` record block: flat, numeric, host-side only —
    rendered as ``tffm_serve_*`` by /metrics and summarized by
    tools/report.py.  ``snap`` is the one telemetry snapshot the whole
    record is built from (one instrument-lock walk per scrape, and the
    block can never disagree with ``stages``)."""
    counters = snap.get("counters") or {}
    timers = snap.get("timers") or {}
    gauges = snap.get("gauges") or {}
    lat = timers.get("serve.latency") or {}
    requests = int(counters.get("serve.requests", 0))
    out = {
        "requests": requests,
        "examples": int(counters.get("serve.examples", 0)),
        "batches": int(counters.get("serve.batches", 0)),
        "qps": round(requests / wall, 2) if wall > 0 else 0.0,
        "inflight": int(gauges.get("serve.inflight", 0)),
        "batch_fill": round(batcher.batch_fill, 6),
        "swaps": int(counters.get("serve.swaps", 0)),
        "compiles": int(scorer.compiles),
        "steady_compiles": int(scorer.steady_compiles),
        "recompiles_unexpected": int(
            counters.get("serve.recompiles_unexpected", 0)
        ),
        "truncated_features": int(
            counters.get("serve.truncated_features", 0)
        ),
        # Front-end shape: 0 = thread-per-connection.  In the record
        # block (not only the run header) so any single metrics
        # snapshot says which accept path produced its latencies.
        "http_threads": int(getattr(
            getattr(scorer, "cfg", None), "serve_http_threads", 0
        ) or 0),
        # Which interaction impl the compiled rungs run (autotune
        # surface; a string — /metrics skips it, the JSONL block keeps
        # it) plus the concurrent-warmup accounting: summed compile
        # seconds vs observed wall, whose gap is the wall the
        # concurrent ladder warmup saved.
        "kernel_impl": getattr(scorer, "kernel_impl", "reference"),
        "warmup_wall_s": round(
            float(getattr(scorer, "warmup_wall_s", 0.0)), 4
        ),
        "warmup_compile_s": round(
            float(getattr(scorer, "warmup_compile_s", 0.0)), 4
        ),
    }
    # Quantized-table accounting, emitted only when the scorer owns
    # the gauges (FixedShapeScorer): the device-resident table's real
    # byte footprint and the max |served_fp32 − served_quant| probe
    # error from the last placement (0 = fp32 serving IS the
    # reference, −1 = unknown).  An OverlayScorer registers neither —
    # defaulting its error to 0 would CLAIM exactness for a quantized
    # cold store it never measured.
    if "serve.table_bytes" in gauges:
        out["table_mb"] = round(
            gauges["serve.table_bytes"] / (1 << 20), 3
        )
    if "serve.quant_error_max" in gauges:
        out["quant_error_max"] = round(
            float(gauges["serve.quant_error_max"]), 6
        )
    for key in ("mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms"):
        if key in lat:
            out[key] = lat[key]
    if lat.get("count"):
        # Sample-count companions of the percentile keys above: the
        # run-total observations and how many ring samples the
        # percentiles actually summarize (a p99 over 3 requests is a
        # different claim than one over 30k).
        out["latency_count"] = int(lat["count"])
        if "window_n" in lat:
            out["latency_window_n"] = int(lat["window_n"])
    parse = timers.get("serve.parse") or {}
    if "p50_ms" in parse:
        out["parse_p50_ms"] = parse["p50_ms"]
    parse_bin = timers.get("serve.parse_bin") or {}
    if "p50_ms" in parse_bin:
        out["parse_bin_p50_ms"] = parse_bin["p50_ms"]
    return out


def serve(cfg: FmConfig, mesh=None, port: Optional[int] = None
          ) -> ServeHandle:
    """Build and start the full serving stack from a config.

    ``port`` overrides ``cfg.serve_port`` (tests pass 0 for an
    OS-assigned port; the bound port is ``handle.port``).
    """
    # Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else
    # the compile_cache_dir knob), enabled before the scorer's warmup
    # compiles: a replica spawned against a populated cache replays its
    # whole ladder from disk — zero fresh lowers
    # (platform.compile_cache_stats counts both ways).
    platform.enable_compile_cache(cfg.compile_cache_dir)
    writer = (
        obs.JsonlWriter(cfg.metrics_file) if cfg.metrics_file else None
    )
    telemetry = obs.Telemetry(enabled=cfg.telemetry)
    # Per-request distributed tracing (serve_trace_sample) + any
    # future serve-path spans land here; trace_file unset = the shared
    # no-op tracer, zero behavior change (same contract as training).
    tracer = (
        Tracer(
            enabled=True, process_name="serve",
            rotate_events=cfg.trace_rotate_events,
            rotate_path=cfg.trace_file or None,
        )
        if cfg.trace_file else NULL_TRACER
    )
    slo = SloTracker(
        cfg.serve_slo_p99_ms, cfg.serve_slo_availability,
        telemetry=telemetry,
    )
    # Training→serving skew detection (obs/quality.py): live request
    # sketches judged against the trainer-published reference sketches
    # in serve_manifest.json; the reference re-reads after every hot
    # swap so it always matches the checkpoint being served.  quality
    # off = no monitor, no skew_* keys, byte-identical serving.
    skew = None
    if cfg.quality:
        from fast_tffm_tpu.train.manifest import read_manifest

        def _read_skew_reference(_model=cfg.model_file):
            man = read_manifest(_model)
            if not isinstance(man, dict) or "quality" not in man:
                return None
            return {"step": man.get("step", -1), **man["quality"]}

        skew = obs.ServeSkewMonitor(
            window_examples=cfg.quality_window, telemetry=telemetry,
            read_reference=_read_skew_reference,
        )
        skew.reload_reference()
    # Watcher baseline BEFORE the load: a checkpoint published while we
    # load/warm up must look NEW to the first poll (the scorer may or
    # may not have caught it; re-swapping to the same step is a cheap
    # no-op, serving stale params forever is not).
    manifest_baseline = checkpoint.read_manifest(cfg.model_file)
    try:
        scorer = scorer_lib.make_scorer(
            cfg, mesh=mesh, telemetry=telemetry, writer=writer
        )
        n_compiles = scorer.warmup()
    except BaseException:
        # No servable checkpoint / warmup failure: close the metrics
        # writer behind the raise (callers retrying against a racing
        # model dir must not accumulate leaked fds).
        if writer is not None:
            writer.close()
        if tracer is not NULL_TRACER:
            tracer.close()
        raise
    log.info(
        "scorer ready: checkpoint step %d, ladder %s, %d rung(s) "
        "precompiled — steady-state serving performs zero compiles",
        scorer.step, list(scorer.ladder), n_compiles,
    )
    stats = platform.compile_cache_stats()
    if stats["dir"]:
        log.info(
            "compile cache %s: %d hit(s), %d miss(es) during warmup%s",
            stats["dir"], stats["hits"], stats["misses"],
            " — warm spawn, zero fresh lowers"
            if stats["hits"] and not stats["misses"] else "",
        )
    batcher = ServeBatcher(
        scorer, max_batch_wait_ms=cfg.max_batch_wait_ms,
        queue_size=cfg.queue_size, telemetry=telemetry, tracer=tracer,
        slo=slo, quality=skew,
    )
    # Live-traffic capture (serve_capture_sample/serve_capture_file):
    # sampled request/response frame pairs land in a rotating TFC1
    # file for tools/replay.py.  FmConfig guarantees both knobs are set
    # together; unset = None = byte-identical serving (pinned by test).
    capture = None
    if cfg.serve_capture_file:
        capture = wire.CaptureWriter(
            cfg.serve_capture_file, sample=cfg.serve_capture_sample,
            telemetry=telemetry,
        )
    t0 = time.time()

    def build(kind: str = "status"):
        now = time.time()
        wall = max(now - t0, 1e-9)
        # SLO (and skew) gauges refresh BEFORE the snapshot so one
        # scrape sees block keys and gauge spellings agree.  The final
        # record forces a fresh skew compute past the TTL memo.
        slo_block = slo.snapshot()
        skew_block = (
            skew.block(force=(kind == "final"))
            if skew is not None else {}
        )
        snap = telemetry.snapshot()
        serve_block = _serve_block(snap, scorer, batcher, wall)
        serve_block.update(slo_block)
        serve_block.update(skew_block)
        rec = {
            "record": kind,
            "time": now,
            "elapsed": round(wall, 3),
            "step": scorer.step,
            "serve": serve_block,
            "stages": snap,
        }
        if cfg.resource_metrics:
            rec["resource"] = obs.basic_block(t0)
        if alert_engine is not None:
            # Armed-rule states for /status and the per-rule
            # tffm_alert_active gauges (defined below build; every
            # call happens after serve() finishes wiring).
            rec["alerts"] = alert_engine.active_snapshot()
        if tracer.enabled:
            rec["trace_dropped_events"] = tracer.dropped_events
            if cfg.trace_rotate_events:
                rec["trace_windows"] = tracer.windows_written
        return rec

    run_header = {
            "record": "run_header",
            "mode": "serve",
            "time": t0,
            "model_file": cfg.model_file,
            "resume_step": scorer.step,
            "serve_batch_sizes": list(scorer.ladder),
            "max_batch_wait_ms": cfg.max_batch_wait_ms,
            "serve_poll_secs": cfg.serve_poll_secs,
            "serve_transport": cfg.serve_transport,
            # Front-end shape knobs: a fleet's accept path must be
            # reconstructable from any metrics stream (KD discipline).
            "serve_parse_mode": cfg.serve_parse_mode,
            "serve_http_threads": cfg.serve_http_threads,
            "serve_http_acceptors": cfg.serve_http_acceptors,
            "serve_request_queue_size": ObsHTTPServer.request_queue_size,
            "batch_size": cfg.batch_size,
            "telemetry": cfg.telemetry,
            "heartbeat_secs": cfg.heartbeat_secs,
            "quality": cfg.quality,
            "kernel_impl": getattr(scorer, "kernel_impl", "reference"),
            "interaction_impl": cfg.interaction_impl,
            "compile_cache_dir": cfg.compile_cache_dir,
            "serve_capture_sample": cfg.serve_capture_sample,
            "blackbox": cfg.blackbox,
    }
    if writer is not None:
        writer.write(run_header)
    # Incident flight recorder: fixed-memory rings of recent records/
    # alerts feeding alert-triggered (and POST /incident) forensic
    # bundles.  The pid suffix keeps co-hosted replicas sharing one
    # incident_dir collision-free; blackbox=false = None = no rings,
    # no routes, byte-identical serving.
    blackbox = None
    if cfg.blackbox:
        blackbox = obs.Blackbox(
            cfg.incident_dir
            or os.path.join(cfg.model_file, "incidents"),
            suffix=f"pid{os.getpid()}",
            run_header=run_header,
            metrics_render=lambda: obs.render_prometheus(
                build("status")
            ),
            trace_tail_fn=(tracer.tail if tracer.enabled else None),
            capture_tail_fn=(
                capture.tail_bytes if capture is not None else None
            ),
            writer=writer,
            telemetry=telemetry,
        )
    # Alert watchdog riding the serve heartbeat (same contract as the
    # trainer's: FmConfig guarantees heartbeat_secs > 0 when rules are
    # set; breaches write `record: alert`; an action=halt rule arms
    # engine.halted, which serve_forever raises as AlertHaltError —
    # an embedder polls handle.alert_engine itself).  Every emitted
    # alert also reaches the blackbox, which dumps a bundle.
    alert_engine = None
    if cfg.alert_rules:
        alert_engine = obs.AlertEngine(
            obs.parse_rules(cfg.alert_rules), writer=writer,
            on_alert=(
                blackbox.on_alert if blackbox is not None else None
            ),
        )

    def heartbeat_build():
        rec = build("heartbeat")
        if rec is not None:
            # Ring BEFORE the alert engine observes: an alert-triggered
            # bundle must contain the record that breached the rule.
            if blackbox is not None:
                blackbox.observe_record(rec)
            if alert_engine is not None:
                alert_engine.observe(rec)
        return rec

    heartbeat = None
    if cfg.heartbeat_secs > 0:
        heartbeat = obs.Heartbeat(
            cfg.heartbeat_secs, heartbeat_build, writer=writer,
        )
    watcher = None
    try:
        if cfg.serve_poll_secs > 0:
            watcher = CheckpointWatcher(
                cfg, scorer, cfg.serve_poll_secs,
                seen=manifest_baseline,
                # A hot swap changes the model being served; the skew
                # reference must follow it to the new manifest.
                on_swap=(
                    (lambda step: skew.reload_reference())
                    if skew is not None else None
                ),
            )
        server = ServeServer(
            cfg.serve_port if port is None else port,
            batcher, cfg, build, telemetry=telemetry,
            host=cfg.serve_host, scorer=scorer, tracer=tracer,
            slo=slo,
            on_reload=(
                skew.reload_reference if skew is not None else None
            ),
            on_rollback=(
                skew.restore_previous_reference
                if skew is not None else None
            ),
            capture=capture,
            incident=(
                blackbox.incident if blackbox is not None else None
            ),
        )
    except BaseException:
        # A taken port (or watcher failure) must not leak the batcher
        # dispatcher / watcher / heartbeat threads behind the raise.
        if watcher is not None:
            watcher.close()
        batcher.close()
        if heartbeat is not None:
            heartbeat.close()
        if capture is not None:
            capture.close()
        if writer is not None:
            writer.close()
        if tracer is not NULL_TRACER:
            tracer.close()
        raise
    log.info(
        "scoring endpoint listening on %s:%d (POST /score; GET "
        "/metrics, /status, /healthz, /debug/threadz)",
        cfg.serve_host, server.port,
    )
    return ServeHandle(
        cfg, scorer, batcher, server, watcher, telemetry, writer,
        heartbeat, build, tracer=tracer, alert_engine=alert_engine,
        blackbox=blackbox, capture=capture,
    )


def serve_forever(cfg: FmConfig) -> int:
    """CLI entry: serve until interrupted.  SIGTERM and SIGINT both
    close cleanly — a replica torn down by its router's manager
    (terminate -> wait) must still write its final record and dump its
    trace.  An armed ``action: halt`` alert rule stops the process
    with the crash-truthful final record (AlertHaltError), the same
    watchdog contract as training."""
    handle = serve(cfg)
    print(f"serving on {cfg.serve_host}:{handle.port}", flush=True)

    def _sigterm(signum, frame):  # pragma: no cover - signal path
        raise KeyboardInterrupt

    prev = signal.signal(signal.SIGTERM, _sigterm)
    try:
        obs.run_until_halt(handle.alert_engine)
    except KeyboardInterrupt:
        log.info("interrupted; shutting down the scoring endpoint")
    except obs.AlertHaltError as e:
        log.error("HALT: %s", e)
        handle.exception = e
        handle.close()
        signal.signal(signal.SIGTERM, prev)
        return 1
    finally:
        if not handle._closed:
            handle.close()
        signal.signal(signal.SIGTERM, prev)
    return 0
