"""Request phases of the serving path (ISSUE 26): one vocabulary,
recorded where the work happens, visible in the device trace.

A real ``serve(cfg, port=0)`` at toy size answers a few dozen text and
binary requests over sockets, first with no profiler session and then
inside one; the cases read the session's host plane (the
``tffm:serve.<phase>`` annotations with their stats), the ``serve.*``
timers, and the per-request span chain of ``obs.Tracer``.
"""

from __future__ import annotations

import glob
import http.client
import threading
import time

import numpy as np
import pytest

import jax

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.serve import wire
from fast_tffm_tpu.serve.batcher import ServeBatcher
from fast_tffm_tpu.serve.scorer import FixedShapeScorer
from fast_tffm_tpu.serve.server import ServeServer, serve
from fast_tffm_tpu.train import checkpoint

V, F = 256, 4
HTTP_PHASES = ("read_body", "parse", "respond")
DISPATCHER_PHASES = ("coalesce", "fill", "launch", "readback", "deliver",
                     "quality")
PREFIX = "tffm:serve."
IDLE_SPAN = "test:idle"


def _cfg(tmp_path, **kw):
    base = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
        model_file=str(tmp_path / "model"), seed=3, log_steps=0,
        serve_batch_sizes="32,64", max_batch_wait_ms=1.0,
    )
    base.update(kw)
    return FmConfig(**base)


def _params(cfg):
    return jax.jit(lambda k: fm.init_params(k, cfg=cfg))(
        jax.random.PRNGKey(0))


def _requests(rng):
    """(path, body, examples) of a few dozen requests, text and binary,
    one to forty examples each."""
    out = []
    for k in range(36):
        n = int(rng.integers(1, 41))
        ids = rng.integers(0, V, (n, F)).astype(np.int32)
        vals = rng.uniform(0.1, 1.0, (n, F)).astype(np.float32)
        if k % 3 == 0:
            body = "".join(
                "0 " + " ".join(f"{i}:{v:.4f}" for i, v in zip(ri, rv))
                + "\n" for ri, rv in zip(ids, vals)).encode()
            out.append(("/score", body, n))
        else:
            out.append(("/score_bin", wire.encode_bin_request(ids, vals), n))
    return out


def _post_all(port, requests, clients=4):
    """Every request's reply bytes, in order, from ``clients`` keep-alive
    connections at once (so that groups of more than one form)."""
    replies = [None] * len(requests)

    def client(k):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            for i in range(k, len(requests), clients):
                path, body, _ = requests[i]
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                replies[i] = (resp.status, resp.read())
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return replies


def _host_lines(trace_dir):
    """The host plane's lines, each a start-sorted list of
    (name, start_ns, end_ns, stats) of the ``tffm:`` / ``test:`` spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name, dict(e.stats))
                for e in line.events
                if e.name.startswith(("tffm:", "test:")))
            if events:
                lines.append([(n, s, e, st) for s, e, n, st in events])
    return lines


def _timers(handle):
    return handle.telemetry.snapshot()["timers"]


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One server, the same requests answered outside and inside a
    profiler session."""
    tmp = tmp_path_factory.mktemp("serve_phases")
    cfg = _cfg(tmp)
    checkpoint.save(cfg.model_file, 1, fm.FmParams(
        *[np.asarray(x) for x in _params(cfg)]))
    requests = _requests(np.random.default_rng(26))
    handle = serve(cfg, port=0)
    try:
        closed = _post_all(handle.port, requests)
        # a reply reaches its client before the worker's respond phase
        # and the dispatcher's quality phase have been observed
        time.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        trace_dir = str(tmp / "trace")
        before = _timers(handle)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            # one rung compiles outside warm-up, on the dispatcher
            handle.scorer._cache.pop(32)
            opened = _post_all(handle.port, requests)
            # nobody in the house: whatever still carries a span over
            # this stretch annotates a wait
            time.sleep(0.3)
            with jax.profiler.TraceAnnotation(IDLE_SPAN):
                time.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        after = _timers(handle)
    finally:
        handle.close()
    lines = _host_lines(trace_dir)
    dispatcher = [ln for ln in lines
                  if any(n == PREFIX + "coalesce" for n, *_ in ln)]
    return {"requests": requests, "closed": closed, "opened": opened,
            "lines": lines, "dispatcher": dispatcher,
            "before": before, "after": after}


def _delta(session, timer, key):
    return (session["after"][timer][key]
            - session["before"].get(timer, {}).get(key, 0))


def test_every_phase_is_in_the_host_plane_under_its_name(session):
    names = {n for ln in session["lines"] for n, *_ in ln
             if n.startswith(PREFIX)}
    want = {PREFIX + p for p in HTTP_PHASES + DISPATCHER_PHASES
            + ("compile",)}
    assert names == want  # and no other: a wait has no name
    # each with the stats of the table
    stats = {}
    for ln in session["lines"]:
        for n, _, _, st in ln:
            stats.setdefault(n, set()).update(st)
    assert stats[PREFIX + "read_body"] == {"bytes"}
    assert stats[PREFIX + "parse"] == {"n", "text"}
    assert stats[PREFIX + "respond"] == {"n"}
    assert stats[PREFIX + "coalesce"] == {"reqs", "n"}
    assert stats[PREFIX + "fill"] == {"reqs", "n", "rung", "qwait_us"}
    assert stats[PREFIX + "launch"] == {"rung", "inflight"}
    assert stats[PREFIX + "readback"] == {"rung"}
    assert stats[PREFIX + "deliver"] == {"reqs"}
    assert stats[PREFIX + "quality"] == {"n"}
    assert stats[PREFIX + "compile"] == {"rung"}


def test_dispatcher_phases_tile_one_thread(session):
    assert len(session["dispatcher"]) == 1  # one thread holds them all
    line = session["dispatcher"][0]
    names = {n for n, *_ in line}
    assert names == {PREFIX + p for p in DISPATCHER_PHASES + ("compile",)}
    for (_, _, end, _), (name, start, _, _) in zip(line, line[1:]):
        assert start >= end, f"{name} starts inside the span before it"
    # A dispatch is coalesce, fill, launch | readback, deliver, quality,
    # each half in that order (the compile between fill and launch,
    # once).  The halves of one group are adjacent unless the queue
    # closed the next group at once: then that group's first half comes
    # between them, and never more than one.
    order = [n[len(PREFIX):] for n, *_ in line if n != PREFIX + "compile"]
    assert len(order) % len(DISPATCHER_PHASES) == 0
    first, second = DISPATCHER_PHASES[:3], DISPATCHER_PHASES[3:]
    inflight = [st["inflight"] for n, _, _, st in line
                if n == PREFIX + "launch"]
    unread = 0
    for i in range(0, len(order), 3):
        half = tuple(order[i:i + 3])
        assert half in (first, second), (i, half)
        if half == first:
            # the stat on `launch`: a group not yet read back is ahead
            assert inflight.pop(0) == unread
            unread += 1
        else:
            unread -= 1
        assert 0 <= unread <= 2
    assert unread == 0 and not inflight
    # no HTTP worker's phase is on the dispatcher's thread, nor the
    # other way round
    for ln in session["lines"]:
        if ln is not line:
            assert not {n for n, *_ in ln} & names


def test_launch_and_readback_lie_inside_the_dispatch_timer(session):
    line = session["dispatcher"][0]
    inner = sum(e - s for n, s, e, _ in line
                if n in (PREFIX + "launch", PREFIX + "readback")) / 1e9
    dispatches = sum(n == PREFIX + "launch" for n, *_ in line)
    assert dispatches == _delta(session, "serve.dispatch", "count")
    assert dispatches == _delta(session, "serve.launch", "count")
    assert dispatches == _delta(session, "serve.readback", "count")
    # (the one compile also lies inside the dispatch timer)
    assert 0 < inner <= _delta(session, "serve.dispatch", "total_s")
    timed = (_delta(session, "serve.launch", "total_s")
             + _delta(session, "serve.readback", "total_s"))
    assert timed <= _delta(session, "serve.dispatch", "total_s")


def test_fill_counts_every_request_and_example_sent(session):
    fills = [st for n, _, _, st in session["dispatcher"][0]
             if n == PREFIX + "fill"]
    sent = session["requests"]
    assert sum(st["reqs"] for st in fills) == len(sent)
    assert sum(st["n"] for st in fills) == sum(n for _, _, n in sent)
    assert any(st["reqs"] > 1 for st in fills)  # groups did form
    assert all(st["rung"] in (32, 64) and st["n"] <= st["rung"]
               for st in fills)
    # the HTTP worker's side of the same count
    parses = [st for ln in session["lines"] for n, _, _, st in ln
              if n == PREFIX + "parse"]
    assert len(parses) == len(sent)
    assert sum(st["n"] for st in parses) == sum(n for _, _, n in sent)
    assert sum(st["text"] for st in parses) == sum(
        path == "/score" for path, _, _ in sent)
    bodies = [st["bytes"] for ln in session["lines"] for n, _, _, st in ln
              if n == PREFIX + "read_body"]
    assert sorted(bodies) == sorted(len(b) for _, b, _ in sent)


def test_no_span_covers_a_wait_on_another_thread(session):
    """Neither the dispatcher on its empty queue nor an HTTP worker in
    ``batcher.result()`` carries a ``tffm:`` span."""
    idle = [(s, e) for ln in session["lines"] for n, s, e, _ in ln
            if n == IDLE_SPAN]
    assert len(idle) == 1
    lo, hi = idle[0]
    for ln in session["lines"]:
        for n, s, e, _ in ln:
            if n.startswith("tffm:"):
                assert e <= lo or s >= hi, f"{n} spans the idle stretch"
    # An HTTP worker's line holds its three phases and nothing over
    # them: between a request's parse and its respond (the wait for the
    # dispatcher) the line is bare.
    line = session["dispatcher"][0]
    workers = [ln for ln in session["lines"] if ln is not line
               and any(n.startswith(PREFIX) for n, *_ in ln)]
    assert workers
    for ln in workers:
        spans = [x for x in ln if x[0].startswith(PREFIX)]
        assert {n for n, *_ in spans} <= {PREFIX + p for p in HTTP_PHASES}
        for (name, _, end, _), (nxt, start, _, _) in zip(spans, spans[1:]):
            assert start >= end, f"{nxt} starts inside {name}"
            if nxt == PREFIX + "respond":
                assert name == PREFIX + "parse"
    # the dispatch of every request fell into such a bare stretch
    launches = [(s, e) for n, s, e, _ in line if n == PREFIX + "launch"]
    covered = 0
    for ln in workers:
        spans = [x for x in ln if x[0].startswith(PREFIX)]
        for (name, _, end, _), (nxt, start, _, _) in zip(spans, spans[1:]):
            if nxt == PREFIX + "respond":
                covered += any(end <= s and e <= start for s, e in launches)
    assert covered == len(session["requests"])


def test_replies_are_byte_identical_with_the_session_open_and_closed(session):
    assert all(status == 200 for status, _ in session["closed"])
    assert session["closed"] == session["opened"]
    for (path, _, n), (_, body) in zip(session["requests"],
                                       session["opened"]):
        got = (len(body.split()) if path == "/score"
               else len(wire.decode_bin_response(body)))
        assert got == n


def test_queue_wait_observes_unsampled_requests(session):
    """No trace_file, so no request carries a rid: the timer counts
    them all the same, and agrees with the stat on ``fill``."""
    sent = len(session["requests"])
    assert _delta(session, "serve.queue_wait", "count") == sent
    assert session["before"]["serve.queue_wait"]["count"] == sent
    fills = [st for n, _, _, st in session["dispatcher"][0]
             if n == PREFIX + "fill"]
    from_spans = sum(st["qwait_us"] for st in fills) / 1e6
    from_timer = _delta(session, "serve.queue_wait", "total_s")
    # (the stat is cut to whole microseconds per group, a snapshot's
    # total is rounded to one)
    assert abs(from_spans - from_timer) <= 1e-6 * (len(fills) + 2)
    # the phase timers counted what the spans show
    for phase in ("coalesce", "fill", "deliver", "quality"):
        spans = sum(n == PREFIX + phase
                    for n, *_ in session["dispatcher"][0])
        assert _delta(session, "serve." + phase, "count") == spans
    assert _delta(session, "serve.respond", "count") == sent
    assert _delta(session, "serve.read_body", "count") == sent
    assert (_delta(session, "serve.parse", "count")
            + _delta(session, "serve.parse_bin", "count")) == sent
    assert "serve.lock_wait" in session["after"]


def test_rid_chain_reads_the_phases_boundaries(tmp_path):
    """The per-request span chain gains ``serve.parse`` at its head and
    the dispatch's split in ``serve.dispatch``'s args, and its spans
    meet at the phases' own timestamps."""
    cfg = _cfg(tmp_path)
    tel = obs.Telemetry()
    tracer = obs.Tracer(enabled=True, process_name="replica")
    scorer = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
    scorer.warmup()
    batcher = ServeBatcher(scorer, max_batch_wait_ms=1.0, telemetry=tel,
                           tracer=tracer)
    server = ServeServer(
        0, batcher, cfg, lambda: {"record": "status"}, telemetry=tel,
        tracer=tracer, sampler=wire.RequestSampler(1.0, enabled=True))
    try:
        replies = _post_all(server.port,
                            _requests(np.random.default_rng(5))[:6],
                            clients=1)
    finally:
        server.close()
        batcher.close()
    assert all(status == 200 for status, _ in replies)
    path = str(tmp_path / "trace.json")
    tracer.dump(path)
    tracer.close()
    import json

    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("ph") == "X"]
    chains = {}
    for ev in events:
        chains.setdefault(ev["args"]["rid"], {})[ev["name"]] = ev
    assert len(chains) == 6
    for spans in chains.values():
        assert set(spans) == {"serve.parse", "serve.queue_wait",
                              "serve.coalesce", "serve.dispatch",
                              "serve.respond"}
        end = {k: v["ts"] + v["dur"] for k, v in spans.items()}
        start = {k: v["ts"] for k, v in spans.items()}
        assert end["serve.parse"] <= start["serve.queue_wait"] + 1
        # one timestamp per boundary (microseconds, rounded once each)
        assert abs(end["serve.queue_wait"] - start["serve.coalesce"]) <= 2
        assert abs(end["serve.coalesce"] - start["serve.dispatch"]) <= 2
        assert end["serve.dispatch"] <= start["serve.respond"] + 1
        args = spans["serve.dispatch"]["args"]
        assert args["launch_ms"] > 0 and args["readback_ms"] > 0
        assert (args["launch_ms"] + args["readback_ms"]
                <= spans["serve.dispatch"]["dur"] / 1e3 + 1e-3)
    from tools import report

    for chain in report.serve_request_chains(events):
        assert chain["complete"] and "parse" in chain["spans"]


def test_direct_score_carries_launch_and_readback(tmp_path):
    """``scorer.score()`` (offline predict, an oversized request) runs
    the same two phases once a chunk."""
    cfg = _cfg(tmp_path)
    tel = obs.Telemetry()
    scorer = FixedShapeScorer(cfg, _params(cfg), telemetry=tel)
    scorer.warmup()
    rng = np.random.default_rng(1)
    n = 64 + 64 + 7  # three chunks at the 64 rung
    ids = rng.integers(0, V, (n, F)).astype(np.int32)
    vals = rng.uniform(0.1, 1.0, (n, F)).astype(np.float32)
    whole = scorer.score(ids, vals)
    timers = tel.snapshot()["timers"]
    assert timers["serve.launch"]["count"] == 3
    assert timers["serve.readback"]["count"] == 3
    assert timers["serve.dispatch"]["count"] == 3
    assert timers["serve.lock_wait"]["count"] == 1
    assert (timers["serve.launch"]["total_s"]
            + timers["serve.readback"]["total_s"]
            <= timers["serve.dispatch"]["total_s"])
    # through the batcher an oversized request is one fill of one
    # request and the same three chunks
    batcher = ServeBatcher(scorer, max_batch_wait_ms=0.0, telemetry=tel)
    try:
        got = batcher.score(ids, vals)
    finally:
        batcher.close()
    assert np.array_equal(got, whole)
    timers = tel.snapshot()["timers"]
    assert timers["serve.launch"]["count"] == 6
    assert timers["serve.fill"]["count"] == 1
    assert timers["serve.queue_wait"]["count"] == 1


def test_phase_pairs_a_timer_with_a_span():
    tel = obs.Telemetry()
    timer = tel.timer("serve.fill")
    with obs.Phase(timer, "tffm:serve.fill", rung=64) as ph:
        time.sleep(0.002)
        ph.set(reqs=2, n=9)
    assert timer.count == 1
    assert ph.t1 > ph.t0 and ph.seconds == ph.t1 - ph.t0
    assert timer.total_s == pytest.approx(ph.seconds)
    assert ph.seconds >= 0.002
    # a disabled registry still gives the span and the timestamps
    with obs.Phase(obs.NULL.timer("serve.fill"), "tffm:serve.fill") as ph:
        pass
    assert ph.t1 >= ph.t0
