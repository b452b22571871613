"""Incident flight recorder + serve traffic capture (ISSUE 20
tentpole).

Pins:

  * the TFC1 capture container: write/read roundtrip byte-for-byte,
    sampling gate, rotation to ``<path>.1``, the in-memory tail
    rendered as a standalone capture, truncated-final-record drop;
  * the :class:`Blackbox` bundle contract: artifact set + the
    ``record: incident`` manifest schema, rings stay FIXED-memory
    under unbounded load, same-second collisions ordinal-retry,
    rank/replica suffixes never collide, the bundle cap, the disabled
    recorder is a no-op;
  * alert integration: an ``AlertEngine`` breach through ``on_alert``
    dumps an ``alert_<rule>`` bundle that CONTAINS the breaching
    record (ring-before-observe ordering), and ``active_snapshot``'s
    ``alerts`` block renders as ``tffm_alert_active{rule="..."}``;
  * resource vitals: ``uptime_s`` + ``open_fds`` in the basic block,
    and their alert aliases gated on ``resource_metrics`` like the
    rest of the resource plane;
  * serving e2e: capture OFF is byte-identical to capture ON
    (both transports), a capture replays BITWISE against a fresh
    server via ``tools/replay.py``, and ``POST /incident`` dumps a
    bundle live (503 with the blackbox off).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.alerts import AlertEngine, parse_rules
from fast_tffm_tpu.obs.blackbox import (
    Blackbox, NULL_BLACKBOX, _sanitize_reason,
)
from fast_tffm_tpu.serve import wire
from fast_tffm_tpu.serve.server import serve
from fast_tffm_tpu.train.loop import Trainer

_TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
)
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import replay  # noqa: E402
import report  # noqa: E402

V = 256
F = 4


def _cfg(tmp_path, **kw):
    defaults = dict(
        vocabulary_size=V, factor_num=4, max_features=F, batch_size=32,
        train_files=[str(tmp_path / "train.libsvm")],
        predict_files=[str(tmp_path / "train.libsvm")],
        score_path=str(tmp_path / "scores.txt"),
        model_file=str(tmp_path / "model"),
        epoch_num=1, log_steps=0, thread_num=1, seed=3,
        serve_batch_sizes="32,64", max_batch_wait_ms=1.0,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _write_data(path, rng, lines=256, vocab=V):
    with open(path, "w") as f:
        for i in range(lines):
            f.write(
                f"{i % 2} {rng.integers(0, vocab)}:1 "
                f"{rng.integers(0, vocab)}:0.5 "
                f"{rng.integers(0, vocab)}:0.25\n"
            )


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained dense checkpoint shared by the serve e2e tests."""
    tmp_path = tmp_path_factory.mktemp("blackbox")
    _write_data(tmp_path / "train.libsvm", np.random.default_rng(0))
    cfg = _cfg(tmp_path)
    Trainer(cfg).train()
    return tmp_path, cfg


def _frame(rng, n=5, vocab=V, feat=F):
    ids = rng.integers(0, vocab, (n, feat)).astype(np.int32)
    vals = rng.uniform(0.1, 1.0, (n, feat)).astype(np.float32)
    return wire.encode_bin_request(ids, vals, None)


def _post(url, body, timeout=60):
    req = urllib.request.Request(
        url, data=body,
        headers={"Content-Type": "application/octet-stream"},
        method="POST",
    )
    return urllib.request.urlopen(req, timeout=timeout).read()


# ----------------------------------------------------------------------
# TFC1 capture container (no jax, no sockets)
# ----------------------------------------------------------------------


class TestCaptureContainer:
    def test_roundtrip_bitwise(self, tmp_path):
        path = str(tmp_path / "req.capture")
        w = wire.CaptureWriter(path, sample=1.0, clock=lambda: 123.5)
        pairs = [(b"req-%d" % i * 3, b"resp-%d" % i) for i in range(7)]
        for req, resp in pairs:
            assert w.sample()
            w.write(req, resp)
        assert w.count == 7
        w.close()
        got = list(wire.read_capture(path))
        assert [(r, p) for _, r, p in got] == pairs
        assert all(t == 123.5 for t, _, _ in got)

    def test_sampling_gate(self, tmp_path):
        w = wire.CaptureWriter(str(tmp_path / "c"), sample=0.0)
        assert not any(w.sample() for _ in range(200))
        w.close()
        w = wire.CaptureWriter(str(tmp_path / "c2"), sample=1.0)
        assert all(w.sample() for _ in range(200))
        w.close()
        assert not w.sample()  # closed writer never samples

    def test_rotation_keeps_one_generation(self, tmp_path):
        path = str(tmp_path / "rot.capture")
        w = wire.CaptureWriter(path, sample=1.0, rotate_bytes=256)
        for i in range(40):
            w.write(b"q" * 16, bytes([i]) * 16)
        w.close()
        assert os.path.exists(path + ".1")
        # Both generations are valid standalone TFC1 files holding a
        # contiguous NEWEST-records window (older generations are
        # gone — a capture is a sliding window, not an archive).
        old = list(wire.read_capture(path + ".1"))
        new = list(wire.read_capture(path))
        assert old and len(old) + len(new) < 40
        got = [resp for _, _, resp in old + new]
        assert got == [bytes([i]) * 16 for i in
                       range(40 - len(got), 40)]

    def test_tail_bytes_is_a_standalone_capture(self, tmp_path):
        path = str(tmp_path / "t.capture")
        w = wire.CaptureWriter(path, sample=1.0, tail=4)
        for i in range(10):
            w.write(b"r%d" % i, b"s%d" % i)
        blob = w.tail_bytes()
        w.close()
        tail_path = str(tmp_path / "tail.capture")
        with open(tail_path, "wb") as f:
            f.write(blob)
        got = list(wire.read_capture(tail_path))
        assert [r for _, r, _ in got] == [b"r6", b"r7", b"r8", b"r9"]

    def test_truncated_final_record_dropped(self, tmp_path):
        path = str(tmp_path / "trunc.capture")
        w = wire.CaptureWriter(path, sample=1.0)
        for i in range(5):
            w.write(b"req" * 10, b"resp" * 10)
        w.close()
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 7)  # the writer died mid-append
        got = list(wire.read_capture(path))
        assert len(got) == 4  # intact prefix survives, no exception

    def test_bad_header_raises(self, tmp_path):
        path = str(tmp_path / "bad")
        with open(path, "wb") as f:
            f.write(b"NOPE\x01\x00\x00\x00")
        with pytest.raises(ValueError, match="magic"):
            list(wire.read_capture(path))

    def test_telemetry_counts_appends(self, tmp_path):
        tel = obs.Telemetry()
        w = wire.CaptureWriter(
            str(tmp_path / "c.capture"), sample=1.0, telemetry=tel
        )
        for _ in range(3):
            w.write(b"a", b"b")
        w.close()
        snap = tel.snapshot()
        assert snap["counters"]["serve.capture_requests"] == 3


# ----------------------------------------------------------------------
# Blackbox: bundle schema, rings, collisions, cap
# ----------------------------------------------------------------------


def _bb(tmp_path, **kw):
    kw.setdefault("suffix", "rank0")
    return Blackbox(str(tmp_path / "incidents"), **kw)


class TestBlackbox:
    def test_sanitize_reason(self):
        assert _sanitize_reason("alert_rss_mb>40000") == "alert_rss_mb_40000"
        assert _sanitize_reason("../../etc/passwd") == "etc_passwd"
        assert _sanitize_reason("") == "incident"
        assert len(_sanitize_reason("x" * 500)) == 64

    def test_bundle_schema(self, tmp_path):
        rows = []

        class W:
            def write(self, rec):
                rows.append(rec)

        bb = _bb(
            tmp_path,
            run_header={"record": "run_header", "batch_size": 32},
            metrics_render=lambda: "tffm_up 1\n",
            trace_tail_fn=lambda n: [{"ph": "X", "name": "t", "dur": 5}],
            capture_tail_fn=lambda: wire.CAPTURE_MAGIC + b"\x01\x00\x00\x00",
            writer=W(),
        )
        bb.observe_record({"record": "heartbeat", "step": 1})
        bb.observe_alert({"record": "alert", "rule": "r"})
        out = bb.incident("manual_test")
        assert out is not None and os.path.isdir(out)
        assert "_rank0" in os.path.basename(out)
        man = json.load(open(os.path.join(out, "manifest.json")))
        assert man["record"] == "incident"
        assert man["reason"] == "manual_test"
        assert man["suffix"] == "rank0"
        assert man["records"] == 1 and man["alerts"] == 1
        for name in ("records.jsonl", "alerts.jsonl", "threadz.txt",
                     "run_header.json", "trace_tail.json", "metrics.prom",
                     "requests.capture"):
            assert man["files"][name] is True
            assert os.path.exists(os.path.join(out, name)), name
        recs = [json.loads(ln) for ln in
                open(os.path.join(out, "records.jsonl"))]
        assert recs == [{"record": "heartbeat", "step": 1}]
        assert "--- thread" in open(os.path.join(out, "threadz.txt")).read()
        hdr = json.loads(open(os.path.join(out, "run_header.json")).read())
        assert hdr["batch_size"] == 32
        # The manifest is ALSO a metrics-stream record.
        assert rows and rows[-1]["record"] == "incident"

    def test_rings_fixed_memory(self, tmp_path):
        bb = _bb(tmp_path, records=16, alerts=8)
        for i in range(5000):
            bb.observe_record({"record": "heartbeat", "step": i})
            bb.observe_alert({"record": "alert", "i": i})
        assert len(bb._records) == 16
        assert len(bb._alerts) == 8
        out = bb.incident("load")
        recs = [json.loads(ln) for ln in
                open(os.path.join(out, "records.jsonl"))]
        # Oldest-first, and only the newest 16 survive.
        assert [r["step"] for r in recs] == list(range(4984, 5000))

    def test_same_second_collision_gets_ordinal(self, tmp_path):
        bb = _bb(tmp_path, clock=lambda: 1754000000.0)
        a = bb.incident("flap")
        b = bb.incident("flap")
        assert a != b and os.path.isdir(a) and os.path.isdir(b)
        assert os.path.basename(b) == os.path.basename(a) + "-2"

    def test_rank_replica_suffixes_never_collide(self, tmp_path):
        clock = lambda: 1754000000.0  # noqa: E731 - frozen clock
        dirs = set()
        for sfx in ("rank0", "rank1", "pid7", "router"):
            bb = Blackbox(
                str(tmp_path / "incidents"), suffix=sfx, clock=clock
            )
            out = bb.incident("oom")
            assert out is not None and sfx in os.path.basename(out)
            dirs.add(out)
        assert len(dirs) == 4

    def test_bundle_cap(self, tmp_path):
        bb = _bb(tmp_path, max_bundles=3, clock=lambda: 1754000000.0)
        outs = [bb.incident(f"r{i}") for i in range(6)]
        assert sum(o is not None for o in outs) == 3
        assert outs[3] is None and bb.dumped == 3

    def test_disabled_is_noop(self, tmp_path):
        bb = Blackbox(str(tmp_path / "inc"), enabled=False)
        bb.observe_record({"record": "heartbeat"})
        bb.on_alert({"record": "alert", "rule": "r"})
        assert bb.incident("nope") is None
        assert not os.path.exists(str(tmp_path / "inc"))
        assert NULL_BLACKBOX.incident("x") is None

    def test_broken_artifact_degrades_not_propagates(self, tmp_path):
        def boom():
            raise RuntimeError("metrics renderer died")

        bb = _bb(tmp_path, metrics_render=boom)
        bb.observe_record({"record": "heartbeat", "step": 9})
        out = bb.incident("partial")
        man = json.load(open(os.path.join(out, "manifest.json")))
        assert man["files"]["metrics.prom"] is False
        assert man["files"]["records.jsonl"] is True

    def test_report_renders_bundle(self, tmp_path, capsys):
        # ``report.py --incident`` on a bundle whose rings hold several
        # readings of a signal: every section prints, the trajectory
        # row sparklines oldest -> newest.
        bb = _bb(
            tmp_path,
            trace_tail_fn=lambda n: [{"ph": "X", "name": "step", "dur": 7}],
        )
        for step, rss in enumerate((100.0, 150.0, 400.0), 1):
            bb.observe_record({
                "record": "heartbeat", "step": step,
                "resource": {"rss_mb": rss, "uptime_s": float(step)},
            })
        bb.observe_alert({
            "record": "alert", "rule": "uptime", "action": "incident",
            "signal": "uptime_s", "value": 3.0, "op": ">",
            "threshold": 2.0, "step": 3,
        })
        out = bb.incident("alert_uptime")
        assert report.main(["--incident", out]) == 0
        text = capsys.readouterr().out
        assert "incident: alert_uptime" in text
        assert "3 record(s), 1 alert(s)" in text
        assert "signal trajectory" in text
        rows = {ln.split()[0]: ln for ln in text.splitlines()
                if ln.startswith("  ") and "->" in ln}
        assert "100 -> 400" in rows["resource.rss_mb"]
        assert "\u2581" in rows["resource.rss_mb"]
        assert "\u2588" in rows["resource.rss_mb"]
        assert "1 -> 3" in rows["uptime_s"] and "1 -> 3" in rows["step"]
        assert "trace tail critical path" in text

    def test_report_flat_signal_and_missing_manifest(self, tmp_path, capsys):
        bb = _bb(tmp_path)
        for _ in range(2):
            bb.observe_record({"record": "heartbeat", "step": 5})
        assert report.main(["--incident", bb.incident("flat")]) == 0
        assert "5 -> 5" in capsys.readouterr().out
        assert report.main(["--incident", str(tmp_path / "absent")]) == 1


# ----------------------------------------------------------------------
# Alert integration: breach -> bundle; the alerts block surface
# ----------------------------------------------------------------------


class TestAlertIntegration:
    def test_breach_dumps_bundle_with_evidence(self, tmp_path):
        bb = _bb(tmp_path)
        eng = AlertEngine(
            parse_rules("ingest_wait_frac > 0.5 : warn"),
            on_alert=bb.on_alert,
        )
        rec = {"record": "heartbeat", "step": 3,
               "ingest_wait_frac": 0.9, "time": 1.0}
        # Ring-before-observe: the breaching record must be IN the
        # bundle (the ordering every heartbeat loop follows).
        bb.observe_record(rec)
        fired = eng.observe(rec)
        assert len(fired) == 1
        inc_root = str(tmp_path / "incidents")
        bundles = os.listdir(inc_root)
        assert len(bundles) == 1
        assert bundles[0].split("_", 1)[1].startswith("alert_")
        out = os.path.join(inc_root, bundles[0])
        recs = [json.loads(ln) for ln in
                open(os.path.join(out, "records.jsonl"))]
        assert recs[-1]["step"] == 3
        alerts = [json.loads(ln) for ln in
                  open(os.path.join(out, "alerts.jsonl"))]
        assert alerts[-1]["rule"] == "ingest_wait_frac>0.5"

    def test_active_snapshot_shape(self):
        eng = AlertEngine(parse_rules(
            "ingest_wait_frac > 0.5 for 3 : warn ; rss_mb > 1 : halt"
        ))
        snap = eng.active_snapshot()
        assert snap["armed"] == 2
        assert snap["fired_total"] == 0 and snap["halted"] == 0
        assert [r["action"] for r in snap["rules"]] == ["warn", "halt"]
        beat = {"record": "heartbeat", "ingest_wait_frac": 0.9,
                "time": 1.0}
        eng.observe(beat)
        rule = eng.active_snapshot()["rules"][0]
        # Sustain 3: one breaching beat advances the streak but the
        # episode is not live yet.
        assert rule["active"] == 0 and rule["streak"] == 1
        eng.observe(beat)
        eng.observe(beat)
        rule = eng.active_snapshot()["rules"][0]
        assert rule["active"] == 1 and rule["streak"] == 3

    def test_alert_active_renders_labeled_gauge(self):
        eng = AlertEngine(parse_rules("ingest_wait_frac > 0.5 : warn"))
        eng.observe({"record": "heartbeat", "ingest_wait_frac": 0.9,
                     "time": 1.0})
        rec = {"record": "status", "alerts": eng.active_snapshot()}
        text = obs.render_prometheus(rec)
        assert ('tffm_alert_active{rule="ingest_wait_frac>0.5"} 1'
                in text)
        # The block scalars render like every other block's.
        assert "tffm_alerts_armed 1" in text
        assert "tffm_alerts_fired_total 1" in text

    def test_vitals_aliases_gated_on_resource_metrics(self, tmp_path):
        _write_data(tmp_path / "train.libsvm", np.random.default_rng(1), 8)
        ok = _cfg(tmp_path, heartbeat_secs=1.0,
                  alert_rules="uptime_s > 3600 : warn ; open_fds > 4096 : warn")
        assert ok.alert_rules  # resolves with the plane on (default)
        with pytest.raises(ValueError, match="resource_metrics"):
            _cfg(tmp_path, heartbeat_secs=1.0, resource_metrics=False,
                 alert_rules="uptime_s > 3600 : warn")


class TestResourceVitals:
    def test_read_open_fds(self):
        n = obs.read_open_fds()
        if not os.path.isdir("/proc/self/fd"):
            assert n == -1
        else:
            assert n > 0

    def test_basic_block(self):
        blk = obs.basic_block(0.0)
        assert blk["uptime_s"] > 0
        assert blk["rss_mb"] >= 0
        if os.path.isdir("/proc/self/fd"):
            assert blk["open_fds"] > 0


# ----------------------------------------------------------------------
# Serving e2e: capture off == on (byte-identical), capture -> replay
# bitwise, POST /incident
# ----------------------------------------------------------------------


class TestServeCapture:
    def test_capture_off_is_byte_identical(self, trained, rng):
        """The acceptance pin: turning capture + blackbox ON must not
        perturb a single response byte, on either transport."""
        tmp_path, cfg = trained
        cap_cfg = dataclasses.replace(
            cfg,
            serve_capture_sample=1.0,
            serve_capture_file=str(tmp_path / "cap_parity.capture"),
            incident_dir=str(tmp_path / "inc_parity"),
        )
        frames = [_frame(rng, n) for n in (1, 5, 17)]
        text = "1 5:0.5 9:0.25\n0 7:1 3:0.5\n"
        off = serve(cfg, port=0)
        try:
            plain_bin = [
                _post(f"http://127.0.0.1:{off.port}/score_bin", fr)
                for fr in frames
            ]
            plain_txt = _post(
                f"http://127.0.0.1:{off.port}/score", text.encode()
            )
            assert off.capture is None  # off = the feature does not exist
        finally:
            off.close()
        on = serve(cap_cfg, port=0)
        try:
            for fr, want in zip(frames, plain_bin):
                got = _post(f"http://127.0.0.1:{on.port}/score_bin", fr)
                assert got == want  # byte-identical
            got_txt = _post(
                f"http://127.0.0.1:{on.port}/score", text.encode()
            )
            assert got_txt == plain_txt
            assert on.capture is not None and on.capture.count >= 4
        finally:
            on.close()

    def test_capture_replays_bitwise(self, trained, rng):
        tmp_path, cfg = trained
        cap_path = str(tmp_path / "replayme.capture")
        cap_cfg = dataclasses.replace(
            cfg, serve_capture_sample=1.0, serve_capture_file=cap_path,
        )
        handle = serve(cap_cfg, port=0)
        try:
            for n in (1, 3, 9, 30):
                _post(f"http://127.0.0.1:{handle.port}/score_bin",
                      _frame(rng, n))
            # A TEXT request captures too — as a canonical binary
            # frame, replayable through /score_bin.
            _post(f"http://127.0.0.1:{handle.port}/score",
                  b"1 5:0.5 9:0.25\n")
        finally:
            handle.close()
        records = list(wire.read_capture(cap_path))
        assert len(records) == 5
        # Replay against a FRESH capture-off server: bitwise parity.
        fresh = serve(cfg, port=0)
        try:
            rc = replay.replay(
                cap_path, f"http://127.0.0.1:{fresh.port}",
                out=sys.stderr,
            )
            assert rc == 0
            # And a corrupted response must be CAUGHT (exit 2).
            t, req, resp = records[0]
            bad = bytearray(resp)
            bad[-1] ^= 0x01
            bad_path = str(tmp_path / "bad.capture")
            with open(bad_path, "wb") as f:
                f.write(wire.CAPTURE_MAGIC)
                f.write((1).to_bytes(4, "little"))
                f.write(wire._CAP_REC.pack(t, len(req), len(bad)))
                f.write(req)
                f.write(bytes(bad))
            assert replay.replay(
                bad_path, f"http://127.0.0.1:{fresh.port}",
                out=sys.stderr,
            ) == 2
        finally:
            fresh.close()

    def test_post_incident_route(self, trained, rng):
        tmp_path, cfg = trained
        inc_root = str(tmp_path / "inc_manual")
        bb_cfg = dataclasses.replace(cfg, incident_dir=inc_root)
        handle = serve(bb_cfg, port=0)
        try:
            _post(f"http://127.0.0.1:{handle.port}/score_bin",
                  _frame(rng, 2))
            doc = json.loads(_post(
                f"http://127.0.0.1:{handle.port}/incident?reason=smoke",
                b"",
            ))
            out = doc["incident_dir"]
            assert os.path.isdir(out)
            assert "smoke" in os.path.basename(out)
            assert "_pid" in os.path.basename(out)
            man = json.load(open(os.path.join(out, "manifest.json")))
            assert man["record"] == "incident"
        finally:
            handle.close()
        # Blackbox off -> the route answers 503, and nothing dumps.
        off_cfg = dataclasses.replace(cfg, blackbox=False)
        handle = serve(off_cfg, port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://127.0.0.1:{handle.port}/incident", b"")
            assert ei.value.code == 503
        finally:
            handle.close()
