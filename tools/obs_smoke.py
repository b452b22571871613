#!/usr/bin/env python
"""Live-observability smoke: a real 20-step CLI run with --status_port,
scraped over HTTP while it trains.

tools/verify.sh runs this before the tier-1 gate.  It exercises the
exact production path — ``run_tffm.py train <cfg> --status_port`` in a
SUBPROCESS (pinned to CPU), not an in-process Trainer — and asserts:

1. ``/status`` answers mid-run with well-formed JSON carrying the
   heartbeat-record shape (``record``, ``step``, ``stages``) plus the
   resource block;
2. ``/metrics`` answers non-empty, every line Prometheus-parseable
   (``# HELP``/``# TYPE`` comments or ``name{labels} value``), and
   includes the core series + the ``tffm_build_info`` identity gauge;
3. ``/debug/threadz`` serves an all-thread stack dump naming the
   pipeline's threads;
4. ``/profile?secs=N`` captures one profiler window mid-run, and its
   busy-guard rejects a CONCURRENT second request with 409;
5. the run itself exits 0, and its final record carries a non-empty
   ``quality`` block (windowed online eval + drift sketches ran).

Then the SERVE smoke (the online scoring path, SERVING.md) against the
checkpoint that run just wrote — ``run_tffm.py serve`` in a subprocess:

6. ``POST /score`` answers with one parseable score per input line;
7. ``/metrics`` serves the ``tffm_serve_*`` series (Prometheus-valid);
8. a second short training run into the same model dir republishes the
   checkpoint manifest, and the server HOT-SWAPS exactly as designed
   (``tffm_counter_serve_swaps_total`` reaches 1) while still scoring;
8b. training→serving skew END TO END: identity traffic (lines from
   the training file) reads stable against the manifest's training
   sketches, and a shifted request population (foreign ids, 100x
   values) breaches ``tffm_serve_skew_psi_max`` > 0.25 on /metrics;
8c. request hot path (ISSUE 16): the pooled-accept + vectorized-parse
   defaults answer BYTE-IDENTICALLY to a second server mounted with
   ``--serve_http_threads 0 --serve_parse_mode legacy``, and an
   in-process ``PooledHTTPServer`` start/score/close cycle leaks no
   worker or acceptor threads.

Then the ROUTER smoke (scale-out serving, SERVING.md "Scale-out") —
``run_tffm.py serve --replicas 2`` in a subprocess, with per-request
tracing sampled at 1.0 (``--trace`` + ``--serve_trace_sample 1``):

9.  the router answers ``/score`` AND the binary ``/score_bin`` (a
    hand-rolled frame pinning the documented wire layout) with
    IDENTICAL scores for the same examples, every response echoing an
    ``X-Request-Id``;
10. the router's ``/metrics`` exposes the FLEET: aggregated
    ``tffm_serve_fleet_*`` series and per-replica labeled series
    scraped from each replica's ``/status`` — one scrape sees the
    whole fleet;
11. SIGKILLing one replica MID-TRACE loses no requests (transparent
    retry) and the router's ``/metrics`` shows the eviction
    (``tffm_counter_serve_evictions_total`` >= 1, the replica's
    ``tffm_serve_replica_healthy`` series at 0);
12. the RESPAWN policy relaunches the killed managed replica
    (``tffm_counter_serve_respawns_total`` >= 1) and the health loop
    readmits it (``tffm_serve_replica_healthy{replica="0"} 1``);
13. terminating the router tears down every replica subprocess — no
    orphaned jax processes — and dumps the trace family;
14. ``tools/report.py --serve-trace`` re-joins the router + surviving
    replica traces into COMPLETE per-request chains (admit -> proxy ->
    queue -> coalesce -> dispatch -> respond), the SIGKILLed
    replica's lost spans notwithstanding.

Then the INCIDENT smoke (flight recorder + capture/replay, ISSUE 20) —
``run_tffm.py serve`` with an always-breaching alert rule, full-sample
traffic capture, and an explicit ``--incident_dir``:

15. the breach dumps a VALID forensic bundle (manifest naming the
    rule, heartbeat ring with the ``alerts`` block, threadz dump, a
    /metrics snapshot carrying ``tffm_alert_active{rule=...}`` — also
    asserted on the LIVE endpoint), its dir name pid-suffixed;
    ``POST /incident?reason=...`` dumps a second, manually-named
    bundle and answers its dir as JSON;
16. ``tools/report.py --incident <bundle>`` renders the summary
    (rule fired, signal trajectory) and exits 0;
17. the TFC1 capture file replays BITWISE against a fresh serve
    subprocess on the same checkpoint (``tools/replay.py`` exit 0) —
    the capture/replay loop closes end to end.

The training stage also asserts the ``record: profile`` entry the
``/profile`` capture writes, and the resource block's
``uptime_s``/``open_fds`` vitals.

Exit 0 = all held; any other exit fails the audit.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One sample line per Prometheus text-format metric: bare name or
# name{labels}, then a number (int/float/scientific/inf/nan).
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"[-+]?(\d+\.?\d*([eE][-+]?\d+)?|\.\d+|[Ii]nf|[Nn]a[Nn])$"
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _gen_data(path: str, n_lines: int = 6400, vocab: int = 50) -> None:
    import random

    rng = random.Random(0)
    with open(path, "w") as f:
        for _ in range(n_lines):
            feats = rng.sample(range(vocab), 3)
            toks = " ".join(
                f"{i}:{rng.uniform(0.1, 1.0):.3f}" for i in feats
            )
            f.write(f"{rng.randint(0, 1)} {toks}\n")


def _scrape_both(port: int, deadline: float, proc) -> tuple:
    """(status_bytes, metrics_bytes) fetched back-to-back mid-run.

    The server is up for the whole of train() (it outlives jit compile
    and every dispatch), so one retry loop covers both routes; a child
    that dies before answering fails fast instead of burning the
    deadline.
    """
    base = f"http://127.0.0.1:{port}"
    last_err = None
    while time.time() < deadline:
        try:
            status = urllib.request.urlopen(
                f"{base}/status", timeout=2).read()
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=2).read()
            return status, metrics
        except (urllib.error.URLError, OSError) as e:
            last_err = e
            if proc.poll() is not None:
                out, _ = proc.communicate()
                sys.stderr.write(out.decode(errors="replace")[-2000:])
                raise SystemExit(
                    f"FAIL: run exited {proc.returncode} before the "
                    f"status endpoint answered ({e})"
                )
            time.sleep(0.1)
    raise SystemExit(f"FAIL: {base} unreachable before deadline "
                     f"({last_err})")


def check_prometheus(text: str) -> int:
    """Validate Prometheus exposition text; returns the sample count."""
    samples = 0
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip() or line.startswith("#"):
            continue
        if not _SAMPLE.match(line):
            raise SystemExit(
                f"FAIL: /metrics line {lineno} is not Prometheus-"
                f"parseable: {line!r}"
            )
        samples += 1
    if samples == 0:
        raise SystemExit("FAIL: /metrics served zero samples")
    return samples


def _get(port: int, route: str, timeout: float = 30.0) -> tuple:
    """(http_code, body bytes) — HTTPError codes return, not raise."""
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{route}", timeout=timeout
        )
        return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def check_capture_routes(port: int) -> None:
    """/debug/threadz + the /profile busy-guard, mid-run.

    The guard contract: while one capture window is open, a second
    request gets 409 — so request A (a 0.5 s window; the process's
    FIRST capture also pays jax's one-time ~5 s profiler init, which
    the guard covers too) runs on a thread, request B fires into the
    middle of it, and both responses are asserted.  Runs right after
    the first successful scrape — early in the run, so the sized-up
    smoke run (see _run) cannot end under the open capture.
    """
    code, body = _get(port, "/debug/threadz")
    if code != 200:
        raise SystemExit(f"FAIL: /debug/threadz answered {code}")
    text = body.decode(errors="replace")
    if "--- thread" not in text or "MainThread" not in text:
        raise SystemExit(
            f"FAIL: /debug/threadz is not a thread dump: {text[:200]!r}"
        )
    results: dict = {}

    def slow_profile():
        # Store failures too: a connection reset (training subprocess
        # dying mid-capture) must surface as a FAIL diagnostic below,
        # not a KeyError in the main thread.
        try:
            results["a"] = _get(port, "/profile?secs=0.5", timeout=60)
        except Exception as exc:
            results["error"] = exc

    t = threading.Thread(target=slow_profile)
    t.start()
    time.sleep(0.5)  # give A a head start toward the capture lock
    code_b, body_b = _get(port, "/profile?secs=0.5")
    t.join()
    if "a" not in results:
        raise SystemExit(
            f"FAIL: /profile capture got no HTTP response "
            f"(run died mid-capture?): {results.get('error')!r}"
        )
    # The guard contract is about the PAIR, not the order: on a loaded
    # box request B can reach the lock first, so accept either winner —
    # exactly one 200 (with a capture dir) and one 409.
    pair = {"a": results["a"], "b": (code_b, body_b)}
    codes = sorted(code for code, _ in pair.values())
    if codes != [200, 409]:
        raise SystemExit(
            f"FAIL: concurrent /profile pair answered {codes}, wanted "
            f"exactly one 200 and one busy-guard 409"
        )
    winner = next(body for code, body in pair.values() if code == 200)
    doc = json.loads(winner)
    if not doc.get("profile_dir"):
        raise SystemExit(f"FAIL: /profile response names no dir: {doc}")
    print(f"capture routes ok: threadz dumped "
          f"{text.count('--- thread')} thread(s), /profile wrote "
          f"{doc['profile_dir']}, concurrent request got 409")


def check_serve(cfg_path: str, data: str) -> None:
    """Serve smoke: score over the socket, scrape tffm_serve_*, and
    assert one warm hot-swap when the trainer republishes the
    checkpoint.  Runs against the model dir the training smoke wrote."""
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "serve",
         cfg_path, "--serve_port", str(port),
         "--serve_poll_secs", "0.2"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        base = f"http://127.0.0.1:{port}"
        deadline = time.time() + 120
        while True:
            try:
                urllib.request.urlopen(f"{base}/healthz", timeout=2)
                break
            except (urllib.error.URLError, OSError) as e:
                if proc.poll() is not None:
                    out, _ = proc.communicate()
                    sys.stderr.write(
                        out.decode(errors="replace")[-2000:]
                    )
                    raise SystemExit(
                        f"FAIL: serve exited {proc.returncode} before "
                        f"answering ({e})"
                    )
                if time.time() > deadline:
                    raise SystemExit(
                        f"FAIL: serve endpoint unreachable ({e})"
                    )
                time.sleep(0.2)
        with open(data) as f:
            lines = "".join(f.readline() for _ in range(10))
        req = urllib.request.Request(
            f"{base}/score", data=lines.encode(), method="POST"
        )
        body = urllib.request.urlopen(req, timeout=30).read().decode()
        scores = body.strip().splitlines()
        if len(scores) != 10 or not all(
            0.0 <= float(s) <= 1.0 for s in scores
        ):
            raise SystemExit(
                f"FAIL: /score answered {len(scores)} line(s) for 10 "
                f"examples: {body[:200]!r}"
            )
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=10).read().decode()
        check_prometheus(metrics)
        for series in ("tffm_counter_serve_requests_total",
                       "tffm_counter_serve_examples_total",
                       "tffm_timer_serve_latency_p99_ms",
                       "tffm_gauge_serve_batch_fill"):
            if series not in metrics:
                raise SystemExit(
                    f"FAIL: /metrics missing serve series {series}"
                )
        # Hot swap: a short warm-start training run into the same model
        # dir republishes the manifest; the server must swap without
        # dropping its socket.
        swap_cfg = cfg_path + ".swap"
        with open(cfg_path) as f:
            content = f.read().replace("epoch_num = 20", "epoch_num = 1")
        with open(swap_cfg, "w") as f:
            f.write(content)
        train = subprocess.run(
            [sys.executable, os.path.join(REPO, "run_tffm.py"), "train",
             swap_cfg],
            cwd=REPO, env=env, capture_output=True, timeout=180,
        )
        if train.returncode != 0:
            sys.stderr.write(
                train.stdout.decode(errors="replace")[-2000:]
            )
            raise SystemExit(
                f"FAIL: hot-swap training run exited {train.returncode}"
            )
        deadline = time.time() + 60
        swaps = 0
        while time.time() < deadline:
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            m = re.search(
                r"^tffm_counter_serve_swaps_total (\d+)", metrics,
                re.MULTILINE,
            )
            swaps = int(m.group(1)) if m else 0
            if swaps >= 1:
                break
            time.sleep(0.3)
        if swaps < 1:
            raise SystemExit(
                "FAIL: server never hot-swapped after the checkpoint "
                "manifest was republished"
            )
        body2 = urllib.request.urlopen(
            urllib.request.Request(
                f"{base}/score", data=lines.encode(), method="POST"
            ), timeout=30,
        ).read().decode()
        if len(body2.strip().splitlines()) != 10:
            raise SystemExit("FAIL: /score broken after hot-swap")
        # Training→serving skew, end to end over the socket: identity
        # traffic (lines from the training file itself) must read
        # stable against the manifest's training sketches; a shifted
        # request population (foreign ids, 100x values) must breach
        # tffm_serve_skew_* — the ISSUE 15 acceptance path.
        with open(data) as f:
            identity = "".join(f.readline() for _ in range(200))
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/score", data=identity.encode(), method="POST"
        ), timeout=30).read()
        status = json.loads(urllib.request.urlopen(
            f"{base}/status", timeout=10).read())
        serve_block = status.get("serve") or {}
        if serve_block.get("skew_ref_step", -1) < 0:
            raise SystemExit(
                "FAIL: serve has no skew reference — the training "
                f"smoke's manifest carried no sketches: {serve_block}"
            )
        if serve_block.get("skew_psi_max", 1.0) > 0.25:
            raise SystemExit(
                "FAIL: identity traffic reads as skewed "
                f"(skew_psi_max {serve_block.get('skew_psi_max')})"
            )
        shifted = "".join(
            "0 " + " ".join(
                f"{45 + (i + j) % 5}:{(1 + j) * 100}" for j in range(4)
            ) + "\n"
            for i in range(300)
        )
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/score", data=shifted.encode(), method="POST"
        ), timeout=30).read()
        time.sleep(0.6)  # skew block memo window
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=10).read().decode()
        m = re.search(
            r"^tffm_serve_skew_psi_max ([0-9.eE+-]+)", metrics,
            re.MULTILINE,
        )
        if m is None or float(m.group(1)) <= 0.25:
            raise SystemExit(
                "FAIL: shifted traffic did not breach "
                f"tffm_serve_skew_psi_max (got "
                f"{m.group(1) if m else 'no series'})"
            )
        # Request hot path (ISSUE 16): the pooled-accept + vectorized
        # parser stack (the defaults above) must be byte-identical on
        # the wire to the legacy thread-per-connection +
        # per-line-parser stack.  Second serve subprocess on the same
        # model dir with both knobs flipped, same request body,
        # compare responses byte for byte.
        l_port = _free_port()
        l_proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "run_tffm.py"),
             "serve", cfg_path, "--serve_port", str(l_port),
             "--serve_poll_secs", "0.2",
             "--serve_http_threads", "0",
             "--serve_parse_mode", "legacy"],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        try:
            l_base = f"http://127.0.0.1:{l_port}"
            deadline = time.time() + 120
            while True:
                try:
                    urllib.request.urlopen(
                        f"{l_base}/healthz", timeout=2)
                    break
                except (urllib.error.URLError, OSError) as e:
                    if l_proc.poll() is not None:
                        out, _ = l_proc.communicate()
                        sys.stderr.write(
                            out.decode(errors="replace")[-2000:]
                        )
                        raise SystemExit(
                            f"FAIL: legacy-mode serve exited "
                            f"{l_proc.returncode} before answering "
                            f"({e})"
                        )
                    if time.time() > deadline:
                        raise SystemExit(
                            f"FAIL: legacy-mode serve unreachable ({e})"
                        )
                    time.sleep(0.2)
            pooled_body = urllib.request.urlopen(
                urllib.request.Request(
                    f"{base}/score", data=lines.encode(), method="POST"
                ), timeout=30).read()
            legacy_body = urllib.request.urlopen(
                urllib.request.Request(
                    f"{l_base}/score", data=lines.encode(),
                    method="POST"
                ), timeout=30).read()
            if pooled_body != legacy_body:
                raise SystemExit(
                    "FAIL: pooled/vec serve stack is not "
                    "byte-identical to the legacy accept+parser: "
                    f"{pooled_body[:100]!r} vs {legacy_body[:100]!r}"
                )
        finally:
            if l_proc.poll() is None:
                l_proc.terminate()
                try:
                    l_proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    l_proc.kill()
                    l_proc.wait()
        # ISSUE 16: pooled server teardown must leak no worker or
        # acceptor thread — in-process so the thread set is ours to
        # enumerate.
        from http.server import BaseHTTPRequestHandler

        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from fast_tffm_tpu.obs.status import PooledHTTPServer

        class _NoopHandler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - http.server API name
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, *args):
                pass

        hs = PooledHTTPServer(("127.0.0.1", 0), _NoopHandler,
                              pool_size=4, acceptors=2)
        st = threading.Thread(target=hs.serve_forever, daemon=True)
        st.start()
        urllib.request.urlopen(
            f"http://127.0.0.1:{hs.server_address[1]}/", timeout=10
        ).read()
        hs.shutdown()
        st.join(timeout=10)
        hs.server_close()
        leaked = [
            t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("tffm-http-")
        ]
        if leaked:
            raise SystemExit(
                f"FAIL: PooledHTTPServer teardown leaked threads: "
                f"{leaked}"
            )
        print(f"serve smoke ok: scored 10/10 over the socket, "
              f"tffm_serve_* series present, {swaps} hot-swap(s) "
              f"mid-traffic, skew breach visible "
              f"(tffm_serve_skew_psi_max {float(m.group(1)):.2f} "
              f"after shifted traffic), pooled==legacy byte-identical, "
              f"pooled teardown leaked 0 threads")
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def _wait_healthz(base: str, proc, what: str,
                  timeout_s: float = 120.0) -> None:
    deadline = time.time() + timeout_s
    while True:
        try:
            urllib.request.urlopen(f"{base}/healthz", timeout=2)
            return
        except (urllib.error.URLError, OSError) as e:
            if proc.poll() is not None:
                out, _ = proc.communicate()
                sys.stderr.write(out.decode(errors="replace")[-2000:])
                raise SystemExit(
                    f"FAIL: {what} exited {proc.returncode} before "
                    f"answering ({e})"
                )
            if time.time() > deadline:
                raise SystemExit(f"FAIL: {what} unreachable ({e})")
            time.sleep(0.2)


def check_incident(cfg_path: str, data: str) -> None:
    """Incident flight recorder + traffic capture, end to end (ISSUE
    20): a real serve subprocess with an always-breaching alert rule
    and full-sample capture; asserts

    a. the breach dumps a VALID forensic bundle (manifest + rings +
       threadz + metrics snapshot), its dir name carrying the pid
       suffix and an ``alert_`` reason;
    b. ``POST /incident?reason=...`` dumps a second, manually-named
       bundle and answers its dir as JSON;
    c. ``tools/report.py --incident`` renders the bundle (rule fired,
       signal trajectory) and exits 0;
    d. the capture file replays against a FRESH server on the same
       checkpoint with bitwise score parity (``tools/replay.py``
       exit 0).
    """
    tmpdir = os.path.dirname(cfg_path)
    incident_dir = os.path.join(tmpdir, "incidents")
    capture_file = os.path.join(tmpdir, "requests.capture")
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "serve",
         cfg_path, "--serve_port", str(port),
         "--serve_poll_secs", "0",
         # uptime_s is alive from the first heartbeat, so this rule
         # breaches ~0.2 s in — the injected incident.
         "--alert_rules", "uptime_s > 0 : warn",
         "--incident_dir", incident_dir,
         "--serve_capture_sample", "1",
         "--serve_capture_file", capture_file],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        base = f"http://127.0.0.1:{port}"
        _wait_healthz(base, proc, "incident-smoke serve")
        # Traffic for the capture file (sample 1.0 records every one).
        with open(data) as f:
            lines = "".join(f.readline() for _ in range(10))
        for _ in range(3):
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/score", data=lines.encode(), method="POST"
            ), timeout=30).read()
        # (a) the breach-triggered bundle.
        deadline = time.time() + 60
        bundle = None
        while time.time() < deadline:
            if os.path.isdir(incident_dir):
                for name in sorted(os.listdir(incident_dir)):
                    man = os.path.join(
                        incident_dir, name, "manifest.json"
                    )
                    if "alert_" in name and os.path.exists(man):
                        bundle = os.path.join(incident_dir, name)
                        break
            if bundle:
                break
            if proc.poll() is not None:
                out, _ = proc.communicate()
                sys.stderr.write(out.decode(errors="replace")[-2000:])
                raise SystemExit(
                    f"FAIL: serve exited {proc.returncode} before "
                    f"dumping the alert bundle"
                )
            time.sleep(0.1)
        if bundle is None:
            raise SystemExit(
                f"FAIL: alert breach dumped no incident bundle under "
                f"{incident_dir}"
            )
        if "_pid" not in os.path.basename(bundle):
            raise SystemExit(
                f"FAIL: bundle dir carries no pid suffix: {bundle}"
            )
        with open(os.path.join(bundle, "manifest.json")) as f:
            manifest = json.load(f)
        if not manifest.get("reason", "").startswith("alert_"):
            raise SystemExit(
                f"FAIL: manifest reason {manifest.get('reason')!r} "
                f"does not name the breached rule"
            )
        records = [
            json.loads(line)
            for line in open(os.path.join(bundle, "records.jsonl"))
        ]
        if not records or records[-1].get("record") != "heartbeat":
            raise SystemExit(
                f"FAIL: bundle records ring empty or malformed "
                f"({len(records)} records)"
            )
        if (records[-1].get("alerts") or {}).get("armed") != 1:
            raise SystemExit(
                "FAIL: ringed record carries no alerts block: "
                f"{records[-1].get('alerts')}"
            )
        with open(os.path.join(bundle, "threadz.txt")) as f:
            threadz = f.read()
        if "--- thread" not in threadz:
            raise SystemExit("FAIL: bundle threadz.txt is not a dump")
        with open(os.path.join(bundle, "metrics.prom")) as f:
            prom = f.read()
        if "tffm_alert_active" not in prom:
            raise SystemExit(
                "FAIL: bundle metrics snapshot lacks the per-rule "
                "tffm_alert_active gauge"
            )
        # Live /metrics must carry the armed-rule gauge too.
        live = urllib.request.urlopen(
            f"{base}/metrics", timeout=10).read().decode()
        if 'tffm_alert_active{rule="' not in live:
            raise SystemExit(
                "FAIL: live /metrics lacks tffm_alert_active{rule=...}"
            )
        # (b) the manual POST /incident route.
        resp = urllib.request.urlopen(urllib.request.Request(
            f"{base}/incident?reason=smoke", data=b"", method="POST"
        ), timeout=30)
        doc = json.loads(resp.read())
        manual = doc.get("incident_dir")
        if not manual or not os.path.exists(
            os.path.join(manual, "manifest.json")
        ):
            raise SystemExit(
                f"FAIL: POST /incident answered no valid bundle: {doc}"
            )
        if "smoke" not in os.path.basename(manual):
            raise SystemExit(
                f"FAIL: manual bundle ignores ?reason=smoke: {manual}"
            )
        # (c) report.py renders the alert bundle.
        rep = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "report.py"),
             "--incident", bundle],
            cwd=REPO, capture_output=True, timeout=60,
        )
        rep_out = rep.stdout.decode(errors="replace")
        if rep.returncode != 0 or "incident:" not in rep_out \
                or "uptime_s" not in rep_out:
            sys.stderr.write(rep_out[-2000:])
            raise SystemExit(
                f"FAIL: report.py --incident exited {rep.returncode} "
                f"or named no rule"
            )
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    # (d) capture -> replay, bitwise, against a fresh server on the
    # same checkpoint (capture off — the replay target must not
    # append to the file it is being judged against).
    if not os.path.exists(capture_file):
        raise SystemExit(f"FAIL: no capture file at {capture_file}")
    r_port = _free_port()
    r_proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "serve",
         cfg_path, "--serve_port", str(r_port),
         "--serve_poll_secs", "0"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        _wait_healthz(f"http://127.0.0.1:{r_port}", r_proc,
                      "replay-target serve")
        rep = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "replay.py"),
             capture_file, "--endpoint",
             f"http://127.0.0.1:{r_port}"],
            cwd=REPO, capture_output=True, timeout=120,
        )
        rep_out = rep.stdout.decode(errors="replace")
        if rep.returncode != 0:
            sys.stderr.write(rep_out[-2000:])
            sys.stderr.write(rep.stderr.decode(errors="replace")[-500:])
            raise SystemExit(
                f"FAIL: tools/replay.py exited {rep.returncode} — "
                f"captured traffic did not re-score bitwise"
            )
        n_match = rep_out.split("/")[0].rsplit(" ", 1)[-1]
        print(
            f"incident smoke ok: alert bundle {os.path.basename(bundle)}"
            f" valid, POST /incident dumped "
            f"{os.path.basename(manual)}, report.py rendered it, "
            f"replay re-scored {n_match} captured request(s) bitwise"
        )
    finally:
        if r_proc.poll() is None:
            r_proc.terminate()
            try:
                r_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                r_proc.kill()
                r_proc.wait()


def check_router(cfg_path: str, data: str) -> None:
    """Router smoke: 2 replicas behind the P2C router, text/binary
    parity over the socket, fleet-aggregated /metrics, a SIGKILL
    mid-trace with transparent retry + respawn, teardown with no
    orphaned replica processes, and a complete merged request trace."""
    import signal
    import struct

    port = _free_port()
    tmpdir = os.path.dirname(cfg_path)
    trace_path = os.path.join(tmpdir, "serve_trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "serve",
         cfg_path, "--replicas", "2", "--serve_port", str(port),
         "--serve_poll_secs", "0.2",
         "--trace", trace_path, "--serve_trace_sample", "1.0"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    pids = []
    try:
        base = f"http://127.0.0.1:{port}"
        deadline = time.time() + 240
        while True:
            try:
                urllib.request.urlopen(f"{base}/healthz", timeout=2)
                break
            except (urllib.error.URLError, OSError) as e:
                if proc.poll() is not None:
                    out, _ = proc.communicate()
                    sys.stderr.write(out.decode(errors="replace")[-2000:])
                    raise SystemExit(
                        f"FAIL: router exited {proc.returncode} before "
                        f"answering ({e})"
                    )
                if time.time() > deadline:
                    raise SystemExit(
                        f"FAIL: router endpoint unreachable ({e})"
                    )
                time.sleep(0.3)
        status = json.loads(urllib.request.urlopen(
            f"{base}/status", timeout=10).read())
        per = status["serve"]["per_replica"]
        if len(per) != 2 or any(p["pid"] is None for p in per):
            raise SystemExit(
                f"FAIL: /status per_replica malformed: {per}"
            )
        pids = [p["pid"] for p in per]
        # Text/binary parity through the router, on a hand-rolled
        # frame so the DOCUMENTED wire layout is what's pinned (not
        # the package's own encoder): 2 examples x 3 features.
        examples = [[(5, 0.5), (9, 0.25), (3, 1.0)],
                    [(7, 0.125), (2, 0.75), (11, 1.0)]]
        text = "".join(
            "1 " + " ".join(f"{i}:{v}" for i, v in ex) + "\n"
            for ex in examples
        ).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            f"{base}/score", data=text, method="POST",
        ), timeout=30)
        text_scores = resp.read().decode().split()
        if not resp.headers.get("X-Request-Id"):
            raise SystemExit(
                "FAIL: sampled /score response carries no "
                "X-Request-Id echo"
            )
        frame = struct.pack("<4sIIB", b"TFB1", 2, 3, 0)
        frame += b"".join(
            struct.pack("<i", i) for ex in examples for i, _ in ex
        )
        frame += b"".join(
            struct.pack("<f", v) for ex in examples for _, v in ex
        )
        raw = urllib.request.urlopen(urllib.request.Request(
            f"{base}/score_bin", data=frame, method="POST",
        ), timeout=30).read()
        magic, n = struct.unpack_from("<4sI", raw)
        if magic != b"TFB1" or n != 2:
            raise SystemExit(
                f"FAIL: /score_bin response frame malformed "
                f"({magic!r}, n={n})"
            )
        bin_scores = [
            f"{s:.6f}" for s in struct.unpack_from("<2f", raw, 8)
        ]
        if bin_scores != text_scores:
            raise SystemExit(
                f"FAIL: binary scores {bin_scores} != text scores "
                f"{text_scores} for the same examples"
            )
        # Fleet metrics aggregation: the health loop scrapes every
        # replica's /status, and ONE router scrape must expose the
        # aggregated tffm_serve_fleet_* series plus per-replica
        # labeled series.
        deadline = time.time() + 60
        while True:
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            if (
                "tffm_serve_fleet_requests" in metrics
                and 'tffm_serve_replica_qps{replica="0"}' in metrics
                and 'tffm_serve_replica_qps{replica="1"}' in metrics
            ):
                break
            if time.time() > deadline:
                raise SystemExit(
                    "FAIL: router /metrics never exposed the fleet "
                    "aggregates / per-replica scraped series"
                )
            time.sleep(0.3)
        check_prometheus(metrics)
        # Fleet-wide skew visibility: the scrape max-merges each
        # replica's skew_* keys under the same names, so the ROUTER's
        # /metrics carries tffm_serve_skew_examples (and the psi
        # series once enough traffic flows) — one scrape sees
        # fleet-wide training→serving skew.
        if "tffm_serve_skew_examples" not in metrics:
            raise SystemExit(
                "FAIL: router /metrics carries no fleet-merged "
                "tffm_serve_skew_* series"
            )
        # Kill one replica mid-traffic: every request must keep
        # succeeding (the router retries in-flight requests on the
        # survivor) and the eviction must show on /metrics.
        os.kill(pids[0], signal.SIGKILL)
        for i in range(20):
            body = urllib.request.urlopen(urllib.request.Request(
                f"{base}/score", data=text, method="POST",
            ), timeout=30).read().decode()
            if len(body.split()) != 2:
                raise SystemExit(
                    f"FAIL: request {i} after the SIGKILL answered "
                    f"{body[:100]!r}"
                )
        deadline = time.time() + 30
        while True:
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            m = re.search(
                r"^tffm_counter_serve_evictions_total (\d+)", metrics,
                re.MULTILINE,
            )
            if m and int(m.group(1)) >= 1:
                break
            if time.time() > deadline:
                raise SystemExit(
                    "FAIL: router /metrics never showed the eviction"
                )
            time.sleep(0.3)
        check_prometheus(metrics)
        if not re.search(
            r'^tffm_serve_replica_healthy\{replica="0"[^}]*\} 0',
            metrics, re.MULTILINE,
        ):
            raise SystemExit(
                "FAIL: killed replica not marked unhealthy in the "
                "per-replica /metrics series"
            )
        # Respawn policy: the manager relaunches the killed MANAGED
        # replica (capped backoff) and the health loop readmits it
        # once its ladder is warm — the deadline is generous because
        # the fresh process pays a full jax startup + warmup on a
        # box already running two replicas.
        deadline = time.time() + 300
        while True:
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            m = re.search(
                r"^tffm_counter_serve_respawns_total (\d+)", metrics,
                re.MULTILINE,
            )
            respawns = int(m.group(1)) if m else 0
            healthy0 = re.search(
                r'^tffm_serve_replica_healthy\{replica="0"[^}]*\} 1',
                metrics, re.MULTILINE,
            )
            if respawns >= 1 and healthy0:
                break
            if time.time() > deadline:
                raise SystemExit(
                    f"FAIL: killed replica never respawned+readmitted "
                    f"(respawns={respawns}, healthy0={bool(healthy0)})"
                )
            time.sleep(1.0)
        # The respawned replica is a NEW pid: the teardown check below
        # must track the live fleet, not the original pids.
        status = json.loads(urllib.request.urlopen(
            f"{base}/status", timeout=10).read())
        pids = [p["pid"] for p in status["serve"]["per_replica"]
                if p["pid"] is not None]
        # Scoring still flows through the recovered fleet.
        body = urllib.request.urlopen(urllib.request.Request(
            f"{base}/score", data=text, method="POST",
        ), timeout=30).read().decode()
        if len(body.split()) != 2:
            raise SystemExit("FAIL: scoring broken after the respawn")
        print(
            f"router smoke ok: 2 replicas, text==binary scores, "
            f"fleet aggregates on /metrics, 20/20 requests after "
            f"SIGKILL, eviction visible, {respawns} respawn(s) + "
            f"readmission"
        )
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    # The manager's teardown contract: no replica outlives its router
    # — including the RESPAWNED one (pids was refreshed post-respawn).
    deadline = time.time() + 10
    for pid in pids:
        while time.time() < deadline:
            try:
                os.kill(pid, 0)
                time.sleep(0.2)
            except ProcessLookupError:
                break
        else:
            os.kill(pid, signal.SIGKILL)
            raise SystemExit(
                f"FAIL: replica pid {pid} outlived the router "
                "(manager teardown leak)"
            )
    print("router teardown ok: no orphaned replica processes")
    # Distributed-trace merge: the router trace + whatever replica
    # traces survived (the SIGKILLed replica's die with it — that is
    # the point of the mid-trace kill) must re-join into COMPLETE
    # per-request chains under tools/report.py --serve-trace.
    trace_files = [
        p for p in (
            trace_path,
            trace_path + ".replica0",
            trace_path + ".replica1",
        ) if os.path.exists(p)
    ]
    if trace_path not in trace_files or len(trace_files) < 2:
        raise SystemExit(
            f"FAIL: trace family incomplete on disk: {trace_files} "
            "(need the router trace + >= 1 replica trace)"
        )
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "report.py"),
         "--serve-trace"] + trace_files,
        capture_output=True, timeout=120,
    )
    out = rep.stdout.decode(errors="replace")
    if rep.returncode != 0:
        sys.stderr.write(out[-2000:])
        raise SystemExit(
            f"FAIL: report.py --serve-trace exited {rep.returncode}"
        )
    m = re.search(
        r"sampled requests: (\d+) traced, (\d+) with a complete chain",
        out,
    )
    if not m or int(m.group(2)) < 1:
        sys.stderr.write(out[-2000:])
        raise SystemExit(
            "FAIL: merged serve trace reconstructed no complete "
            "request chain"
        )
    print(
        f"serve-trace merge ok: {m.group(1)} request(s) traced, "
        f"{m.group(2)} complete chain(s) across "
        f"{len(trace_files)} file(s)"
    )


# Two-rank fleet-training worker (ISSUE 18): the ranks join a
# loopback jax.distributed cluster for IDENTITY (process_index,
# rank-suffixed streams) but each trains on its own LOCAL 2x1 mesh —
# lock-step SPMD would synchronize every dispatch through the
# all-reduce and smear the injected straggler's latency across BOTH
# ranks' dispatch timers (ratio ~= 1.0 however slow the straggler),
# which is exactly the single-host drive mode the explicit
# train_fleet_scrape target list exists for.  Rank 1 sleeps 80 ms per
# dispatch (the injected straggler); rank 0 runs the TrainFleet
# aggregator over both ranks with a live straggler_ratio rule.
_FLEET_WORKER = r"""
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
# CPU cross-process collectives need the gloo transport; without it
# any multi-process computation fails with "Multiprocess computations
# aren't implemented on the CPU backend".  Training here is local per
# rank, but checkpoint-save barriers still cross processes.
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
tmpdir, port0, port1 = sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
rank = jax.process_index()

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train.loop import Trainer

cfg = FmConfig(
    vocabulary_size=64, factor_num=4, max_features=4, batch_size=64,
    mesh_data=2, mesh_model=1,
    train_files=[tmpdir + "/fleet.libsvm"],
    model_file=tmpdir + "/fleet_model%d" % rank,
    epoch_num=24, log_steps=0, thread_num=1, seed=5,
    heartbeat_secs=0.2,
    metrics_file=tmpdir + "/fleet_metrics.jsonl",
    status_port=port0 if rank == 0 else port1,
    train_fleet_scrape="127.0.0.1:%d,127.0.0.1:%d" % (port0, port1),
    alert_rules="straggler_ratio > 1.4 for 2 : warn",
)
trainer = Trainer(
    cfg, mesh=mesh_lib.make_mesh(cfg, jax.local_devices())
)
# Orbax refuses host-local arrays when process_count > 1, and this
# smoke exercises the fleet plane, not checkpointing.
trainer.save = lambda stepno: None
if rank == 1:
    real = trainer._scan_train_step
    def slow(state, batches):
        time.sleep(0.08)
        return real(state, batches)
    trainer._scan_train_step = slow
trainer.train()
print("FLEET_RANK_DONE", rank)
"""


def check_fleet(tmpdir: str) -> None:
    """2-rank fleet-training smoke: rank 0 aggregates the fleet LIVE
    (per-rank ``tffm_train_rank_*`` series on its /metrics, merged
    ``fleet`` block on /status), the injected 60 ms straggler on rank 1
    trips the ``straggler_ratio`` alert while training runs, and the
    per-rank JSONL writers never double-count into one stream."""
    import numpy as np

    rng = np.random.default_rng(11)
    data = os.path.join(tmpdir, "fleet.libsvm")
    with open(data, "w") as f:
        for _ in range(512):
            toks = [str(rng.integers(0, 2))]
            toks += [f"{rng.integers(0, 64)}:{rng.uniform(0.1, 1):.4f}"
                     for _ in range(3)]
            f.write(" ".join(toks) + "\n")
    coord_port, port0, port1 = _free_port(), _free_port(), _free_port()
    script = os.path.join(tmpdir, "fleet_worker.py")
    with open(script, "w") as f:
        f.write(_FLEET_WORKER)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=REPO + os.pathsep + os.environ.get(
            "PYTHONPATH", ""
        ),
    )
    procs = [
        subprocess.Popen(
            [sys.executable, script,
             f"127.0.0.1:{coord_port}", str(i), tmpdir,
             str(port0), str(port1)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for i in range(2)
    ]
    try:
        # Live assertion window: rank 0's /metrics must grow BOTH
        # ranks' labeled series plus the merged fleet aggregates while
        # the ranks are still training.
        deadline = time.time() + 240
        fleet_metrics = None
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break  # a fast box may finish before we catch it live
            try:
                text = urllib.request.urlopen(
                    f"http://127.0.0.1:{port0}/metrics", timeout=2
                ).read().decode()
            except (urllib.error.URLError, OSError):
                time.sleep(0.2)
                continue
            if ('tffm_train_rank_dispatch_mean_ms{rank="1"}' in text
                    and "tffm_fleet_straggler_ratio" in text):
                fleet_metrics = text
                break
            time.sleep(0.2)
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode(errors="replace"))
            if p.returncode != 0:
                sys.stderr.write(outs[-1][-3000:])
                raise SystemExit(
                    f"FAIL: fleet worker exited {p.returncode}"
                )
        if fleet_metrics is None:
            raise SystemExit(
                "FAIL: rank 0 /metrics never served the per-rank "
                "fleet series mid-run"
            )
        check_prometheus(fleet_metrics)
        for series in ('tffm_train_rank_step{rank="0"}',
                       'tffm_train_rank_step{rank="1"}',
                       "tffm_fleet_ranks_scraped 2",
                       "tffm_fleet_straggler_ratio"):
            if series not in fleet_metrics:
                raise SystemExit(
                    f"FAIL: fleet /metrics missing {series!r}"
                )
        # Rank files: rank 0 owns metrics.jsonl, rank 1 the .rank1
        # suffix — merged streams must never double-count.
        rank0_path = os.path.join(tmpdir, "fleet_metrics.jsonl")
        rank1_path = rank0_path + ".rank1"
        for path in (rank0_path, rank1_path):
            if not os.path.exists(path):
                raise SystemExit(f"FAIL: missing rank stream {path}")
        recs0 = [json.loads(line) for line in open(rank0_path)]
        ranks0 = {r.get("rank") for r in recs0 if "rank" in r}
        if ranks0 - {0}:
            raise SystemExit(
                f"FAIL: rank-0 stream carries foreign ranks {ranks0}"
            )
        recs1 = [json.loads(line) for line in open(rank1_path)]
        if not any(r.get("rank") == 1 for r in recs1):
            raise SystemExit(
                "FAIL: rank-1 stream has no rank-1 records"
            )
        # The LIVE alert: the injected straggler must have fired the
        # straggler_ratio rule into rank 0's stream during the run.
        alerts = [r for r in recs0 if r.get("record") == "alert"]
        stragglers = [
            a for a in alerts if a.get("signal") == "straggler_ratio"
        ]
        if not stragglers:
            raise SystemExit(
                f"FAIL: no straggler_ratio alert fired "
                f"(alerts: {alerts})"
            )
        if stragglers[0]["value"] <= 1.4:
            raise SystemExit(
                f"FAIL: straggler alert fired below threshold: "
                f"{stragglers[0]}"
            )
        # The final record carries the merged fleet view.
        final = [r for r in recs0 if r.get("record") == "final"][-1]
        fl = final.get("fleet") or {}
        if fl.get("ranks_scraped") != 2:
            raise SystemExit(
                f"FAIL: final fleet block incomplete: {fl}"
            )
        if fl.get("slowest_rank") != 1:
            raise SystemExit(
                f"FAIL: straggler attribution blamed rank "
                f"{fl.get('slowest_rank')}, expected 1: {fl}"
            )
        print(
            f"fleet smoke ok: 2 ranks aggregated live, "
            f"straggler_ratio={stragglers[0]['value']} alert fired "
            f"(slowest_rank={fl['slowest_rank']}), "
            f"{len(recs1)} rank-1 records in .rank1"
        )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()


def main() -> int:
    port = _free_port()
    tmpdir = tempfile.mkdtemp(prefix="tffm_obs_smoke_")
    try:
        return _run(port, tmpdir)
    finally:
        # verify.sh runs this on every invocation; leaked data/model
        # dirs would accumulate on CI boxes.
        shutil.rmtree(tmpdir, ignore_errors=True)


def _run(port: int, tmpdir: str) -> int:
    data = os.path.join(tmpdir, "train.libsvm")
    # 6400 lines x 20 epochs / batch 32 = 4000 steps (~20 s on a CPU
    # box): long enough that the /profile capture — jax's one-time
    # ~5 s profiler init plus the 0.5 s window — finishes well before
    # the run does.  A 20-step run used to end UNDER the open capture
    # and reset the connection.
    _gen_data(data)
    cfg_path = os.path.join(tmpdir, "smoke.cfg")
    with open(cfg_path, "w") as f:
        f.write(f"""[General]
vocabulary_size = 50
factor_num = 4
model_file = {tmpdir}/model
[Train]
train_files = {data}
epoch_num = 20
batch_size = 32
log_steps = 0
thread_num = 2
heartbeat_secs = 0.2
metrics_file = {tmpdir}/metrics.jsonl
[Tpu]
max_features = 4
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "train",
         cfg_path, "--status_port", str(port)],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.time() + 180
        status_raw, metrics_raw = _scrape_both(port, deadline, proc)
        # Capture routes first: the scrape above succeeded inside the
        # startup/compile window, so the 2 s profile capture cannot
        # outlive the run.
        check_capture_routes(port)
        status = json.loads(status_raw)
        for key in ("record", "step", "stages", "resource"):
            if key not in status:
                raise SystemExit(
                    f"FAIL: /status record missing {key!r}: {status}"
                )
        if status["record"] != "status":
            raise SystemExit(
                f"FAIL: /status record type {status['record']!r}"
            )
        if "rss_mb" not in status["resource"]:
            raise SystemExit(
                f"FAIL: resource block has no rss_mb: "
                f"{status['resource']}"
            )
        metrics = metrics_raw.decode()
        n = check_prometheus(metrics)
        for series in ("tffm_step", "tffm_counter_ingest_examples_total",
                       "tffm_timer_train_dispatch_count",
                       "tffm_resource_rss_mb", "tffm_build_info"):
            if series not in metrics:
                raise SystemExit(
                    f"FAIL: /metrics missing core series {series}"
                )
        out, _ = proc.communicate(timeout=180)
        if proc.returncode != 0:
            sys.stderr.write(out.decode(errors="replace")[-2000:])
            raise SystemExit(
                f"FAIL: training run exited {proc.returncode}"
            )
        # Model-quality plane: the final record must carry the quality
        # block (windowed eval + sketch counts) — default-on, like the
        # resource block above.
        finals = [
            json.loads(line)
            for line in open(os.path.join(tmpdir, "metrics.jsonl"))
        ]
        final = [r for r in finals if r.get("record") == "final"][-1]
        q = final.get("quality") or {}
        if not q.get("examples") or not q.get("sketch_examples"):
            raise SystemExit(
                f"FAIL: final record's quality block is missing or "
                f"empty: {q}"
            )
        # The /profile capture above must have logged itself into the
        # stream (`record: profile`) — a profiler window perturbs step
        # time, and the stream has to say so.
        profiles = [r for r in finals if r.get("record") == "profile"]
        if not profiles or not profiles[-1].get("profile_dir"):
            raise SystemExit(
                f"FAIL: /profile capture wrote no `record: profile` "
                f"entry to the metrics stream ({len(profiles)} found)"
            )
        # Resource vitals (ISSUE 20): uptime + the open-fd ledger must
        # ride the resource block.
        res = final.get("resource") or {}
        if res.get("uptime_s", 0) <= 0 or "open_fds" not in res:
            raise SystemExit(
                f"FAIL: resource block lacks uptime_s/open_fds: {res}"
            )
        print(
            f"obs smoke ok: /status step={status['step']}, /metrics "
            f"served {n} Prometheus samples, quality block eval'd "
            f"{q['examples']} examples, run exited 0"
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # The serve smoke scores against the checkpoint the run above just
    # saved (run_tffm.py serve in its own subprocess), then the router
    # smoke mounts a 2-replica fleet over the same checkpoint.
    check_serve(cfg_path, data)
    # Incident flight recorder + capture/replay (ISSUE 20): an
    # injected alert breach must dump a valid forensic bundle,
    # report.py must render it, and the captured traffic must replay
    # bitwise against a fresh server on the same checkpoint.
    check_incident(cfg_path, data)
    check_router(cfg_path, data)
    # Fleet-training smoke (ISSUE 18): 2 spawned CPU ranks, rank 0
    # aggregating, an injected straggler tripping the live alert.
    check_fleet(tmpdir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
