"""End-to-end batch tracing + training-health monitors (ISSUE 5).

Pins the tentpole guarantees:

  * the ``trace_file`` output is valid Chrome-trace (Perfetto-loadable)
    JSON, with spans from EVERY execution context of a
    ``parse_processes`` run — reader, SHM ring slot acquire, spawned
    parse workers (their spans ship back over the result messages),
    delivery, prefetcher stack/H2D, and the train loop's wait/dispatch;
  * super-batch ids correlate across the process boundary: every
    dispatched super-batch reconstructs a CONNECTED chain
    read -> ring slot -> parse -> deliver -> stack -> H2D -> dispatch
    (tools/report.py --trace is the reference chain-walker, and its
    merge output stays loadable);
  * ``trace_file`` unset = shared no-op tracer = bit-identical training;
  * the scan-carry health monitors detect an injected NaN under both
    ``nan_policy`` modes — ``halt`` raises within one dispatch of the
    poisoned one, ``warn`` finishes and reports the damage in the final
    record;
  * a crashed run's metrics stream still ends with a ``final`` record
    (exception type + partial counters) — the try/finally contract
    tools/report.py relies on;
  * tools/check_tier1.py (the marker audit behind lint rule T1001) and
    tools/report.py --compare behave.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.train.loop import NonFiniteGradError, Trainer

_TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
)
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import check_tier1  # noqa: E402
import report  # noqa: E402


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_records_complete_event_with_args(self):
        tr = obs.Tracer(enabled=True)
        with tr.span("work", args={"seq": 7}):
            pass
        evs = [e for e in tr.take() if e.get("ph") == "X"]
        assert len(evs) == 1
        ev = evs[0]
        assert ev["name"] == "work" and ev["args"] == {"seq": 7}
        for key in ("ts", "dur", "pid", "tid"):
            assert key in ev
        assert ev["dur"] >= 1  # zero-length spans stay visible

    def test_flow_events_bind_to_span(self):
        tr = obs.Tracer(enabled=True)
        with tr.span("stack", flow=("s", "sb3")):
            pass
        with tr.span("dispatch", flow=("f", "sb3")):
            pass
        evs = tr.take()
        flows = [e for e in evs if e.get("cat") == "tffm_flow"]
        assert [f["ph"] for f in flows] == ["s", "f"]
        assert all(f["id"] == "sb3" for f in flows)
        assert flows[1]["bp"] == "e"  # flow end binds to enclosing slice

    def test_disabled_tracer_is_noop(self):
        tr = obs.Tracer(enabled=False)
        with tr.span("x", args={"a": 1}):
            pass
        tr.point("y")
        tr.emit("z", 0.0, 1.0)
        tr.add_raw([{"ph": "X"}])
        assert tr.take() == []
        assert obs.NULL_TRACER.take() == []

    def test_add_raw_merges_shipped_events(self):
        worker = obs.Tracer(enabled=True, process_name="w")
        with worker.span("parse.batch", args={"seq": 1}):
            pass
        shipped = worker.take()
        parent = obs.Tracer(enabled=True)
        parent.add_raw(shipped)
        names = {e.get("name") for e in parent.take()}
        assert "parse.batch" in names and "process_name" in names

    def test_event_cap_drops_and_counts(self, tmp_path):
        tr = obs.Tracer(enabled=True, max_events=3)
        for i in range(10):
            tr.point(f"e{i}")
        path = str(tmp_path / "t.json")
        assert tr.dump(path) == 3
        doc = json.load(open(path))
        assert doc["otherData"]["dropped_events"] == 7

    def test_cap_overflow_warns_at_dump_and_exposes_count(
        self, tmp_path, caplog
    ):
        """Silent truncation is a lie by omission: past the cap, dump()
        must WARN and the dropped count must be queryable."""
        tr = obs.Tracer(enabled=True, max_events=3)
        for i in range(10):
            tr.point(f"e{i}")
        assert tr.dropped_events == 7
        with caplog.at_level("WARNING", logger="fast_tffm_tpu.obs.trace"):
            tr.dump(str(tmp_path / "t.json"))
        assert any("TRUNCATED" in r.message for r in caplog.records)
        # A clean dump stays quiet.
        caplog.clear()
        tr2 = obs.Tracer(enabled=True)
        tr2.point("a")
        with caplog.at_level("WARNING", logger="fast_tffm_tpu.obs.trace"):
            tr2.dump(str(tmp_path / "t2.json"))
        assert not caplog.records
        assert tr2.dropped_events == 0

    def test_reset_preserves_process_name(self):
        tr = obs.Tracer(enabled=True, process_name="trainer")
        tr.point("a")
        tr.reset()
        evs = tr.take()
        assert [e["name"] for e in evs] == ["process_name"]


# ---------------------------------------------------------------------------
# Traced training runs
# ---------------------------------------------------------------------------


def _write_libsvm(path, n_lines, vocab=50, n_feat=3, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n_lines):
            feats = rng.choice(vocab, size=n_feat, replace=False)
            toks = " ".join(f"{i}:{rng.uniform(0.1, 1):.3f}" for i in feats)
            f.write(f"{rng.integers(0, 2)} {toks}\n")
    return str(path)


def _cfg(data, tmp_path, tag, **kw):
    defaults = dict(
        vocabulary_size=50,
        factor_num=4,
        model_file=str(tmp_path / f"model_{tag}"),
        train_files=[data],
        epoch_num=1,
        batch_size=32,
        max_features=4,
        log_steps=0,
        thread_num=2,
        steps_per_dispatch=4,
        seed=3,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


@pytest.fixture(scope="module")
def train_file(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace_data")
    return _write_libsvm(out / "train.libsvm", 640)


@pytest.fixture(scope="module")
def traced_procs_run(train_file, tmp_path_factory):
    """ONE traced run shared by the trace-content tests: the acceptance
    configuration — parse_processes=2, steps_per_dispatch=4."""
    tmp = tmp_path_factory.mktemp("traced_run")
    trace = str(tmp / "trace.json")
    metrics = str(tmp / "metrics.jsonl")
    cfg = _cfg(
        train_file, tmp, "procs", parse_processes=2,
        trace_file=trace, metrics_file=metrics,
    )
    result = Trainer(cfg).train()
    return {"trace": trace, "metrics": metrics, "result": result,
            "tmp": tmp}


def _events(path):
    doc = json.load(open(path))
    assert isinstance(doc, dict) and "traceEvents" in doc
    return doc["traceEvents"]


class TestTraceContent:
    def test_trace_is_valid_chrome_trace_json(self, traced_procs_run):
        doc = json.load(open(traced_procs_run["trace"]))
        # Perfetto object format: traceEvents + clock anchors for the
        # multi-rank merge.
        assert isinstance(doc["traceEvents"], list)
        assert doc["traceEvents"], "empty trace"
        for key in ("wall_anchor", "perf_anchor"):
            assert key in doc["otherData"], key
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "M", "s", "t", "f"), ev
            assert "pid" in ev and "tid" in ev
            if ev["ph"] == "X":
                assert "ts" in ev and "dur" in ev and "name" in ev

    def test_spans_cover_every_stage(self, traced_procs_run):
        names = {e.get("name") for e in _events(traced_procs_run["trace"])}
        for stage in (
            "read.item",          # reader window production
            "ring.slot_acquire",  # SHM ring slot wait (reader side)
            "parse.window",       # worker-side window span (slot release)
            "parse.batch",        # worker-side per-batch parse
            "ingest.deliver",     # delivery bridge (seq -> batch idx)
            "prefetch.stack",     # transfer-stage stacking
            "prefetch.h2d",       # device put
            "train.wait_input",   # starvation side of the loop
            "train.dispatch",     # fused-scan dispatch
        ):
            assert stage in names, f"missing stage span {stage}"

    def test_worker_spans_carry_worker_pids(self, traced_procs_run):
        evs = _events(traced_procs_run["trace"])
        parent_pids = {
            e["pid"] for e in evs if e.get("name") == "train.dispatch"
        }
        parse_pids = {
            e["pid"] for e in evs if e.get("name") == "parse.batch"
        }
        assert parse_pids, "no parse spans"
        # parse spans were recorded in spawned workers and shipped back:
        # they carry the WORKER pids, not the trainer's.
        assert parse_pids.isdisjoint(parent_pids)

    def test_every_dispatch_has_connected_chain(self, traced_procs_run):
        """The acceptance criterion: every dispatched super-batch's life
        reconstructs as one connected chain across the process
        boundary (sb -> batch range -> seq -> worker parse spans)."""
        chains = report.trace_chains(_events(traced_procs_run["trace"]))
        assert chains, "no dispatched super-batches in trace"
        # 640 lines / 32 = 20 batches at K=4 -> 5 dispatches.
        assert len(chains) == 5
        for c in chains:
            assert c["complete"], f"disconnected chain for sb {c['sb']}"
            # Chain links really cross the process boundary: the parse
            # span of every batch came from a worker pid.
            disp_pid = c["dispatch"]["pid"]
            for b in c["batches"]:
                assert b["parse"]["pid"] != disp_pid

    def test_report_trace_merges_to_loadable_file(self, traced_procs_run,
                                                  capsys):
        merged = str(traced_procs_run["tmp"] / "merged.json")
        rc = report.main(
            ["--trace", traced_procs_run["trace"], "-o", merged]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "critical path" in out
        assert "5 with a complete" in out
        doc = json.load(open(merged))
        # Normalized timeline starts at zero and chains still connect.
        tss = [e["ts"] for e in doc["traceEvents"] if "ts" in e]
        assert min(tss) == 0
        chains = report.trace_chains(doc["traceEvents"])
        assert all(c["complete"] for c in chains)

    def test_prestacked_replay_chains_complete(self, train_file,
                                               tmp_path):
        """cache_prestacked replay epochs deliver whole SuperBatches —
        ONE ingest.deliver point covering n batches.  Chain completeness
        must treat that range as delivered (a healthy prestacked trace
        used to report every replay chain incomplete)."""
        trace = str(tmp_path / "prestack_trace.json")
        cfg = _cfg(
            train_file, tmp_path, "prestack", epoch_num=2,
            cache_epochs=True, cache_prestacked=True, trace_file=trace,
        )
        Trainer(cfg).train()
        chains = report.trace_chains(_events(trace))
        # 20 batches/epoch at K=4 -> 5 dispatches x 2 epochs.
        assert len(chains) == 10
        assert all(c["complete"] for c in chains), [
            c["sb"] for c in chains if not c["complete"]
        ]
        # Every dispatch took the prestacked path (epoch 0 stacks ONCE
        # in the pipeline; replays reuse): h2d spans carry the batch
        # range + prestacked flag, no transfer-stage stack span.
        assert all(c["stack"] is None for c in chains)
        assert all(
            (c["h2d"]["args"] or {}).get("prestacked") for c in chains
        )

    def test_multi_rank_merge_builds_per_rank_chains(
        self, traced_procs_run, tmp_path, capsys
    ):
        """Fleet merge: sb/seq ids restart per rank, so chains must be
        reconstructed per input file — two rank files with identical id
        spaces merge without cross-wiring (or crashing on duplicate
        ring seqs) and yield 2x the chains."""
        import shutil

        r0 = str(tmp_path / "t.rank0.json")
        r1 = str(tmp_path / "t.rank1.json")
        shutil.copy(traced_procs_run["trace"], r0)
        shutil.copy(traced_procs_run["trace"], r1)
        merged = str(tmp_path / "fleet.json")
        rc = report.main(["--trace", r0, r1, "-o", merged])
        assert rc == 0
        out = capsys.readouterr().out
        assert "10 dispatched, 10 with a complete" in out

    def test_health_in_final_record_and_results(self, traced_procs_run):
        recs = [json.loads(l) for l in open(traced_procs_run["metrics"])]
        final = [r for r in recs if r.get("record") == "final"][-1]
        health = final["health"]
        for key in ("grad_norm", "grad_norm_rms", "nonfinite_steps",
                    "first_nonfinite_step", "emb_rows_touched",
                    "emb_row_occupancy", "emb_touch_events"):
            assert key in health, key
        assert health["nonfinite_steps"] == 0
        assert health["first_nonfinite_step"] == -1
        assert 0 < health["emb_rows_touched"] <= 50
        # 640 lines x 3 real features each.
        assert health["emb_touch_events"] == 1920.0
        rh = traced_procs_run["result"]["train"]["health"]
        assert rh["nonfinite_steps"] == 0
        assert rh["emb_rows_touched"] == health["emb_rows_touched"]

    def test_final_record_surfaces_trace_dropped_events(
        self, traced_procs_run, train_file, tmp_path
    ):
        """The final metrics record carries ``trace_dropped_events`` on
        traced runs — 0 for a healthy run, the true drop count for a
        run that overflowed the event cap."""
        recs = [json.loads(l) for l in open(traced_procs_run["metrics"])]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert final["trace_dropped_events"] == 0
        # Overflowed run: shrink the live tracer's cap before training.
        metrics = str(tmp_path / "m.jsonl")
        cfg = _cfg(train_file, tmp_path, "capped",
                   trace_file=str(tmp_path / "t.json"),
                   metrics_file=metrics)
        trainer = Trainer(cfg)
        trainer.tracer._max = 5
        trainer.train()
        recs = [json.loads(l) for l in open(metrics)]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert final["trace_dropped_events"] > 0
        # An untraced run's final record carries no trace field at all.
        cfg2 = _cfg(train_file, tmp_path, "untraced",
                    metrics_file=str(tmp_path / "m2.jsonl"))
        Trainer(cfg2).train()
        recs = [json.loads(l) for l in open(str(tmp_path / "m2.jsonl"))]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert "trace_dropped_events" not in final


class TestTraceOff:
    def test_trace_off_is_bit_identical_training(self, train_file,
                                                 tmp_path):
        """trace_file unset must not perturb a single bit of training:
        the tracer is the shared no-op and no span code runs."""
        import jax

        states = {}
        for tag in ("on", "off"):
            cfg = _cfg(
                train_file, tmp_path, f"bit_{tag}",
                trace_file=(
                    str(tmp_path / "t.json") if tag == "on" else ""
                ),
            )
            t = Trainer(cfg)
            t.train()
            states[tag] = t.state
        eq = jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a),
                                             np.asarray(b))),
            states["on"], states["off"],
        )
        assert all(jax.tree.leaves(eq))


# ---------------------------------------------------------------------------
# Windowed trace rotation (ISSUE 7 tentpole, layer 3)
# ---------------------------------------------------------------------------


class TestTraceRotation:
    def test_rotates_at_watermark_with_zero_drops(self, tmp_path):
        base = str(tmp_path / "t.json")
        tr = obs.Tracer(enabled=True, rotate_events=5, rotate_path=base)
        for i in range(17):
            tr.point(f"e{i}")
        # 17 events at watermark 5 -> 3 full windows already on disk.
        assert tr.windows_written == 3
        last = tr.dump(base)  # final window: the 2-event remainder
        assert last == 2
        assert tr.windows_written == 4
        assert tr.dropped_events == 0
        total = 0
        for i in range(4):
            doc = json.load(open(str(tmp_path / f"t.{i}.json")))
            other = doc["otherData"]
            assert other["window"] == i
            assert other["dropped_events"] == 0
            for key in ("wall_anchor", "perf_anchor", "pid"):
                assert key in other
            total += len(doc["traceEvents"])
        assert total == 17  # every event landed in exactly one window

    def test_watermark_above_event_cap_still_rotates(self, tmp_path):
        """The in-memory drop cap must not apply under rotation: a
        watermark past the cap used to hit the cap's drop path first
        and silently never rotate — the exact truncation rotation
        exists to prevent."""
        base = str(tmp_path / "t.json")
        tr = obs.Tracer(
            enabled=True, max_events=10, rotate_events=20,
            rotate_path=base,
        )
        for i in range(50):
            tr.point(f"e{i}")
        tr.dump(base)
        assert tr.dropped_events == 0
        total = sum(
            len(json.load(open(str(p)))["traceEvents"])
            for p in tmp_path.glob("t.*.json")
        )
        assert total == 50

    def test_worker_shipment_crossing_watermark_never_truncates(
        self, tmp_path
    ):
        """add_raw ships worker span BATCHES; a batch landing near the
        watermark must rotate, not truncate (the cap's room check used
        to drop the batch's tail before the rotation check ran)."""
        base = str(tmp_path / "t.json")
        tr = obs.Tracer(
            enabled=True, max_events=20, rotate_events=20,
            rotate_path=base,
        )
        for i in range(15):
            tr.point(f"e{i}")
        worker = obs.Tracer(enabled=True)
        for i in range(30):
            worker.point(f"w{i}")
        tr.add_raw(worker.take())  # 15 + 30 crosses the watermark
        tr.dump(base)
        assert tr.dropped_events == 0
        total = sum(
            len(json.load(open(str(p)))["traceEvents"])
            for p in tmp_path.glob("t.*.json")
        )
        assert total == 45

    def test_window_naming(self, tmp_path):
        tr = obs.Tracer(
            enabled=True, rotate_events=5,
            rotate_path=str(tmp_path / "trace.json"),
        )
        assert tr.window_path(0).endswith("trace.0.json")
        tr2 = obs.Tracer(
            enabled=True, rotate_events=5,
            rotate_path=str(tmp_path / "trace.json.rank1"),
        )
        assert tr2.window_path(2).endswith("trace.json.rank1.2.json")

    def test_reset_restarts_window_numbering(self, tmp_path):
        base = str(tmp_path / "t.json")
        tr = obs.Tracer(enabled=True, rotate_events=3, rotate_path=base)
        for i in range(7):
            tr.point(f"e{i}")
        assert tr.windows_written == 2
        tr.reset()
        assert tr.windows_written == 0

    def test_traced_run_rotates_and_chains_remerge(self, train_file,
                                                   tmp_path, capsys):
        """The acceptance criterion: a run traced past the watermark
        yields rotated files that --trace merges back into COMPLETE
        chains with zero dropped events — including chains that span a
        rotation boundary."""
        trace = str(tmp_path / "rot.json")
        metrics = str(tmp_path / "rot_metrics.jsonl")
        cfg = _cfg(
            train_file, tmp_path, "rotate", trace_file=trace,
            trace_rotate_events=40, metrics_file=metrics,
        )
        Trainer(cfg).train()
        windows = sorted(
            str(p) for p in tmp_path.glob("rot.*.json")
        )
        assert len(windows) >= 3, windows  # genuinely rotated
        assert not (tmp_path / "rot.json").exists()  # windows only
        # Zero drops, surfaced in the final record (rotation is WHY).
        recs = [json.loads(l) for l in open(metrics)]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert final["trace_dropped_events"] == 0
        assert final["trace_windows"] == len(windows) - 1  # pre-final
        # Windows re-join into one stream; every chain reconnects.
        merged = str(tmp_path / "rot_merged.json")
        rc = report.main(["--trace"] + windows + ["-o", merged])
        assert rc == 0
        out = capsys.readouterr().out
        assert (
            f"re-joined {len(windows)} file(s) into 1 stream(s)" in out
        )
        # 640 lines / 32 = 20 batches at K=4 -> 5 dispatches.
        assert "5 dispatched, 5 with a complete" in out
        # And the merged artifact stays Perfetto-loadable.
        doc = json.load(open(merged))
        assert doc["traceEvents"]

    def test_rotation_bitwise_identical_to_unrotated(self, train_file,
                                                     tmp_path):
        """Rotation is a storage policy of the trace output: the
        recorded EVENTS (ignoring timestamps/ids) and the trained model
        must match an unrotated run exactly."""
        import jax

        states = {}
        for tag, rot in (("rot", 40), ("flat", 0)):
            cfg = _cfg(
                train_file, tmp_path, f"parity_{tag}",
                trace_file=str(tmp_path / f"parity_{tag}.json"),
                trace_rotate_events=rot,
            )
            t = Trainer(cfg)
            t.train()
            states[tag] = t.state
        eq = jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a),
                                             np.asarray(b))),
            states["rot"], states["flat"],
        )
        assert all(jax.tree.leaves(eq))
        flat_events = _events(str(tmp_path / "parity_flat.json"))
        rot_events = []
        for p in sorted(tmp_path.glob("parity_rot.*.json"),
                        key=lambda p: int(p.name.split(".")[1])):
            rot_events.extend(json.load(open(str(p)))["traceEvents"])

        def stage_counts(events):
            # Only the work-deterministic spans: thread-scheduling
            # artifacts (thread_name metadata, conditional
            # staging_wait spans) legitimately vary run to run.
            out: dict = {}
            for e in events:
                if e.get("ph") == "X" and e["name"] in (
                    "read.item", "parse.batch", "ingest.deliver",
                    "prefetch.stack", "prefetch.h2d", "train.dispatch",
                ):
                    out[e["name"]] = out.get(e["name"], 0) + 1
            return out

        assert stage_counts(rot_events) == stage_counts(flat_events)
        assert stage_counts(rot_events)["train.dispatch"] == 5

    def test_straggler_section_names_slowest_rank(self, train_file,
                                                  tmp_path, capsys):
        """Two rank streams -> the merge grows a straggler section
        attributing each chain segment to its slowest rank."""
        trace = str(tmp_path / "strag.json")
        cfg = _cfg(
            train_file, tmp_path, "strag", trace_file=trace,
            trace_rotate_events=40,
        )
        Trainer(cfg).train()
        windows = sorted(str(p) for p in tmp_path.glob("strag.*.json"))
        # Synthesize rank 1: same windows under a different pid +
        # anchors (a different process would differ in exactly these).
        rank1 = []
        for i, path in enumerate(windows):
            doc = json.load(open(path))
            doc["otherData"]["pid"] = 99999
            doc["otherData"]["wall_anchor"] += 1000.0
            out = str(tmp_path / f"strag_rank1.{i}.json")
            json.dump(doc, open(out, "w"))
            rank1.append(out)
        rc = report.main(
            ["--trace"] + windows + rank1
            + ["-o", str(tmp_path / "strag_merged.json")]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "10 dispatched, 10 with a complete" in out  # 5 + 5
        assert "straggler attribution" in out
        assert "slowest dispatch" in out
        assert "slowest latency" in out

    def test_unrotated_files_stay_separate_streams(self, traced_procs_run,
                                                   tmp_path):
        """Legacy traces (no window metadata) must keep the one-file =
        one-rank contract even when byte-identical copies are merged
        (sb ids restart per rank; anchor-grouping them would
        cross-wire the chains)."""
        import shutil

        r0 = str(tmp_path / "a.json")
        r1 = str(tmp_path / "b.json")
        shutil.copy(traced_procs_run["trace"], r0)
        shutil.copy(traced_procs_run["trace"], r1)
        _, _, per_file = report.merge_traces([r0, r1])
        streams = report.group_streams(per_file)
        assert len(streams) == 2


# ---------------------------------------------------------------------------
# Health monitors: NaN injection under both nan_policy modes
# ---------------------------------------------------------------------------


def _poison(trainer):
    """Inject a NaN that corrupts every subsequent gradient: w0 = NaN
    makes scores (hence dL/dscore) non-finite from the first step."""
    trainer.state = trainer.state._replace(
        params=trainer.state.params._replace(
            w0=jnp.full((), jnp.nan, jnp.float32)
        )
    )


class TestNanPolicy:
    def test_halt_raises_within_one_dispatch(self, train_file, tmp_path):
        k = 4
        mf = str(tmp_path / "halt.jsonl")
        cfg = _cfg(
            train_file, tmp_path, "halt", steps_per_dispatch=k,
            nan_policy="halt", metrics_file=mf,
        )
        t = Trainer(cfg)
        _poison(t)
        with pytest.raises(NonFiniteGradError):
            t.train()
        # The poisoned dispatch is #0; the delayed check consumes its
        # scalars right after dispatch #1 — within one dispatch, i.e.
        # at most 2K steps ever ran.
        assert int(t.state.step) <= 2 * k
        # Crash-truthful stream: the final record names the exception
        # and carries the health counters.
        recs = [json.loads(l) for l in open(mf)]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert final["exception"] == "NonFiniteGradError"
        assert final["health"]["nonfinite_steps"] > 0
        assert final["health"]["first_nonfinite_step"] == 0

    def test_warn_completes_and_reports(self, train_file, tmp_path):
        mf = str(tmp_path / "warn.jsonl")
        cfg = _cfg(
            train_file, tmp_path, "warn", nan_policy="warn",
            metrics_file=mf,
        )
        t = Trainer(cfg)
        _poison(t)
        result = t.train()  # must NOT raise
        health = result["train"]["health"]
        assert health["nonfinite_steps"] == 20  # every step was bad
        assert health["first_nonfinite_step"] == 0
        # The damage appears in the final record too (no exception —
        # the run completed under warn).
        recs = [json.loads(l) for l in open(mf)]
        final = [r for r in recs if r.get("record") == "final"][-1]
        assert "exception" not in final
        assert final["health"]["nonfinite_steps"] == 20
        assert final["health"]["first_nonfinite_step"] == 0

    def test_health_reporting_is_per_run(self, train_file, tmp_path):
        """state.step is instance-cumulative; health reporting must
        rebase to the run (a clean first run then a poisoned second on
        the same Trainer reports first_nonfinite_step 0, not 20, and an
        RMS over run-2 steps only)."""
        cfg = _cfg(train_file, tmp_path, "rerun", nan_policy="warn")
        t = Trainer(cfg)
        r1 = t.train()
        assert r1["train"]["health"]["nonfinite_steps"] == 0
        _poison(t)
        r2 = t.train()
        health = r2["train"]["health"]
        assert health["nonfinite_steps"] == 20
        assert health["first_nonfinite_step"] == 0  # per-run step base

    def test_nan_policy_validated(self):
        with pytest.raises(ValueError, match="nan_policy"):
            FmConfig(nan_policy="explode")

    def test_halt_blocks_periodic_save_of_poisoned_params(
        self, train_file, tmp_path
    ):
        """A save boundary in the same iteration as the poisoned
        dispatch must NOT write the checkpoint first: the save path
        force-consumes the pending health readback, so halt fires
        before any poisoned params persist."""
        from fast_tffm_tpu.train import checkpoint

        cfg = _cfg(
            train_file, tmp_path, "halt_save", steps_per_dispatch=4,
            nan_policy="halt", save_steps=4,  # save every dispatch
        )
        t = Trainer(cfg)
        _poison(t)
        with pytest.raises(NonFiniteGradError):
            t.train()
        # The first save boundary coincided with the first (poisoned)
        # dispatch; the forced check ran first, so no checkpoint exists.
        assert not checkpoint.exists(cfg.model_file)


# ---------------------------------------------------------------------------
# Crash-truthful final record (any crash, not just nan halt)
# ---------------------------------------------------------------------------


class TestCrashTruthfulFinal:
    def test_interrupted_run_still_writes_final_record(self, train_file,
                                                       tmp_path, capsys):
        mf = str(tmp_path / "crash.jsonl")
        cfg = _cfg(
            train_file, tmp_path, "crash", metrics_file=mf,
            steps_per_dispatch=2,
        )
        t = Trainer(cfg)
        real = t._scan_train_step
        count = {"n": 0}

        def dying(state, batch):
            if count["n"] >= 2:
                raise KeyboardInterrupt("simulated preemption")
            count["n"] += 1
            return real(state, batch)

        t._scan_train_step = dying
        with pytest.raises(KeyboardInterrupt):
            t.train()
        recs = [json.loads(l) for l in open(mf)]
        final = [r for r in recs if r.get("record") == "final"]
        assert len(final) == 1
        final = final[-1]
        assert final["exception"] == "KeyboardInterrupt"
        assert final["step"] == 4  # partial counters survived
        assert "stages" in final and "health" in final
        # And report.py summarizes the crashed stream end to end.
        assert report.main([mf]) == 0
        out = capsys.readouterr().out
        assert "KeyboardInterrupt" in out


# ---------------------------------------------------------------------------
# tools/check_tier1.py — the marker audit behind lint rule T1001
# ---------------------------------------------------------------------------


_GOOD = """
import pytest

def test_fast():
    pass

@pytest.mark.slow
def test_slow():
    pass

class TestGroup:
    def test_also_fast(self):
        pass
"""

_ALL_SLOW = """
import pytest
pytestmark = pytest.mark.slow

def test_one():
    pass

def test_two():
    pass
"""

_TYPO_MARK = """
import pytest

@pytest.mark.sloww
def test_typo():
    pass
"""


class TestCheckTier1:
    def _repo(self, tmp_path, files):
        tests = tmp_path / "tests"
        tests.mkdir()
        (tmp_path / "pytest.ini").write_text(
            "[pytest]\nmarkers =\n    slow: slow tests\n    tpu: tpu\n"
        )
        for name, body in files.items():
            (tests / name).write_text(body)
        return str(tests), str(tmp_path)

    def test_counts_and_module_pytestmark(self, tmp_path):
        tests, root = self._repo(tmp_path, {
            "test_good.py": _GOOD, "test_allslow.py": _ALL_SLOW,
        })
        result = check_tier1.audit(tests, root)
        assert result["per_file"]["test_good.py"] == {
            "tests": 3, "tier1": 2, "slow": 1,
            "marks_used": {"slow"},
        }
        assert result["per_file"]["test_allslow.py"]["tier1"] == 0
        assert not result["ok"]
        assert any("test_allslow.py" in p for p in result["problems"])

    def test_undeclared_marker_flagged(self, tmp_path):
        tests, root = self._repo(tmp_path, {"test_typo.py": _TYPO_MARK})
        result = check_tier1.audit(tests, root)
        assert any("sloww" in p for p in result["problems"])

    def test_real_repo_passes(self):
        repo = os.path.dirname(_TOOLS)
        result = check_tier1.audit(os.path.join(repo, "tests"), repo)
        assert result["ok"], result["problems"]
        # This very file must contribute tier-1 tests.
        assert result["per_file"]["test_tracing.py"]["tier1"] > 0


# ---------------------------------------------------------------------------
# tools/report.py --compare — regression flagging
# ---------------------------------------------------------------------------


class TestCompare:
    def test_bench_json_regression_flagged(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        base = {"metric": "x", "value": 100.0,
                "e2e_examples_per_sec": 100.0, "ingest_wait_frac": 0.10,
                "platform": "cpu"}
        a.write_text(json.dumps(base))
        worse = dict(base, e2e_examples_per_sec=80.0, value=80.0,
                     ingest_wait_frac=0.30)
        b.write_text(json.dumps(worse))
        rc = report.main(["--compare", str(a), str(b)])
        out = capsys.readouterr().out
        assert rc == 2
        assert out.count("REGRESSION") >= 3  # rate fell, wait rose

    def test_no_flag_within_threshold(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps({"metric": "x", "value": 100.0}))
        b.write_text(json.dumps({"metric": "x", "value": 98.0}))
        assert report.main(["--compare", str(a), str(b)]) == 0
        assert "REGRESSION" not in capsys.readouterr().out

    def test_metrics_jsonl_compare(self, traced_procs_run, capsys):
        mf = traced_procs_run["metrics"]
        rc = report.main(["--compare", mf, mf])
        assert rc == 0  # identical run: no regression against itself
        out = capsys.readouterr().out
        assert "examples_in" in out


# ---------------------------------------------------------------------------
# ISSUE 14: per-request distributed tracing across the serve fleet
# ---------------------------------------------------------------------------


class TestServeTrace:
    """A sampled request through an (in-process) 2-replica router
    renders as ONE connected cross-process chain — router admit ->
    proxy -> replica queue wait -> coalesce -> rung dispatch ->
    respond — and ``tools/report.py --serve-trace`` walks it.  The
    unsampled path stays bitwise-identical (same score bytes, no
    X-Request-Id, zero spans)."""

    _CFG_KW = dict(
        vocabulary_size=64, factor_num=4, max_features=4,
        serve_batch_sizes="8", max_batch_wait_ms=1.0,
    )

    @pytest.fixture(scope="class")
    def fleet(self, tmp_path_factory):
        import urllib.request

        import jax

        from fast_tffm_tpu.models import fm
        from fast_tffm_tpu.serve import wire
        from fast_tffm_tpu.serve.batcher import ServeBatcher
        from fast_tffm_tpu.serve.router import Replica, ServeRouter
        from fast_tffm_tpu.serve.scorer import FixedShapeScorer
        from fast_tffm_tpu.serve.server import ServeServer

        tmp = tmp_path_factory.mktemp("serve_trace")
        cfg = FmConfig(model_file=str(tmp / "model"), **self._CFG_KW)
        params = jax.jit(
            lambda k: fm.init_params(k, cfg=cfg)
        )(jax.random.PRNGKey(0))
        stacks = []
        replicas = []
        for i in range(2):
            tracer = obs.Tracer(enabled=True,
                                process_name=f"replica{i}")
            scorer = FixedShapeScorer(cfg, params)
            scorer.warmup()
            batcher = ServeBatcher(
                scorer, max_batch_wait_ms=cfg.max_batch_wait_ms,
                tracer=tracer,
            )
            server = ServeServer(
                0, batcher, cfg, lambda: {"record": "status"},
                tracer=tracer,
            )
            stacks.append((tracer, batcher, server))
            replicas.append(Replica(i, "127.0.0.1", server.port))
        router_tracer = obs.Tracer(enabled=True,
                                   process_name="router")
        rcfg = FmConfig(model_file=str(tmp / "model"),
                        serve_replicas=2, **self._CFG_KW)
        router = ServeRouter(
            0, replicas, rcfg, health_secs=10.0,
            tracer=router_tracer,
            sampler=wire.RequestSampler(1.0, enabled=True, tag="rt"),
        )

        def post(path, body, headers=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}{path}", data=body,
                method="POST", headers=headers or {},
            )
            resp = urllib.request.urlopen(req, timeout=30)
            return resp.status, resp.read(), dict(resp.headers)

        yield {
            "router": router, "router_tracer": router_tracer,
            "stacks": stacks, "post": post, "tmp": tmp,
        }
        router.close()
        for _, batcher, server in stacks:
            server.close()
            batcher.close()

    def _dump_all(self, fleet):
        tmp = fleet["tmp"]
        paths = []
        router_path = str(tmp / "trace.json")
        fleet["router_tracer"].dump(router_path)
        paths.append(router_path)
        for i, (tracer, _, _) in enumerate(fleet["stacks"]):
            p = str(tmp / f"trace.json.replica{i}")
            tracer.dump(p)
            paths.append(p)
        return paths

    def _chains_of(self, fleet, rid):
        """Merged events and the chains of one request.  The replica
        emits ``serve.respond`` AFTER it wrote the reply (the span
        covers the write), so the client can hold the reply before the
        span exists: dump again until the chain closes, 5 s at most."""
        deadline = time.monotonic() + 5.0
        while True:
            events, _, _ = report.merge_traces(self._dump_all(fleet))
            mine = [c for c in report.serve_request_chains(events)
                    if c["rid"] == rid]
            if (mine and mine[0]["complete"]) \
                    or time.monotonic() > deadline:
                return events, mine
            time.sleep(0.02)

    def test_sampled_request_chain_is_complete(self, fleet):
        status, body, hdrs = fleet["post"](
            "/score", b"1 3:1\n0 2:0.5\n"
        )
        assert status == 200
        rid = hdrs.get("X-Request-Id")
        assert rid, "sampled request lost its id echo"
        assert len(body.decode().split()) == 2
        events, mine = self._chains_of(fleet, rid)
        assert len(mine) == 1
        chain = mine[0]
        assert chain["complete"], (
            f"chain missing segments: {sorted(chain['spans'])}"
        )
        for seg in ("admit", "proxy", "queue_wait", "coalesce",
                    "dispatch", "respond"):
            assert seg in chain["spans"], seg
        assert chain["replica"] in (0, 1)
        # The replica half carries the SAME rid the router minted:
        # the spans came from different Tracer instances, joined only
        # by the propagated id.
        assert chain["spans"]["dispatch"]["args"]["rid"] == rid
        # Flow arrows: start at the proxy, step at the dispatch, end
        # at the respond — the Perfetto-visible connection.
        flows = [
            ev for ev in events
            if ev.get("cat") == "tffm_flow" and ev.get("id") == rid
        ]
        assert {f["ph"] for f in flows} == {"s", "t", "f"}

    def test_sampled_score_bin_chain_is_complete(self, fleet):
        """The acceptance shape: a sampled /score_bin request — the id
        rides the frame's flags-bit-1 trailer across the proxy hop —
        still reconstructs the full cross-process chain."""
        from fast_tffm_tpu.serve import wire

        ids = np.zeros((2, 4), np.int32)
        vals = np.ones((2, 4), np.float32)
        status, body, hdrs = fleet["post"](
            "/score_bin", wire.encode_bin_request(ids, vals),
            headers={"Content-Type": "application/octet-stream"},
        )
        assert status == 200
        rid = hdrs.get("X-Request-Id")
        assert rid
        assert len(wire.decode_bin_response(body)) == 2
        _, chains = self._chains_of(fleet, rid)
        assert len(chains) == 1 and chains[0]["complete"], (
            f"bin chain: {sorted(chains[0]['spans']) if chains else []}"
        )

    def test_report_serve_trace_mode(self, fleet, capsys):
        for _ in range(3):
            status, _, _ = fleet["post"]("/score", b"1 3:1\n")
            assert status == 200
        paths = self._dump_all(fleet)
        rc = report.main(["--serve-trace"] + paths)
        out = capsys.readouterr().out
        assert rc == 0
        assert "sampled requests:" in out
        assert "critical path" in out
        assert "dispatch" in out

    def test_unsampled_serving_is_bitwise_identical(
        self, tmp_path_factory
    ):
        import urllib.request

        import jax

        from fast_tffm_tpu.models import fm
        from fast_tffm_tpu.serve import wire
        from fast_tffm_tpu.serve.batcher import ServeBatcher
        from fast_tffm_tpu.serve.scorer import FixedShapeScorer
        from fast_tffm_tpu.serve.server import ServeServer

        tmp = tmp_path_factory.mktemp("serve_trace_off")
        cfg = FmConfig(model_file=str(tmp / "model"), **self._CFG_KW)
        params = jax.jit(
            lambda k: fm.init_params(k, cfg=cfg)
        )(jax.random.PRNGKey(0))
        scorer = FixedShapeScorer(cfg, params)
        scorer.warmup()

        def serve_once(tracer, sampler):
            batcher = ServeBatcher(scorer, tracer=tracer)
            server = ServeServer(
                0, batcher, cfg, lambda: {"record": "status"},
                tracer=tracer, sampler=sampler,
            )
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{server.port}/score",
                    data=b"1 3:1\n0 2:0.5\n", method="POST",
                )
                resp = urllib.request.urlopen(req, timeout=30)
                return resp.read(), dict(resp.headers)
            finally:
                server.close()
                batcher.close()

        off_tracer = obs.Tracer(enabled=True)  # enabled, NOT sampled
        body_off, hdrs_off = serve_once(
            off_tracer, wire.RequestSampler(0.0, enabled=True)
        )
        on_tracer = obs.Tracer(enabled=True)
        body_on, hdrs_on = serve_once(
            on_tracer, wire.RequestSampler(1.0, enabled=True)
        )
        # Scores are bitwise-identical with tracing on or off...
        assert body_off == body_on
        # ...the unsampled response carries no id header...
        assert "X-Request-Id" not in hdrs_off
        assert "X-Request-Id" in hdrs_on
        # ...and the unsampled path emitted ZERO spans (no-op spans,
        # no id allocation — the satellite contract).
        assert off_tracer.take() == []
        assert [e for e in on_tracer.take()
                if e.get("ph") == "X"] != []
