"""Driver of the job kind ``serve``: ``serve(cfg, port=0)`` in this
process (it holds the chip), the load generator in a child process that
never touches jax, requests over HTTP on keep-alive connections.

The table is made from ``--seed`` on the device in one jitted call and
handed to the scorer in memory, in place of ``serve.scorer.load_model``:
a 2.4 GB checkpoint written and read back in every run of every later
check would be most of what a check costs, and serves no request.  That
one name is all the harness replaces; ``serve()``, the scorer's warm-up,
the batcher and the HTTP front end are the program's own, and the
scorer places what it is handed exactly as it places a restored table
(``FixedShapeScorer._place`` puts it under the mesh's parameter
sharding, and the rungs are jitted with explicit ``in_shardings``).
``run.py --via-checkpoint`` (labelled) writes the same table through
the program's checkpoint writer and lets the real ``load_model`` restore
it: the run that PERF.md sets beside an in-memory one.

Once the window has closed and the server is shut down and freed, the
plain reference scores every request the window answered (open loop) or
the first answer to every pool body (closed loop), from the same seed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from fmbench import compare, harness, roofline, traffic
from fmbench.harness import write_cfg

def build_cfg(work: str, config: dict, rehearse: bool, control: str):
    keys = dict(config["cfg"])
    if rehearse:
        keys.update(config["rehearse"])
    if control == "bf16":
        keys["serve_table_dtype"] = "bf16"
    elif control:
        raise SystemExit(f"unknown control {control!r} for a serve cell")
    keys.update({"model_file": os.path.join(work, "model"),
                 "metrics_file": os.path.join(work, "serve_metrics.jsonl")})
    path = os.path.join(work, "serve.cfg")
    write_cfg(path, keys)
    return path, keys


def make_weights(ref, config: dict, keys: dict, seed: int):
    """(w0, table) on the device, from the seed."""
    import jax.numpy as jnp

    hz = config["harness"]
    s = harness.fold_seed(seed)
    table = ref.uniform_table(s, keys["vocabulary_size"],
                              1 + keys["factor_num"], hz["weight_scale"])
    w0 = np.random.default_rng(s).uniform(hz["w0_low"], hz["w0_high"])
    return jnp.asarray(w0, jnp.float32), table


def write_checkpoint(model_file: str, w0, table) -> None:
    """The table through the program's own checkpoint writer, for its
    own ``load_model`` to restore (``--via-checkpoint``)."""
    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.train import checkpoint

    checkpoint.save(model_file, 0, fm.FmParams(w0=w0, table=table))


def hand_over_model(w0, table):
    """Replace ``serve.scorer.load_model`` for this process."""
    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.serve import scorer as scorer_lib

    def load_model(cfg, mesh=None):
        return "dense", 0, fm.FmParams(w0=w0, table=table)

    scorer_lib.load_model = load_model


def plant_fault(handle, fault: str) -> None:
    if fault != "answer_altered":
        raise SystemExit(f"unknown fault {fault!r} for a serve cell")
    inner = handle.scorer._dispatch_rung

    def altered(ids, vals, fields, b):
        out = np.array(inner(ids, vals, fields, b))
        out[0] += 1e-3  # one answer altered where it is produced
        return out

    handle.scorer._dispatch_rung = altered


def _say(child, line: str) -> None:
    child.stdin.write(line + "\n")
    child.stdin.flush()


def _hear(child, want: str) -> None:
    line = child.stdout.readline().strip()
    if line != want:
        raise RuntimeError(f"load generator said {line!r}, not {want!r}")


def _serve_counters(snap: dict) -> dict:
    t = (snap.get("timers") or {}).get("serve.dispatch") or {}
    c = snap.get("counters") or {}
    g = snap.get("gauges") or {}
    ex = float(c.get("serve.examples", 0))
    fill = float(g.get("serve.batch_fill", 0.0))
    return {"dispatch_s": float(t.get("total_s", 0.0)),
            "dispatch_count": int(t.get("count", 0)),
            "examples": ex, "slots": ex / fill if fill else 0.0,
            "batches": int(c.get("serve.batches", 0))}


def reference_probs(ref, keys, w0, table, raw_ids, v4, text_rows):
    """Probabilities for ``[n, F]`` requests' rows, in blocks.  Binary
    frames' ids reduce modulo the vocabulary; text tokens are hashed."""
    import jax
    import jax.numpy as jnp

    v = keys["vocabulary_size"]
    ids = (raw_ids % v).astype(np.int32)
    if text_rows.any():
        ids[text_rows] = ref.hash_bucket_decimal(raw_ids[text_rows], v)
    vals = traffic.values(v4)
    # w0 and table are ARGUMENTS: closed over, a 4 GiB table would be
    # folded into the program as a constant (minutes, and the host's RAM).
    fn = jax.jit(ref.probabilities)
    out = np.empty((len(ids),), np.float32)
    block = 65536
    for lo in range(0, len(ids), block):
        hi = min(lo + block, len(ids))
        pad = block - (hi - lo)
        i = np.pad(ids[lo:hi], ((0, pad), (0, 0)))
        x = np.pad(vals[lo:hi], ((0, pad), (0, 0)))
        out[lo:hi] = np.asarray(
            fn(w0, table, jnp.asarray(i), jnp.asarray(x)))[:hi - lo]
    return out


def _quantile_ms(lat, q: float) -> float:
    return 1e3 * float(np.quantile(lat, q, method="higher"))


def run(*, cell, seed, seconds, trace, rehearse, control, fault, rate,
        via_checkpoint, work, t0) -> dict:
    config, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    cfg_path, keys = build_cfg(work, config, rehearse, control)
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    out_path = os.path.join(work, "loadgen.npz")
    spec = {"mix": mix, "seconds": seconds, "seed": seed, "rate": rate,
            "vocab": keys["vocabulary_size"],
            "features": keys["max_features"], "out": out_path}
    spec_path = os.path.join(work, "loadgen.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "fmbench",
                                      "loadgen.py"), "--spec", spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    handle = None
    tracer = harness.TraceWindow(work, trace)
    try:
        from fast_tffm_tpu.config import load_config
        from fast_tffm_tpu.serve.server import serve

        ref = harness.load_by_path("reference", config["reference"])
        w0, table = make_weights(ref, config, keys, seed)
        if via_checkpoint:
            write_checkpoint(keys["model_file"], w0, table)
        else:
            hand_over_model(w0, table)
        del w0, table
        handle = serve(load_config(cfg_path), port=0)
        if fault:
            plant_fault(handle, fault)
        _hear(child, "GENERATED")
        _say(child, f"PORT {handle.port}")
        _hear(child, "WARM")
        tracer.start()
        tel0 = _serve_counters(handle.telemetry.snapshot())
        compiles0 = handle.scorer.compiles
        _say(child, "GO")
        setup_s = time.time() - t0
        if trace:
            time.sleep(min(seconds, float(mix.get("trace_seconds", 5))))
            tracer.stop()
        _hear(child, "DONE")
        child.wait(timeout=30)
        tel1 = _serve_counters(handle.telemetry.snapshot())
        steady_compiles = handle.scorer.compiles - compiles0
        memory_peak = harness.memory_peak_bytes()
    finally:
        tracer.stop()
        if handle is not None:
            handle.close()
        if child.poll() is None:
            child.kill()
            child.wait()
    t_close = time.time()
    reduced = tracer.reduce()
    handle = None
    harness.free_device()

    with np.load(out_path) as npz:  # read each array once, not per use
        got = {k: npz[k] for k in npz.files}
    plan = traffic.make_plan(
        mix, seconds=seconds, seed=seed, vocab=keys["vocabulary_size"],
        features=keys["max_features"], rate=rate)
    checks = compare.Checks()
    w0, table = make_weights(ref, config, keys, seed)
    row_text = np.repeat(plan["text"], plan["n"])
    info = {"steady_compiles": int(steady_compiles)}
    if mix["loop"] == "open":
        status, lat = got["status"], got["latency"]
        n_req = len(status)
        ok = status == 200
        unanswered = int((status == 0).sum())
        offs = got["offsets"]
        short = int(sum((offs[i + 1] - offs[i]) != plan["n"][i]
                        for i in np.flatnonzero(ok)))
        good = ok & ((offs[1:] - offs[:-1]) == plan["n"])
        rows = np.repeat(good, plan["n"])
        want = reference_probs(ref, keys, w0, table, plan["raw_ids"][rows],
                               plan["v4"][rows], row_text[rows])
        served = np.concatenate(
            [got["scores"][offs[i]:offs[i + 1]] for i in np.flatnonzero(good)]
        ) if good.any() else np.zeros((0,), np.float32)
        gap = np.abs(served.astype(np.float64) - want)
        is_text = row_text[rows]
        checks.add("unanswered", unanswered, 0)
        checks.add("wrong_length", short, 0)
        if (~is_text).any():
            checks.add_limited("bin_gap", gap[~is_text].max(), limits)
        if is_text.any():
            checks.add_limited("text_gap", gap[is_text].max(), limits)
        # Every request of the window is in the tail: one that failed or
        # never came counts as slower than any that did.  Latency counts
        # from when a request was DUE; a reply counts towards the rate if
        # it came before the window closed.
        lat_all = np.where(good, lat, np.inf)
        attempted, failed = n_req, int((~good).sum())
        in_window = good & (plan["due"] + lat <= seconds)
        examples = int(plan["n"][in_window].sum())
        span = float(seconds)
        info.update({
            "requests": n_req, "checked_examples": int(len(gap)),
            "rate_per_s": n_req / seconds,
            "generator_late_ms_p50": 1e3 * float(np.median(got["late"])),
            "generator_late_ms_p99": 1e3 * float(
                np.quantile(got["late"], 0.99)),
            "generator_late_ms_max": 1e3 * float(got["late"].max()),
            "span_s": float(got["span_s"]),
            "status_counts": {str(k): int(v) for k, v in zip(
                *np.unique(status, return_counts=True))},
        })
        uniq = 0
    else:
        have, offs = got["have"], got["offsets"]
        rows = np.zeros((len(row_text),), bool)
        for i in have:
            rows[plan["start"][i]:plan["start"][i + 1]] = True
        want = reference_probs(ref, keys, w0, table, plan["raw_ids"][rows],
                               plan["v4"][rows], row_text[rows])
        served = got["scores"]
        checks.add("wrong_length", int(len(served) != len(want)), 0)
        if len(served) == len(want) and len(want):
            # The first answer to each body against the reference, plus
            # how far any later answer to it strayed from the first.
            gap = np.abs(served.astype(np.float64) - want)
            checks.add_limited("bin_gap", gap.max() + float(got["repeat_gap"]),
                               limits)
        info["repeat_gap"] = float(got["repeat_gap"])
        attempted, failed = int(got["attempted"]), int(got["failed"])
        examples = int(got["examples"])
        span = float(got["span_s"])
        # latency from the send to the reply's last byte; a failed
        # request counts as slower than any
        lat_all = np.concatenate([got["latency"], np.full((failed,), np.inf)])
        info.update({"requests": attempted, "checked_examples": int(len(want))})
        # needed rows per request: its own unique ids (one request fills
        # one top rung, so nothing is shared across requests in a rung)
        v = keys["vocabulary_size"]
        uniq = float(np.mean([
            len(np.unique(plan["raw_ids"][plan["start"][i]:
                                          plan["start"][i + 1]] % v))
            for i in range(len(plan["n"]))]))
    # Every end-to-end metric the load generator's record allows, whatever
    # the loop: BENCHMARK.json says which of them a cell is judged on.
    e2e = {"setup_s": setup_s, "serve_ex_per_s": examples / span}
    latency_ms = None
    if len(lat_all):
        latency_ms = {f"p{q}": _quantile_ms(lat_all, q / 100)
                      for q in (50, 90, 95, 99)}
        e2e["serve_p50_ms"] = latency_ms["p50"]
        e2e["serve_p99_ms"] = latency_ms["p99"]
        info["latency_ms"] = latency_ms
        info["latency_ms_max"] = 1e3 * float(lat_all.max())
    del w0, table
    info["phases_s"] = {"setup": setup_s, "check": time.time() - t_close}
    per_req_n = float(np.mean(plan["n"]))
    needed = roofline.serve_needed(
        int(per_req_n), keys["max_features"], keys["factor_num"], int(uniq))
    d_ex = tel1["examples"] - tel0["examples"]
    d_slots = tel1["slots"] - tel0["slots"]
    return {
        "attempted": attempted, "failed": failed, "e2e": e2e,
        "memory_peak_bytes": memory_peak, "trace": reduced,
        "checks": checks,
        "counters": {
            "window_s": span,
            "serve_dispatch_s": tel1["dispatch_s"] - tel0["dispatch_s"],
            "serve_dispatch_count":
                tel1["dispatch_count"] - tel0["dispatch_count"],
            "serve_examples": d_ex, "serve_slots": d_slots,
            "serve_batches": tel1["batches"] - tel0["batches"],
            "rung_program_prefix": "jit_score_fn",
            "latency_ms": latency_ms,
            "request_needed_bytes": needed["bytes"] if uniq else 0,
            "request_needed_flops": needed["flops"] if uniq else 0,
            "request_examples": per_req_n,
        },
        "info": {**info, "examples_ok": examples,
                 "batch_fill": d_ex / d_slots if d_slots else None,
                 "dispatches": tel1["dispatch_count"] - tel0["dispatch_count"]},
    }
