"""Driver of the job kind ``train``: ``cli.main(["train", cfg])`` over
seed-made libsvm text, one process, the trainer's own threads.

The trainer has no time limit and no hook of its own, so the harness
stands around the one call every dispatch goes through
(``Trainer._scan_train_step``) and touches nothing else:

* dispatches 1..3 are set-up.  They compile the step, and they are the
  steps the plain reference follows: around each, the rows the batch
  touches are read back before and after, with the batch as it was fed
  and the scores the step returned.
* the window opens when the third has finished (the same Trainer, the
  same compiled step, the same feed) and closes at the first dispatch
  boundary at or past ``--seconds``: the device is drained, the clock
  read, and the trainer's handled KeyboardInterrupt path ends the job
  (no checkpoint is written inside the window).  ``train_ex_per_s`` is
  every example dispatched in the window over all its seconds.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from fmbench import compare, harness, roofline, synth
from fmbench.harness import write_cfg

N_CHECK = 3
BATCH_FIELDS = ("ids", "vals", "labels", "weights")


class WindowClosed(KeyboardInterrupt):
    """Ends ``Trainer.train()`` through its KeyboardInterrupt path."""


def make_inputs(work: str, config: dict, seed: int, rehearse: bool,
                control: str) -> tuple:
    keys = dict(config["cfg"])
    hz = dict(config["harness"])
    if rehearse:
        over = dict(config["rehearse"])
        hz["train_lines"] = over.pop("train_lines")
        keys.update(over)
    if control == "bf16":
        keys["compute_dtype"] = "bfloat16"
    elif control:
        raise SystemExit(f"unknown control {control!r} for a train cell")
    n, f, v = hz["train_lines"], keys["max_features"], keys["vocabulary_size"]
    # The lines are a pure function of these sizes and the seed: made once
    # per checkout (arrays first, the text last and by a rename, so a file
    # that is there is whole), found again by every later run on the seed.
    cache = harness.cache_dir(f"{config['name']}-n{n}-f{f}-v{v}-seed{seed}")
    data = os.path.join(cache, "train_0.libsvm")
    arrays = os.path.join(cache, "lines.npz")
    if os.path.isfile(data) and os.path.isfile(arrays):
        with np.load(arrays) as z:
            ids, v4, labels = z["ids"], z["v4"], z["labels"]
    else:
        rng = np.random.default_rng(seed)
        ids = synth.zipf_ids(rng, (n, f), v)
        v4 = synth.val4(rng, (n, f))
        labels = synth.planted_labels(rng, ids, v4)
        tmp = os.path.join(cache, f"{os.getpid()}.tmp")
        with open(tmp, "wb") as out:
            np.savez(out, ids=ids, v4=v4.astype(np.int16),
                     labels=labels.astype(np.int8))
        os.replace(tmp, arrays)
        synth.write_libsvm(tmp, labels, ids, v4)
        os.replace(tmp, data)
    keys.update({
        "epoch_num": hz["epoch_num"],
        "seed": harness.fold_seed(seed),
        "train_files": data,
        "model_file": os.path.join(work, "model"),
        "metrics_file": os.path.join(work, "metrics.jsonl"),
    })
    cfg_path = os.path.join(work, "train.cfg")
    write_cfg(cfg_path, keys)
    return cfg_path, keys, {"raw_ids": ids, "v4": v4, "labels": labels}


def _key(k) -> str:
    for attr in ("name", "key", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def named_leaves(state) -> dict:
    """The leaves of the program's ``(params, opt_state)`` by path:
    ``params.table``, ``opt_state.acc.w0``, ..."""
    import jax

    out = {}
    for prefix in ("params", "opt_state"):
        flat, _ = jax.tree_util.tree_flatten_with_path(getattr(state, prefix))
        for path, leaf in flat:
            out[".".join([prefix] + [_key(k) for k in path])] = leaf
    return out


class StepObserver:
    """Stands around ``Trainer._scan_train_step``."""

    def __init__(self, seconds: float, tracer, fault: str, t0: float,
                 warm: dict, leaves: dict, vocab: int):
        self.seconds, self.tracer, self.fault = seconds, tracer, fault
        self.warm, self.leaves, self.vocab = warm, leaves, vocab
        self.proc_t0 = t0
        self.calls = 0
        self.steps = []   # per observed step: the batch, scores, leaves
        self.final = None  # per observed step: params on its rows, at the end
        self.row_leaves = set()  # leaves read by the rows a batch touches
        self.first_dispatch_s = self.checked_s = self.setup_s = None
        self.warm_dispatches = 0
        self._best_backlog, self._best_at = -1, 0.0
        self._buckets = {}
        self._caught_up, self._ticks = False, []
        self.win_t0 = self.win_t1 = None
        self.win_dispatches = 0
        self.tel0 = self.tel1 = None
        self.memory_peak = 0
        self._gather = None

    def install(self):
        from fast_tffm_tpu.train import loop

        self._orig = loop.Trainer._scan_train_step
        me = self

        def observed(trainer, state, batches):
            return me.on_dispatch(trainer, state, batches)

        loop.Trainer._scan_train_step = observed

    def uninstall(self):
        from fast_tffm_tpu.train import loop

        loop.Trainer._scan_train_step = self._orig

    # ------------------------------------------------------------ set-up

    def _snap(self, state, names, ids) -> dict:
        """Host copies of the named leaves: a table (leading axis the
        vocabulary) by the rows ``ids`` touches, any other leaf whole."""
        import jax

        if self._gather is None:
            self._gather = jax.jit(lambda t, i: t[i])
        leaves = named_leaves(state)
        out = {}
        for name in names:
            leaf = leaves[name]
            if leaf.ndim and leaf.shape[0] == self.vocab:
                self.row_leaves.add(name)
                out[name] = np.asarray(self._gather(leaf, ids))
            else:
                out[name] = np.asarray(leaf)
        return out

    def _observe(self, trainer, state, batches):
        import jax
        import jax.numpy as jnp

        if batches.ids.shape[0] != 1:
            raise RuntimeError("the check follows steps_per_dispatch = 1")
        ids = batches.ids[0]
        params = self.leaves["params"]
        # the batch as the feed handed it: what the reference gets
        rec = {n: np.asarray(getattr(batches, n)[0]) for n in BATCH_FIELDS}
        rec["pre"] = self._snap(state, params, ids)
        if self.fault == "half_batch":
            # Half of the batch left out, the mean taken over the rest.
            b = batches.weights.shape[1]
            batches = batches._replace(
                weights=batches.weights.at[:, b // 2:].set(0.0))
        elif self.fault and self.fault != "state_unchanged":
            raise SystemExit(f"unknown fault {self.fault!r} for a train cell")
        kept = None
        if self.fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new = self._orig(trainer, state, batches)
        if kept is not None:
            # A step that returns its state unchanged.
            new = new._replace(params=kept[0], opt_state=kept[1])
        rec["scores"] = np.asarray(trainer._last_scores)[0]
        if not self.steps:  # what the first gradient is worked out from
            rec["post"] = self._snap(
                new, params + self.leaves["optimizer"], ids)
        self.steps.append(rec)
        if len(self.steps) == N_CHECK:
            self.final = [self._snap(new, params, s["ids"])
                          for s in self.steps]
            jax.block_until_ready(new)
        return new

    def _ingest_caught_up(self, trainer) -> bool:
        """True once the parse threads have filled their queue: the
        program's ``ingest.out_q_depth`` histogram has seen, since the
        last look, a depth at or over the mix's share of the
        configuration's ``queue_size``.  A loop that drains the queue
        faster than it fills (a rehearsal) never gets there; it stops
        waiting once the queue has not been fuller for ``stall_s``."""
        frac = float(self.warm.get("ingest_queue_frac", 0.0))
        if not frac:
            return True
        waited = time.perf_counter() - self._warm_t0
        hist = (trainer.telemetry.snapshot().get("depths") or {}).get(
            "ingest.out_q_depth") or {}
        buckets = {k: int(v) for k, v in (hist.get("buckets") or {}).items()}
        want = frac * trainer.cfg.queue_size
        new = {k: v - self._buckets.get(k, 0) for k, v in buckets.items()}
        self._buckets = buckets
        # a bucket "64-127" / "128+" / "5" counts by its lower end
        full = sum(v for k, v in new.items()
                   if int(k.rstrip("+").split("-")[0]) >= want)
        deepest = int(hist.get("max", 0))
        if deepest > self._best_backlog:
            self._best_backlog, self._best_at = deepest, waited
        return (full > 0
                or waited - self._best_at >= float(
                    self.warm.get("stall_s", 10))
                or waited >= float(self.warm.get("max_s", 120)))

    def _in_rhythm(self) -> bool:
        """True once the last ``settle_dispatches`` gaps between dispatches
        are within ``settle_tol`` of their median (the loop runs at the
        device's pace, not in fits), or ``settle_max_s`` after the queue
        filled."""
        n = int(self.warm.get("settle_dispatches", 0))
        if not n:
            return True
        ticks = self._ticks
        if ticks[-1] - ticks[0] >= float(self.warm.get("settle_max_s", 30)):
            return True
        if len(ticks) < n + 1:
            return False
        gaps = np.diff(ticks[-(n + 1):])
        med = float(np.median(gaps))
        tol = float(self.warm.get("settle_tol", 0.15))
        return bool(np.all(np.abs(gaps - med) <= tol * med))

    # ------------------------------------------------------------ window

    def on_dispatch(self, trainer, state, batches):
        import jax

        i = self.calls
        self.calls += 1
        if i == 0:
            self.first_dispatch_s = time.time() - self.proc_t0
        if i < N_CHECK:
            new = self._observe(trainer, state, batches)
            if i == N_CHECK - 1:
                self.batch = int(batches.labels.shape[1])
                self.checked_s = time.time() - self.proc_t0
                self._warm_t0 = time.perf_counter()
            return new
        if self.win_t0 is None:
            if not self._caught_up and self._ingest_caught_up(trainer):
                self._caught_up = True
            if self._caught_up:
                self._ticks.append(time.perf_counter())
            if not (self._caught_up and self._in_rhythm()):
                # Still set-up: the parse threads are filling their queue
                # and crowd the dispatch loop while they do, then finish
                # the batches they hold.  A job of hours pays this once;
                # the window measures what follows.
                self.warm_dispatches += 1
                return self._orig(trainer, state, batches)
            jax.block_until_ready(state)
            self.tracer.start()
            self.tel0 = trainer.telemetry.snapshot()
            self.win_t0 = time.perf_counter()
            self.setup_s = time.time() - self.proc_t0
        if time.perf_counter() - self.win_t0 >= self.seconds:
            jax.block_until_ready(state)
            self.win_t1 = time.perf_counter()
            self.tracer.stop()
            self.tel1 = trainer.telemetry.snapshot()
            self.memory_peak = harness.memory_peak_bytes()
            raise WindowClosed()
        self.win_dispatches += 1
        return self._orig(trainer, state, batches)


def _f64(x):
    return np.asarray(x, np.float64)


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(_f64(x)))))


def _logloss(scores, labels, weights) -> float:
    s, y, w = _f64(scores), _f64(labels), _f64(weights)
    per = np.logaddexp(0.0, s) - y * s
    return float((per * w).sum() / max(w.sum(), 1e-12))


def _rows_by_id(steps, rows_list):
    """(ids, rows): for every row id the steps touched, its values in the
    earliest of ``rows_list`` (one ``[B, F, D]`` gather per step) that
    holds it."""
    ids_all, rows_all = [], []
    for s, rows in zip(steps, rows_list):
        flat = s["ids"].reshape(-1)
        u, idx = np.unique(flat, return_index=True)
        ids_all.append(u)
        rows_all.append(rows.reshape(len(flat), -1)[idx])
    ids = np.concatenate(ids_all)
    u, idx = np.unique(ids, return_index=True)  # first = earliest step
    return u, np.concatenate(rows_all)[idx]


def program_numbers(ref, keys: dict, obs: StepObserver) -> dict:
    s0 = obs.steps[0]
    flat = s0["ids"].reshape(-1)
    _, idx = np.unique(flat, return_index=True)

    def once(snap):  # a table's rows once per unique id of step 1
        return {n: _f64(x.reshape(len(flat), -1)[idx]
                        if n in obs.row_leaves else x)
                for n, x in snap.items()}

    # Change over the three steps: a table's, on every row they touched
    # (a row's value before the step that first touched it is its
    # initial value); any other leaf's, whole.
    change, touched = {}, np.zeros((0,), np.int64)
    for name in obs.leaves["params"]:
        if name in obs.row_leaves:
            touched, first = _rows_by_id(
                obs.steps, [s["pre"][name] for s in obs.steps])
            _, last = _rows_by_id(obs.steps, [f[name] for f in obs.final])
        else:
            first, last = s0["pre"][name], obs.final[-1][name]
        change[name] = _norm(_f64(last) - _f64(first))
    return {
        "loss": [_logloss(s["scores"], s["labels"], s["weights"])
                 for s in obs.steps],
        "grad": ref.first_gradient(keys, once(s0["pre"]), once(s0["post"])),
        "change": change,
        "rows_touched": touched,
        "unique_per_step": [int(len(np.unique(s["ids"]))) for s in obs.steps],
    }


def reference_numbers(ref, keys: dict, obs: StepObserver, touched) -> dict:
    """The plain reference follows the three steps from the seed's own
    initial state, on the batches as they were fed."""
    import jax
    import jax.numpy as jnp

    gather = jax.jit(lambda t, i: t[i])

    def snap(state, ids):
        return {n: np.asarray(gather(x, ids) if n in obs.row_leaves else x)
                for n, x in ref.param_leaves(state).items()}

    state = ref.init_state(keys)
    at0 = snap(state, obs.steps[0]["ids"])
    init_gap = max(float(np.abs(_f64(at0[n]) - _f64(x)).max())
                   for n, x in obs.steps[0]["pre"].items())
    del at0
    first = snap(state, touched)
    step = ref.make_step(keys)
    out = {"loss": [], "scores": [], "init_gap": init_gap}
    for k, s in enumerate(obs.steps):
        state, aux = step(state, {n: jnp.asarray(s[n]) for n in BATCH_FIELDS})
        out["loss"].append(_logloss(aux["scores"], s["labels"], s["weights"]))
        out["scores"].append(np.asarray(aux["scores"]))
        if k == 0:
            out["grad"] = {n: _norm(g) for n, g in aux["grad"].items()}
        del aux
    last = snap(state, touched)
    del state
    out["change"] = {n: _norm(_f64(last[n]) - _f64(first[n])) for n in first}
    return out


def check(cell: dict, keys: dict, obs: StepObserver, inputs: dict,
          checks: compare.Checks) -> dict:
    config, limits = cell["config"], cell["limits"]
    ref = harness.load_by_path("reference", config["reference"])
    if len(obs.steps) < N_CHECK:
        checks.add("steps_observed", N_CHECK - len(obs.steps), 0)
        return {}
    # hash -> the rows fed are lines of the file under the reference's hash
    want = ref.hash_bucket_decimal(inputs["raw_ids"], keys["vocabulary_size"])

    def sig(ids, v4, y):  # one integer per line: ids, values and label
        return ((ids.astype(np.int64) * 10007 + v4.astype(np.int64)).sum(
            axis=1) * 2 + y.astype(np.int64))

    have = set(sig(want, inputs["v4"], inputs["labels"]).tolist())
    stray = 0
    for s in obs.steps:
        v4 = np.rint(_f64(s["vals"]) * 1e4).astype(np.int64)
        got = sig(s["ids"], v4, s["labels"])
        stray += int(sum(g not in have for g in got.tolist()))
    checks.add("rows_not_in_file", stray, 0)
    prog = program_numbers(ref, keys, obs)
    refn = reference_numbers(ref, keys, obs, prog["rows_touched"])
    loss_gap = max(abs(p - r) / r for p, r in zip(prog["loss"], refn["loss"]))
    score_gap = max(float(np.abs(_f64(s["scores"]) - _f64(r)).max())
                    for s, r in zip(obs.steps, refn["scores"]))
    checks.add_limited("loss_gap", loss_gap, limits)
    checks.add_limited("grad_gap", compare.worst_leaf_gap(
        prog["grad"], refn["grad"]), limits)
    checks.add_limited("change_gap", compare.worst_leaf_gap(
        prog["change"], refn["change"],
        skip=compare.nought_leaves(refn["grad"])), limits)
    checks.add_limited("score_gap", score_gap, limits)
    return {"init_gap": refn["init_gap"], "prog": {
        k: prog[k] for k in ("loss", "grad", "change", "unique_per_step")},
        "ref": {k: refn[k] for k in ("loss", "grad", "change")}}


def _timer(snap: dict, name: str) -> tuple:
    t = (snap.get("timers") or {}).get(name) or {}
    return float(t.get("total_s", 0.0)), int(t.get("count", 0))


def drive_job(cfg_path: str, metrics_file: str, obs: StepObserver,
              tracer) -> dict:
    """``cli.main(["train", cfg])`` under the observer until it closes
    the window; returns the run's own ``final`` record."""
    from fast_tffm_tpu import cli

    obs.install()
    try:
        cli.main(["train", cfg_path])
        raise RuntimeError("the training job ended before the window did")
    except WindowClosed:
        pass
    finally:
        tracer.stop()
        obs.uninstall()
    final = {}
    with open(metrics_file) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("record") == "final":
                final = rec
    return final


def run(*, cell, seed, seconds, trace, rehearse, control, fault, rate,
        via_checkpoint, work, t0) -> dict:
    if rate or via_checkpoint:
        raise SystemExit("--rate and --via-checkpoint are for serve cells")
    config = cell["config"]
    cfg_path, keys, inputs = make_inputs(work, config, seed, rehearse,
                                         control)
    inputs_s = time.time() - t0
    tracer = harness.TraceWindow(work, trace)
    mix = cell["traffic"]
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    ref = harness.load_by_path("reference", config["reference"])
    obs = StepObserver(seconds, tracer, fault, t0, mix.get("warm") or {},
                       ref.program_leaves(keys), keys["vocabulary_size"])
    final = drive_job(cfg_path, keys["metrics_file"], obs, tracer)
    reduced = tracer.reduce()
    harness.free_device()
    checks = compare.Checks()
    t_check = time.time()
    detail = check(cell, keys, obs, inputs, checks)
    check_s = time.time() - t_check
    window_s = obs.win_t1 - obs.win_t0
    examples = obs.win_dispatches * obs.batch
    wait0, _ = _timer(obs.tel0, "train.wait_input")
    wait1, _ = _timer(obs.tel1, "train.wait_input")
    disp0, n0 = _timer(obs.tel0, "train.dispatch")
    disp1, n1 = _timer(obs.tel1, "train.dispatch")
    res = final.get("resource", {})
    uniq = detail.get("prog", {}).get("unique_per_step") or [0]
    needed = roofline.train_step_needed(
        obs.batch, keys["max_features"], keys["factor_num"],
        int(np.mean(uniq)))
    return {
        "attempted": obs.win_dispatches,
        "failed": 0,
        "e2e": {"train_ex_per_s": examples / window_s,
                "setup_s": obs.setup_s},
        "memory_peak_bytes": obs.memory_peak,
        "trace": reduced,
        "checks": checks,
        "counters": {
            "window_s": window_s,
            "dispatches": obs.win_dispatches,
            "examples": examples,
            "wait_input_s": wait1 - wait0,
            "dispatch_s": disp1 - disp0,
            "dispatch_count": n1 - n0,
            "step_needed_bytes": needed["bytes"],
            "step_needed_flops": needed["flops"],
            "step_program_prefix": "jit_scan_health_step",
        },
        "info": {
            "window_s": window_s, "dispatches": obs.win_dispatches,
            "batch_size": obs.batch,
            "phases_s": {"inputs": inputs_s,
                         "first_dispatch": obs.first_dispatch_s,
                         "checked": obs.checked_s,
                         "setup": obs.setup_s, "check": check_s},
            "wait_input_s": wait1 - wait0, "dispatch_s": disp1 - disp0,
            "warm_dispatches": obs.warm_dispatches,
            "compile_s": res.get("compile_s"),
            "compiles": res.get("compiles"),
            "recompiles_unexpected": res.get("recompiles_unexpected"),
            "temp_bytes": res.get("temp_bytes"),
            "unique_rows_per_step": uniq,
            "detail": detail,
        },
    }
