"""Driver of the job kind ``train_ffm``: ``cli.main(["train", cfg])``
over seed-made ``field:id:val`` text -- a field-aware FM (``field_num >
0``), one process, the trainer's own threads.

Everything that knows no model is ``drivers/train.py``'s, loaded by path
as a copy of this driver's own: the observer around
``Trainer._scan_train_step`` (dispatches 1..3 are set-up and are what the
plain reference follows; the window opens behind them), the job's drive,
the check, the timers.  What differs is stated here:

* the text: column j of a line is field j (``j:id:0.dddd``), from the
  same seeded Zipf(1.1) ids, values and planted labels (the module's
  ``synth`` is given another writer; ``make_inputs`` is its own);
* the batch the reference is handed carries ``fields``, and a step whose
  fields are not the columns' is an error of the feed
  (``fields_not_in_file``, exact);
* one more planted fault, ``fields_zeroed``: the timed path gets every
  batch with its fields set to 0 (the reference the batch as fed);
* the whole step's roofline count is the field-aware model's
  (``fmbench/roofline_ffm.py``).

The step is not split by ``jax.named_scope`` here: a v5e trace event
carries no stat that names its scope (PERF.md section 7), so there is
nothing for a reducer to read.
"""

from __future__ import annotations

import time
import types

import numpy as np

from fmbench import compare, harness, roofline_ffm, synth

base = harness.load_by_path("drivers", "train")
# This copy of the module follows steps whose batches carry fields: its
# observer records them and its check hands them to the reference.
base.BATCH_FIELDS = ("ids", "vals", "fields", "labels", "weights")


def write_ffm(path: str, labels, ids: np.ndarray, v4: np.ndarray) -> None:
    """``synth.write_libsvm`` with the column's number in front of every
    token: ``label 0:id:0.dddd 1:id:0.dddd ...``."""
    n, f = ids.shape
    cols = [labels.astype("S1")]
    for j in range(f):
        cols.append(np.char.add(
            np.char.add(b" %d:" % j,
                        np.char.add(ids[:, j].astype("S10"), b":0.")),
            v4[:, j].astype("S4"),
        ))
    while len(cols) > 1:  # log-depth reduce, as in synth.libsvm_lines
        nxt = [np.char.add(cols[i], cols[i + 1])
               for i in range(0, len(cols) - 1, 2)]
        if len(cols) % 2:
            nxt.append(cols[-1])
        cols = nxt
    with open(path, "wb") as out:
        out.write(b"\n".join(cols[0]))
        out.write(b"\n")


# ... and writes its lines (seeded, cached per checkout and seed, the cfg
# beside them: all ``make_inputs``'s) through this writer.
base.synth = types.SimpleNamespace(**{**vars(synth), "write_libsvm": write_ffm})


def round_operands_on_the_cpu() -> None:
    """The lower-precision control of a REHEARSAL.  On the chip
    ``compute_dtype=bfloat16`` rounds the interaction's operands (gathered
    rows, values) to bfloat16; off it ``platform.ffm_compute_dtype`` turns
    that back into float32 (XLA:CPU runs no bf16 dot), so the control
    would read as a sound run.  Here the harness rounds the same two
    operands itself, in front of the program's own op."""
    import jax.numpy as jnp

    from fast_tffm_tpu.ops import interaction

    op = interaction.ffm_interaction

    def rounded(rows, vals, *rest):
        return op(rows.astype(jnp.bfloat16).astype(rows.dtype),
                  vals.astype(jnp.bfloat16).astype(vals.dtype), *rest)

    interaction.ffm_interaction = rounded


class FieldObserver(base.StepObserver):
    """``train.py``'s observer, with the fault this model adds: under
    ``fields_zeroed`` the step itself (every dispatch, checked or timed)
    sees fields of 0 while the record keeps the batch as fed."""

    def install(self):
        super().install()
        self._true_orig = self._orig
        if self.fault == "fields_zeroed":
            import jax.numpy as jnp

            step = self._orig
            self._orig = lambda trainer, state, batches: step(
                trainer, state,
                batches._replace(fields=jnp.zeros_like(batches.fields)))
            self.fault = ""  # nothing for the base's own faults to do

    def uninstall(self):
        self._orig = self._true_orig
        super().uninstall()


def run(*, cell, seed, seconds, trace, rehearse, control, fault, rate,
        via_checkpoint, work, t0) -> dict:
    if rate or via_checkpoint:
        raise SystemExit("--rate and --via-checkpoint are for serve cells")
    config = cell["config"]
    cfg_path, keys, inputs = base.make_inputs(work, config, seed, rehearse,
                                              control)
    if keys["max_features"] != keys["field_num"]:
        raise SystemExit("the text has one column a field: max_features "
                         f"{keys['max_features']} != field_num "
                         f"{keys['field_num']}")
    if control == "bf16" and rehearse:
        round_operands_on_the_cpu()
    inputs_s = time.time() - t0
    tracer = harness.TraceWindow(work, trace)
    mix = cell["traffic"]
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    ref = harness.load_by_path("reference", config["reference"])
    obs = FieldObserver(seconds, tracer, fault, t0, mix.get("warm") or {},
                        ref.program_leaves(keys), keys["vocabulary_size"])
    final = base.drive_job(cfg_path, keys["metrics_file"], obs, tracer)
    reduced = tracer.reduce()
    harness.free_device()
    checks = compare.Checks()
    t_check = time.time()
    # column j of every line is field j, whatever order the lines come in
    f = keys["max_features"]
    checks.add("fields_not_in_file", sum(
        int((s["fields"] != np.arange(f, dtype=s["fields"].dtype)).sum())
        for s in obs.steps), 0)
    detail = base.check(cell, keys, obs, inputs, checks)
    check_s = time.time() - t_check
    window_s = obs.win_t1 - obs.win_t0
    examples = obs.win_dispatches * obs.batch
    wait0, _ = base._timer(obs.tel0, "train.wait_input")
    wait1, _ = base._timer(obs.tel1, "train.wait_input")
    disp0, n0 = base._timer(obs.tel0, "train.dispatch")
    disp1, n1 = base._timer(obs.tel1, "train.dispatch")
    gauges = (obs.tel1 or {}).get("gauges") or {}
    res = final.get("resource", {})
    uniq = detail.get("prog", {}).get("unique_per_step") or [0]
    needed = roofline_ffm.train_step_needed(
        obs.batch, f, keys["field_num"], keys["factor_num"],
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
            # the program's gauges at the window's close
            "apply_unique_frac": gauges.get("train.apply_unique_frac"),
            "row_floats": gauges.get("train.row_floats"),
            "timers": {n: dict(zip(("total_s", "count"),
                                   base._timer(obs.tel1, n)))
                       for n in ("ingest.parse", "ingest.sketch")},
            "detail": detail,
        },
    }
