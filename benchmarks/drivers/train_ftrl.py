"""Driver of the job kind ``train_ftrl``: ``cli.main(["train", cfg])``
over seed-made libsvm text with ``optimizer = ftrl`` -- the FM of
``drivers/train.py`` under FTRL-Proximal, one process, the trainer's own
threads.

Everything that knows no optimizer is ``drivers/train.py``'s, loaded by
path as a copy of this driver's own: the text, the observer around
``Trainer._scan_train_step`` (dispatches 1..3 are set-up and are what the
plain reference follows; the window opens behind them), the job's drive,
the check, the timers.  What differs is stated here:

* the check reads every leaf of FTRL's state (``reference/ftrl.py``
  names the weights and both accumulators as the leaves compared), and
  adds two numbers:

  - ``untouched_changed`` (exact, limit 0): rows that none of the three
    checked steps touched whose ``w``, ``z`` or ``n`` changed.  A
    fingerprint of every row of the three tables is taken on the device
    before the first checked step and after the third (``fingerprint``:
    a sum of each row's bits under odd multipliers, 32 bits; a change it
    misses is one in 2^32), and the rows whose fingerprint moved are
    counted outside the three batches' ids;
  - ``l1_zero_gap``: the share of the touched rows' weights (after the
    third step) that FTRL's L1 holds at exactly 0 in one of the program
    and the reference and not in the other;

* one more planted fault, ``l1_dropped``: the timed path runs with
  ``ftrl.l1 = 0`` while the reference keeps the configuration's;
* the observer is ``drivers/train_mesh.py``'s: under ``state_unchanged``
  it writes the batch's rows back as they were instead of keeping a
  second copy of the state -- a chip that holds three 2^25-row tables
  and the step's temporaries has no room for one (the copy asked for
  3.70G of 3.50G free);
* the whole step's roofline count is FTRL's (``fmbench/roofline_ftrl.py``:
  6 row-bytes a unique row), and the apply's own count is published for
  ``train_apply_write_roofline_pct``.
"""

from __future__ import annotations

import functools
import time
import types

import numpy as np

from fmbench import compare, harness, roofline_ftrl

_mesh = harness.load_by_path("drivers", "train_mesh")
base = _mesh.base
FAULTS = base.FAULTS + ("l1_dropped",)


@functools.cache
def _prints():
    import jax
    import jax.numpy as jnp

    def prints(*tables):
        h = jnp.zeros(tables[0].shape[:1], jnp.uint32)
        for k, t in enumerate(tables):
            bits = jax.lax.bitcast_convert_type(t, jnp.uint32)
            mult = (jnp.arange(t.shape[1], dtype=jnp.uint32)
                    * jnp.uint32(2 * k + 0x9E3779B2)) | jnp.uint32(1)
            h = (h * jnp.uint32(0x01000193)
                 + jnp.sum(bits * mult, axis=1, dtype=jnp.uint32))
        return h

    return jax.jit(prints)


def fingerprint(tables) -> np.ndarray:
    """uint32 per row of ``tables`` (each ``[V, D]``, on the device): the
    row's bits, column by column under odd multipliers, summed modulo
    2^32, the tables folded in one after the other.  Equal rows give
    equal prints; a row that changed gives another but for one chance in
    2^32.  One reduction a table, no copy of one."""
    return np.asarray(_prints()(*tables))


def untouched_changed(before: np.ndarray, after: np.ndarray,
                      ids: list) -> int:
    """Rows whose print moved that none of ``ids`` (each step's ``[B,
    F]``, padding included) names."""
    moved = before != after
    moved[np.concatenate([np.asarray(i).reshape(-1) for i in ids])] = False
    return int(moved.sum())


def l1_zero_gap(prog_w: np.ndarray, ref_w: np.ndarray) -> float:
    """Share of the coordinates that are exactly 0 in one and not in the
    other (the same rows, the same order)."""
    return float(np.mean((prog_w == 0) != (ref_w == 0)))


class FtrlObserver(_mesh.MeshObserver):
    """``train_mesh.py``'s observer, with the two reads this optimizer adds:
    the three tables' row prints before the first checked step and after
    the third.  ``l1_dropped`` acts through the configuration the program
    reads (``make_inputs``), so the base sees no fault of its own."""

    fp_before = fp_after = None

    def install(self):
        super().install()
        if self.fault == "l1_dropped":
            self.fault = ""

    def _tables(self, state):
        leaves = base.named_leaves(state)
        return [leaves[n] for n in self.leaves["params"]
                if leaves[n].ndim and leaves[n].shape[0] == self.vocab]

    def _observe(self, trainer, state, batches):
        if not self.steps:
            self.fp_before = fingerprint(self._tables(state))
        new = super()._observe(trainer, state, batches)
        if len(self.steps) == base.N_CHECK:
            self.fp_after = fingerprint(self._tables(new))
        return new


def make_inputs(work, config, seed, rehearse, control, fault):
    """``train.py``'s, and under ``l1_dropped`` the program's cfg with
    ``ftrl.l1 = 0`` (the keys the reference gets keep the
    configuration's)."""
    cfg_path, keys, inputs = base.make_inputs(work, config, seed, rehearse,
                                              control)
    if fault == "l1_dropped":
        harness.write_cfg(cfg_path, {**keys, "ftrl.l1": 0.0})
    return cfg_path, keys, inputs


_reference_numbers = base.reference_numbers


def reference_numbers(ref, keys, obs, touched):
    """``train.py``'s, keeping the reference's weights on the touched
    rows after the last step (``obs.ref_final_w``): its last read of the
    reference's leaves is of the final state, on ``touched``."""
    last = {}

    def param_leaves(state):
        leaves = ref.param_leaves(state)
        last["table"] = leaves["params.table"]
        return leaves

    spy = types.SimpleNamespace(**{**vars(ref), "param_leaves": param_leaves})
    out = _reference_numbers(spy, keys, obs, touched)
    obs.ref_final_w = np.asarray(last.pop("table")[touched])
    return out


base.reference_numbers = reference_numbers


def check(cell, keys, obs, inputs, checks) -> dict:
    detail = base.check(cell, keys, obs, inputs, checks)
    if len(obs.steps) < base.N_CHECK:
        return detail
    checks.add("untouched_changed", untouched_changed(
        obs.fp_before, obs.fp_after, [s["ids"] for s in obs.steps]), 0)
    _, prog_w = base._rows_by_id(
        obs.steps, [f["params.table"] for f in obs.final])
    gap = l1_zero_gap(prog_w, obs.ref_final_w)
    checks.add_limited("l1_zero_gap", gap, cell["limits"])
    detail["l1_zeros"] = {"prog": int((prog_w == 0).sum()),
                          "ref": int((obs.ref_final_w == 0).sum()),
                          "coordinates": int(prog_w.size)}
    return detail


def run(*, cell, seed, seconds, trace, rehearse, control, fault, rate,
        via_checkpoint, work, t0) -> dict:
    if rate or via_checkpoint:
        raise SystemExit("--rate and --via-checkpoint are for serve cells")
    config = cell["config"]
    cfg_path, keys, inputs = make_inputs(work, config, seed, rehearse,
                                         control, fault)
    inputs_s = time.time() - t0
    tracer = harness.TraceWindow(work, trace)
    mix = cell["traffic"]
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    ref = harness.load_by_path("reference", config["reference"])
    obs = FtrlObserver(seconds, tracer, fault, t0, mix.get("warm") or {},
                       ref.program_leaves(keys), keys["vocabulary_size"])
    records = base.drive_job(cfg_path, keys["metrics_file"], obs, tracer)
    reduced = tracer.reduce()
    harness.free_device()
    checks = compare.Checks()
    t_check = time.time()
    detail = check(cell, keys, obs, inputs, checks)
    check_s = time.time() - t_check
    window_s = obs.win_t1 - obs.win_t0
    examples = obs.win_dispatches * obs.batch
    gauges = (obs.tel1 or {}).get("gauges") or {}
    res = base.final_record(records).get("resource", {})
    uniq = detail.get("prog", {}).get("unique_per_step") or [0]
    u_mean = int(np.mean(uniq))
    step = roofline_ftrl.train_step_needed(
        obs.batch, keys["max_features"], keys["factor_num"], u_mean)
    apply_ = roofline_ftrl.apply_needed(keys["factor_num"], u_mean)
    return {
        "attempted": obs.win_dispatches,
        "failed": 0,
        "e2e": {"train_ex_per_s": examples / window_s,
                "setup_s": obs.setup_s},
        "memory_peak_bytes": obs.memory_peak,
        "trace": reduced,
        "trace_file": tracer.file(),
        "telemetry": {"open": obs.tel0, "close": obs.tel1},
        "records": records,
        "checks": checks,
        "counters": {
            "window_s": window_s,
            "dispatches": obs.win_dispatches,
            "examples": examples,
            "step_needed_bytes": step["bytes"],
            "step_needed_flops": step["flops"],
            "apply_needed_bytes": apply_["bytes"],
            "apply_needed_flops": apply_["flops"],
            "step_program_prefix": "jit_scan_health_step",
        },
        "info": {
            "window_s": window_s, "dispatches": obs.win_dispatches,
            "batch_size": obs.batch,
            "phases_s": {"inputs": inputs_s,
                         "first_dispatch": obs.first_dispatch_s,
                         "checked": obs.checked_s,
                         "setup": obs.setup_s, "check": check_s},
            "warm_dispatches": obs.warm_dispatches,
            "compile_s": res.get("compile_s"),
            "compiles": res.get("compiles"),
            "recompiles_unexpected": res.get("recompiles_unexpected"),
            "temp_bytes": res.get("temp_bytes"),
            "unique_rows_per_step": uniq,
            # the program's gauges at the window's close
            "ftrl_zero_frac": gauges.get("train.ftrl_zero_frac"),
            "apply_stream": gauges.get("train.apply_stream"),
            "touch_mask": gauges.get("train.touch_mask"),
            "apply_unique_frac": gauges.get("train.apply_unique_frac"),
            "timers": {n: dict(zip(("total_s", "count"),
                                   base._timer(obs.tel1, n)))
                       for n in ("ingest.parse", "ingest.sketch")},
            "detail": detail,
        },
    }
