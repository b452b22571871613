"""Driver of the job kind ``train_mesh``: ``cli.main(["train", cfg])``
over seed-made libsvm text on a (data x model) mesh of chips -- the
sharded training job, one process driving every chip of the host.

Everything that knows no mesh is ``drivers/train.py``'s, loaded by path
as a copy of this driver's own: the observer around
``Trainer._scan_train_step`` (dispatches 1..3 are set-up and are what the
plain reference follows, on the GLOBAL batch: a synchronous step over the
mesh is the one-device step on the whole batch), the text, the job's
drive, the check, the timers.  What differs is stated here:

* the roofline count is A CHIP's (``fmbench/roofline_mesh.py``): the
  rows its data shard gathers from its model shard, its shard's rows
  that the global batch touches read and written, its share of the batch
  and of the interaction -- so ``train_step_roofline_mfu`` is a chip's
  share of a chip's peak over the per-chip program time;
* the collectives of the run's own trace are reduced per chip and step
  (``fmbench/xplane_collectives.py``) into ``counters``, beside the bytes
  the exchange has to move, for ``train_exchange_ms`` and
  ``train_exchange_ici_pct``;
* the planted fault ``state_unchanged`` puts the touched rows back
  instead of keeping a second copy of the state: a chip that holds a
  2^25-row shard and the step's temporaries has no room for one;
* a rehearsal keeps the configuration's mesh: where the CPU backend came
  up with fewer devices than the mesh has chips (``run.py`` asks jax for
  its devices before any driver loads), the driver starts the same
  command line once more with the device count in ``XLA_FLAGS`` and says
  so on stderr.  On the chip it does nothing of the kind.
"""

from __future__ import annotations

import os
import re
import sys
import time

import numpy as np

from fmbench import compare, harness, roofline_mesh, xplane
from fmbench import xplane_collectives

base = harness.load_by_path("drivers", "train")

_FLAG = "xla_force_host_platform_device_count"


def rehearse_on_the_mesh(chips: int) -> None:
    """A rehearsal whose CPU backend has fewer than ``chips`` devices
    starts over, once, with ``--xla_force_host_platform_device_count``
    (as ``fast_tffm_tpu/platform.py:pin_cpu`` writes it)."""
    import jax

    have = len(jax.devices())
    if have >= chips:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    asked = re.search(rf"--{_FLAG}=(\d+)", flags)
    if asked and int(asked.group(1)) >= chips:
        raise SystemExit(
            f"the rehearsal asked for {asked.group(1)} CPU devices and "
            f"jax came up with {have}; the cell's mesh needs {chips}")
    want = f"--{_FLAG}={chips}"
    flags = (re.sub(rf"--{_FLAG}=\d+", want, flags) if asked
             else (flags + " " + want).strip())
    print(f"benchmark: the rehearsal's CPU backend has {have} device(s), "
          f"the cell's mesh needs {chips}; starting the same command again "
          f"with XLA_FLAGS={flags!r}", file=sys.stderr, flush=True)
    sys.stdout.flush()
    os.execve(sys.executable, [sys.executable] + sys.argv,
              {**os.environ, "XLA_FLAGS": flags})


class MeshObserver(base.StepObserver):
    """``train.py``'s observer on a mesh: it notes the mesh the trainer
    built, and under ``state_unchanged`` hands the state back by writing
    the batch's rows as they were (every leaf the step writes is a table
    row the batch touches, or a scalar) -- bit for bit the state before,
    with no second table on the chip."""

    mesh_shape = None
    _put_back = None  # a jitted in-place row write per table sharding

    def on_dispatch(self, trainer, state, batches):
        if self.mesh_shape is None:
            self.mesh_shape = {k: int(v) for k, v in
                               trainer.mesh.shape.items()}
        return super().on_dispatch(trainer, state, batches)

    def _restore(self, state, old: dict, ids):
        """``state`` with every leaf of ``old`` (``_snap``'s host copies:
        a table by the rows ``ids`` touches, any other leaf whole) put
        back, the tables in place and sharded as they were."""
        import jax

        if self._put_back is None:
            self._put_back = {}
        put = self._put_back
        out = {}
        for prefix in ("params", "opt_state"):
            flat, treedef = jax.tree_util.tree_flatten_with_path(
                getattr(state, prefix))
            leaves = []
            for path, leaf in flat:
                name = ".".join([prefix] + [base._key(k) for k in path])
                if name not in self.row_leaves:
                    leaf = jax.device_put(old[name], leaf.sharding)
                else:
                    if leaf.sharding not in put:
                        put[leaf.sharding] = jax.jit(
                            lambda t, i, r: t.at[i].set(r),
                            donate_argnums=0, out_shardings=leaf.sharding)
                    leaf = put[leaf.sharding](leaf, ids, old[name])
                leaves.append(leaf)
            out[prefix] = jax.tree_util.tree_unflatten(treedef, leaves)
        return state._replace(**out)

    def _observe(self, trainer, state, batches):
        if self.fault != "state_unchanged":
            return super()._observe(trainer, state, batches)
        import jax

        names = self.leaves["params"] + self.leaves["optimizer"]
        ids = batches.ids[0]
        old = self._snap(state, names, ids)
        self.fault = ""  # the base runs the step as it is ...
        try:
            new = super()._observe(trainer, state, batches)
        finally:
            self.fault = "state_unchanged"
        # ... and what it read behind the step is read again, behind the
        # state put back
        new = self._restore(new, old, ids)
        rec = self.steps[-1]
        if "post" in rec:
            rec["post"] = self._snap(new, names, rec["ids"])
        if self.final is not None:
            self.final = [self._snap(new, self.leaves["params"], s["ids"])
                          for s in self.steps]
            jax.block_until_ready(new)
        return new


def run(*, cell, seed, seconds, trace, rehearse, control, fault, rate,
        via_checkpoint, work, t0) -> dict:
    if rate or via_checkpoint:
        raise SystemExit("--rate and --via-checkpoint are for serve cells")
    config = cell["config"]
    if rehearse:
        rehearse_on_the_mesh(cell["cell"]["chips"])
    cfg_path, keys, inputs = base.make_inputs(work, config, seed, rehearse,
                                              control)
    data_shards, model_shards = keys["mesh_data"], keys["mesh_model"]
    inputs_s = time.time() - t0
    tracer = harness.TraceWindow(work, trace)
    mix = cell["traffic"]
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    ref = harness.load_by_path("reference", config["reference"])
    obs = MeshObserver(seconds, tracer, fault, t0, mix.get("warm") or {},
                       ref.program_leaves(keys), keys["vocabulary_size"])
    final = base.drive_job(cfg_path, keys["metrics_file"], obs, tracer)
    reduced = collectives = None
    if trace:  # the planes once, for both reducers
        planes = xplane.load(xplane.find_xplane(tracer.dir))
        reduced = xplane.reduce(planes)
        collectives = xplane_collectives.reduce(planes)
    harness.free_device()
    checks = compare.Checks()
    t_check = time.time()
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
    # a chip's counts, the mean over the chips and the three checked steps
    per_step = [roofline_mesh.shard_counts(
        s["ids"], keys["vocabulary_size"], data_shards, model_shards)
        for s in obs.steps]
    counts = {k: float(np.mean([c[k] for c in per_step]))
              for k in (per_step[0] if per_step else ())}
    needed = roofline_mesh.train_step_needed(
        obs.batch, keys["max_features"], keys["factor_num"], counts,
        data_shards, model_shards) if counts else {"bytes": 0, "flops": 0}
    import jax

    counters = {
        "window_s": window_s,
        "dispatches": obs.win_dispatches,
        "examples": examples,
        "wait_input_s": wait1 - wait0,
        "dispatch_s": disp1 - disp0,
        "dispatch_count": n1 - n0,
        "step_needed_bytes": needed["bytes"],
        "step_needed_flops": needed["flops"],
        "step_program_prefix": "jit_scan_health_step",
        "device_kind": jax.devices()[0].device_kind,
    }
    step = (reduced or {}).get("programs", {})
    runs = sum(v["runs"] for k, v in step.items()
               if k.startswith(counters["step_program_prefix"]))
    if collectives and runs and counts:
        counters["collective_s_per_step"] = collectives["seconds"] / runs
        counters["exchange_needed_bytes"] = (
            roofline_mesh.exchange_needed_bytes(counts, keys["factor_num"]))
    return {
        "attempted": obs.win_dispatches,
        "failed": 0,
        "e2e": {"train_ex_per_s": examples / window_s,
                "setup_s": obs.setup_s},
        "memory_peak_bytes": obs.memory_peak,
        "trace": reduced,
        "checks": checks,
        "counters": counters,
        "info": {
            "window_s": window_s, "dispatches": obs.win_dispatches,
            "batch_size": obs.batch,
            "mesh": obs.mesh_shape,
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
            "unique_rows_per_step": (
                detail.get("prog", {}).get("unique_per_step") or [0]),
            "chip_counts": counts,
            # the program's gauges at the window's close
            "gauges": {n: gauges.get("train." + n) for n in (
                "exchange_mode", "exchange_fill", "apply_stream",
                "row_floats")},
            "collectives": collectives,
            "timers": {n: dict(zip(("total_s", "count"),
                                   base._timer(obs.tel1, n)))
                       for n in ("ingest.parse", "ingest.sketch",
                                 "train.exchange")},
            "detail": detail,
        },
    }
