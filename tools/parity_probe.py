"""Mixed-mesh parity probe: localize WHERE sharded training diverges.

The `[4-2]` mesh-parity red (tests/test_sharding.py::
test_sharded_step_matches_single_device[4-2]) says the (data=4,
model=2) mesh drifts from the single-device reference — but a failing
end-state assert doesn't say WHEN the drift starts or WHICH model
shard carries it.  This probe is the observability aid: it runs the
same config through both meshes for N dispatches on identical batch
streams and dumps one JSONL record per (mesh, dispatch) —

  - ``update_norm``: L2 of the dispatch's table delta (proportional to
    the gradient under the SGD-family updates, and the per-dispatch
    divergence signal);
  - ``param_hash``: sha256 of the full table bytes (bitwise identity
    check), plus per-model-shard row-block hashes so a diff names the
    shard;
  - ``loss_sum``: the running metric the parity test also checks —

then reports the FIRST divergent dispatch (earliest where the probe
mesh's table differs from the reference beyond --atol/--rtol), the
max |delta|, the row it lives at, and which model shard owns that row.

Fixing the red stays the sharding direction's job (ROADMAP direction
1); this tool only attributes it.

Usage:
  python tools/parity_probe.py [--mesh-data 4] [--mesh-model 2]
      [--dispatches 8] [--out parity_probe.jsonl]
      [--atol 1e-6] [--rtol 1e-5]

``--fleet-gate`` is the second mode (ISSUE 19): a cheap 2-rank CPU
(gloo) gate.  Two real OS processes join a jax.distributed cluster,
build the canonical fleet mesh (data=1, model=2 — one model column per
rank), and run init + N training dispatches on the same batch stream a
single-process (1x2) reference runs locally.  Each rank sha256-hashes
its ADDRESSABLE table block after init and after every dispatch; the
parent compares rank r's hash against the reference's model-shard-r
block hash.  Bitwise equality is the contract (the `[4-2]` fix made
sharded init layout-independent), so the gate catches both init drift
and cross-process step drift in ~3 dispatches.

Exit code: 0 when the meshes agree over every dispatch, 3 when a
divergent dispatch was found (so CI can notice the red moving), 1 on
setup errors.
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# The 8-virtual-CPU-device pin must land before jax initializes — the
# same contract as tests/conftest.py.
from fast_tffm_tpu.platform import pin_cpu  # noqa: E402

pin_cpu(8)

import numpy as np  # noqa: E402

import jax  # noqa: E402

from fast_tffm_tpu.config import FmConfig  # noqa: E402
from fast_tffm_tpu.data.libsvm import Batch  # noqa: E402
from fast_tffm_tpu.parallel import mesh as mesh_lib  # noqa: E402
from fast_tffm_tpu.train.loop import Trainer  # noqa: E402


def _cfg(model_dir: str, **kw) -> FmConfig:
    # The exact test_sharding.py parity config.
    defaults = dict(
        vocabulary_size=256, factor_num=4, max_features=8,
        batch_size=64, model_file=os.path.join(model_dir, "model"),
        log_steps=0,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _batch(rng, cfg: FmConfig) -> Batch:
    n, f = cfg.batch_size, cfg.max_features
    return Batch(
        labels=rng.integers(0, 2, size=(n,)).astype(np.float32),
        ids=rng.integers(
            0, cfg.vocabulary_size, size=(n, f)
        ).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, size=(n, f)).astype(np.float32),
        fields=np.zeros((n, f), np.int32),
        weights=np.ones((n,), np.float32),
    )


def _table(trainer: Trainer) -> np.ndarray:
    return np.asarray(trainer.state.params.table)


def _shard_hashes(table: np.ndarray, model_shards: int) -> list:
    """Per-model-shard row-block sha256 prefixes (the model axis
    shards table rows into contiguous blocks)."""
    rows = table.shape[0]
    per = max(1, rows // model_shards)
    return [
        hashlib.sha256(
            np.ascontiguousarray(table[i * per:(i + 1) * per]).tobytes()
        ).hexdigest()[:16]
        for i in range(model_shards)
    ]


def _record(tag: str, mesh_shape: str, dispatch: int,
            table: np.ndarray, prev: np.ndarray, loss_sum: float,
            model_shards: int) -> dict:
    return {
        "record": "parity_probe",
        "mesh": mesh_shape,
        "tag": tag,
        "dispatch": dispatch,
        "update_norm": round(
            float(np.linalg.norm(table - prev)), 10
        ),
        "param_hash": hashlib.sha256(
            np.ascontiguousarray(table).tobytes()
        ).hexdigest()[:16],
        "shard_hashes": _shard_hashes(table, model_shards),
        "loss_sum": round(loss_sum, 10),
    }


# The 2-rank gloo worker: joins the cluster, builds the canonical fleet
# mesh (data=1, model=2), trains N dispatches on the seeded batch
# stream, and prints one FLEETHASH line per (rank, dispatch) — the
# sha256 of this rank's ADDRESSABLE table block.  argv: coordinator,
# rank, seed, dispatches.
_FLEET_WORKER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
# CPU cross-process collectives need the gloo transport.
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
assert jax.process_count() == 2 and jax.device_count() == 2

import hashlib
import numpy as np
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.train.loop import Trainer

rank, seed, n = int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
cfg = FmConfig(
    vocabulary_size=256, factor_num=4, max_features=8, batch_size=64,
    mesh_data=1, mesh_model=2,
    model_file="/tmp/fftpu_fleet_gate_" + sys.argv[2], log_steps=0,
)
t = Trainer(cfg)
rng = np.random.default_rng(seed)


def h():
    parts = [np.ascontiguousarray(np.asarray(s.data))
             for s in t.state.params.table.addressable_shards]
    return hashlib.sha256(
        b"".join(p.tobytes() for p in parts)
    ).hexdigest()[:16]


print("FLEETHASH", rank, -1, h(), flush=True)
for i in range(n):
    b = Batch(
        labels=rng.integers(0, 2, size=(64,)).astype(np.float32),
        ids=rng.integers(0, 256, size=(64, 8)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, size=(64, 8)).astype(np.float32),
        fields=np.zeros((64, 8), np.int32),
        weights=np.ones((64,), np.float32),
    )
    t.state = t._train_step(t.state, t._put(b))
    print("FLEETHASH", rank, i, h(), flush=True)
"""


def _fleet_gate(args) -> int:
    """Init+N-step hash gate: 2 gloo ranks vs the 1-process (1x2)
    reference, compared bitwise per model shard per dispatch."""
    import socket
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n = args.dispatches
    scratch = args.workdir or tempfile.mkdtemp(prefix="fleet_gate_")
    os.makedirs(scratch, exist_ok=True)

    # Reference: the SAME logical mesh (1 data x 2 model) on one
    # process, same seeded batch stream.
    cfg = _cfg(os.path.join(scratch, "ref"), mesh_data=1, mesh_model=2)
    t_ref = Trainer(
        cfg, mesh=mesh_lib.make_mesh(cfg, jax.devices()[:2])
    )
    rng = np.random.default_rng(args.seed)
    ref_hashes = {-1: _shard_hashes(_table(t_ref), 2)}
    for i in range(n):
        b = _batch(rng, cfg)
        t_ref.state = t_ref._train_step(t_ref.state, t_ref._put(b))
        ref_hashes[i] = _shard_hashes(_table(t_ref), 2)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    script = os.path.join(scratch, "fleet_worker.py")
    with open(script, "w") as f:
        f.write(_FLEET_WORKER)
    print(f"fleet gate: 2 gloo ranks (1 device each) vs 1x2 "
          f"reference, init + {n} dispatches")
    procs = [
        subprocess.Popen(
            [sys.executable, script, coordinator, str(r),
             str(args.seed), str(n)],
            env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for r in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            if p.returncode != 0:
                print(f"fleet worker failed (rc={p.returncode}):\n"
                      f"{err[-3000:]}", file=sys.stderr)
                return 1
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()

    # rank_hashes[r][d] = hash of rank r's table block after dispatch d.
    rank_hashes = {0: {}, 1: {}}
    for line in (ln for o in outs for ln in o.splitlines()):
        if line.startswith("FLEETHASH "):
            _, r, d, hx = line.split()
            rank_hashes[int(r)][int(d)] = hx
    first_divergent = None
    records = []
    for d in [-1] + list(range(n)):
        match = [
            rank_hashes[r].get(d) == ref_hashes[d][r] for r in range(2)
        ]
        records.append({
            "record": "fleet_gate",
            "dispatch": d,
            "rank_hashes": [rank_hashes[r].get(d) for r in range(2)],
            "ref_hashes": ref_hashes[d],
            "match": match,
        })
        tag = "init" if d == -1 else f"dispatch {d}"
        ok = all(match)
        if not ok and first_divergent is None:
            first_divergent = d
        print(f"  {tag}: ranks "
              f"{'== reference' if ok else '!= reference ' + str(match)}")
    with open(args.out, "w") as out:
        for rec in records:
            out.write(json.dumps(rec) + "\n")
        out.write(json.dumps({
            "record": "fleet_gate_summary",
            "dispatches": n,
            "first_divergent_dispatch": first_divergent,
            "agree": first_divergent is None,
        }) + "\n")
    if first_divergent is None:
        print(f"\nfleet gate: 2-rank table blocks bitwise-match the "
              f"single-process reference over init + {n} dispatches")
        return 0
    where = "init" if first_divergent == -1 else \
        f"dispatch {first_divergent}"
    print(f"\nfleet gate: DIVERGED at {where} — per-dispatch records "
          f"in {args.out}")
    return 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="localize the first divergent dispatch between a "
                    "sharded mesh and the single-device reference"
    )
    ap.add_argument("--mesh-data", type=int, default=4)
    ap.add_argument("--mesh-model", type=int, default=2)
    ap.add_argument("--dispatches", type=int, default=8)
    ap.add_argument("--atol", type=float, default=1e-6)
    ap.add_argument("--rtol", type=float, default=1e-5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet-gate", action="store_true",
                    help="2-rank gloo init+N-step hash gate against "
                         "the single-process (1x2) reference")
    ap.add_argument("--out", default="parity_probe.jsonl",
                    help="per-dispatch JSONL dump (default "
                         "parity_probe.jsonl)")
    ap.add_argument("--workdir", default=None,
                    help="model_file scratch dir (default: a tempdir)")
    args = ap.parse_args(argv)

    if args.fleet_gate:
        return _fleet_gate(args)

    d, m = args.mesh_data, args.mesh_model
    if d * m > len(jax.devices()):
        print(f"mesh {d}x{m} needs {d * m} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    if args.workdir is None:
        import tempfile
        scratch = tempfile.mkdtemp(prefix="parity_probe_")
    else:
        scratch = args.workdir
        os.makedirs(scratch, exist_ok=True)

    rng = np.random.default_rng(args.seed)
    cfg_ref = _cfg(os.path.join(scratch, "ref"), mesh_data=1,
                   mesh_model=1)
    cfg_probe = _cfg(os.path.join(scratch, "probe"), mesh_data=d,
                     mesh_model=m)
    batches = [_batch(rng, cfg_ref) for _ in range(args.dispatches)]

    t_ref = Trainer(
        cfg_ref, mesh=mesh_lib.make_mesh(cfg_ref, jax.devices()[:1])
    )
    t_probe = Trainer(cfg_probe)
    mesh_shape = f"{d}x{m}"
    print(f"parity probe: {mesh_shape} vs 1x1 reference, "
          f"{args.dispatches} dispatches, batch {cfg_ref.batch_size}, "
          f"vocab {cfg_ref.vocabulary_size} (dump -> {args.out})")

    first_divergent = None
    worst = {"max_abs_diff": 0.0}
    rows_per_shard = max(1, cfg_ref.vocabulary_size // m)
    prev_ref, prev_probe = _table(t_ref), _table(t_probe)
    # Dispatch "-1": the INIT states.  A diff here predates any step —
    # the divergence is in sharded initialization, not the step math,
    # and every later dispatch only inherits it.
    init_diff = np.abs(prev_probe - prev_ref)
    init_divergent = bool(
        (init_diff > args.atol + args.rtol * np.abs(prev_ref)).any()
    )
    init_row = int(
        np.unravel_index(init_diff.argmax(), init_diff.shape)[0]
    )
    if init_divergent:
        print(f"  init: tables ALREADY differ (max|d|="
              f"{float(init_diff.max()):.3e} at row {init_row}, "
              f"model shard {min(m - 1, init_row // rows_per_shard)})"
              f" — divergence predates the first step")
    with open(args.out, "w") as out:
        out.write(json.dumps({
            "record": "parity_init",
            "divergent": init_divergent,
            "max_abs_diff": round(float(init_diff.max()), 10),
            "argmax_row": init_row,
            "argmax_model_shard": min(
                m - 1, init_row // rows_per_shard
            ),
        }) + "\n")
        for i, b in enumerate(batches):
            t_ref.state = t_ref._train_step(
                t_ref.state, t_ref._put(b)
            )
            t_probe.state = t_probe._train_step(
                t_probe.state, t_probe._put(b)
            )
            tab_ref, tab_probe = _table(t_ref), _table(t_probe)
            rec_ref = _record(
                "reference", "1x1", i, tab_ref, prev_ref,
                float(t_ref.state.metrics.loss_sum), m,
            )
            rec_probe = _record(
                "probe", mesh_shape, i, tab_probe, prev_probe,
                float(t_probe.state.metrics.loss_sum), m,
            )
            prev_ref, prev_probe = tab_ref, tab_probe
            diff = np.abs(tab_probe - tab_ref)
            tol = args.atol + args.rtol * np.abs(tab_ref)
            divergent = bool((diff > tol).any())
            row = int(np.unravel_index(diff.argmax(), diff.shape)[0])
            cmp = {
                "record": "parity_diff",
                "dispatch": i,
                "divergent": divergent,
                "max_abs_diff": round(float(diff.max()), 10),
                "argmax_row": row,
                "argmax_model_shard": min(m - 1, row // rows_per_shard),
                "update_norm_delta": round(
                    abs(rec_probe["update_norm"]
                        - rec_ref["update_norm"]), 10
                ),
                "loss_sum_delta": round(
                    abs(rec_probe["loss_sum"] - rec_ref["loss_sum"]),
                    10,
                ),
                "hash_match": (
                    rec_probe["param_hash"] == rec_ref["param_hash"]
                ),
                "shard_hash_match": [
                    a == b for a, b in zip(
                        rec_ref["shard_hashes"],
                        rec_probe["shard_hashes"],
                    )
                ],
            }
            for rec in (rec_ref, rec_probe, cmp):
                out.write(json.dumps(rec) + "\n")
            marker = ""
            if divergent and first_divergent is None:
                first_divergent = i
                worst = cmp
                marker = "  <-- FIRST DIVERGENT DISPATCH"
            elif divergent:
                marker = "  (divergent)"
                if cmp["max_abs_diff"] > worst.get("max_abs_diff", 0):
                    worst = cmp
            print(f"  dispatch {i}: max|d|="
                  f"{cmp['max_abs_diff']:.3e} "
                  f"update_norm ref={rec_ref['update_norm']:.6f} "
                  f"probe={rec_probe['update_norm']:.6f} "
                  f"hash={'=' if cmp['hash_match'] else '!'}"
                  f"{marker}")
        summary = {
            "record": "parity_summary",
            "mesh": mesh_shape,
            "dispatches": args.dispatches,
            "init_divergent": init_divergent,
            "first_divergent_dispatch": first_divergent,
            "max_abs_diff": worst.get("max_abs_diff", 0.0),
            "argmax_row": worst.get("argmax_row"),
            "argmax_model_shard": worst.get("argmax_model_shard"),
        }
        out.write(json.dumps(summary) + "\n")
    if init_divergent:
        print(f"\ndivergence PREDATES dispatch 0: the {mesh_shape} "
              f"mesh initializes a different table than the 1x1 "
              f"reference (first check sharded init, not the step "
              f"math) — per-dispatch records in {args.out}")
        return 3
    if first_divergent is None:
        print(f"\nno divergence over {args.dispatches} dispatches "
              f"(atol {args.atol:g}, rtol {args.rtol:g})")
        return 0
    print(f"\nFIRST divergent dispatch: {first_divergent} "
          f"(max|d| {worst['max_abs_diff']:.3e} at row "
          f"{worst['argmax_row']}, model shard "
          f"{worst['argmax_model_shard']}) — per-dispatch records in "
          f"{args.out}")
    return 3


if __name__ == "__main__":
    sys.exit(main())
