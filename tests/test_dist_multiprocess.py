"""Multi-process distributed smoke test on localhost (SURVEY.md §4).

The reference exercised multi-node by launching ps+worker processes on
loopback. The analogue here: two OS processes join a jax.distributed
cluster (CPU backend, 2 virtual devices each), build the global (data,
model) mesh, and run real training steps with the table row-sharded
ACROSS PROCESS BOUNDARIES. Asserts both processes agree on the result.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
# CPU cross-process collectives need the gloo transport; without it
# every multi-process computation fails with "Multiprocess
# computations aren't implemented on the CPU backend".
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
assert jax.device_count() == 4, jax.devices()
assert jax.process_count() == 2

import numpy as np
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.train.loop import Trainer

cfg = FmConfig(
    vocabulary_size=256, factor_num=4, max_features=8, batch_size=32,
    mesh_data=2, mesh_model=2, model_file="/tmp/fftpu_dist_" + sys.argv[2],
    log_steps=0,
)
trainer = Trainer(cfg)
rng = np.random.default_rng(0)  # same seed -> same global batch everywhere
for _ in range(3):
    batch = Batch(
        labels=rng.integers(0, 2, size=(32,)).astype(np.float32),
        ids=rng.integers(0, 256, size=(32, 8)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, size=(32, 8)).astype(np.float32),
        fields=np.zeros((32, 8), np.int32),
        weights=np.ones((32,), np.float32),
    )
    trainer.state = trainer._train_step(trainer.state, trainer._put(batch))

# Print a fingerprint of the local table shards + global metrics.
table = trainer.state.params.table
local = np.concatenate(
    [np.asarray(s.data).ravel() for s in table.addressable_shards]
)
print("FINGERPRINT", float(np.abs(local).sum()), float(trainer.state.metrics.loss_sum))

# Second phase, same process pair (amortizes cluster startup): the
# shardmap step with the batch-proportional entries exchange — its
# all-gather of touched-entry streams crosses REAL process boundaries
# here, not just a virtual mesh.
cfg2 = FmConfig(
    vocabulary_size=2048, factor_num=8, max_features=8, batch_size=32,
    mesh_data=2, mesh_model=2, lookup="shardmap",
    sparse_exchange="entries",
    model_file="/tmp/fftpu_dist_e_" + sys.argv[2], log_steps=0,
)
trainer2 = Trainer(cfg2)
for _ in range(2):
    batch = Batch(
        labels=rng.integers(0, 2, size=(32,)).astype(np.float32),
        ids=rng.integers(0, 2048, size=(32, 8)).astype(np.int32),
        vals=rng.uniform(0.1, 1.0, size=(32, 8)).astype(np.float32),
        fields=np.zeros((32, 8), np.int32),
        weights=np.ones((32,), np.float32),
    )
    trainer2.state = trainer2._train_step(trainer2.state, trainer2._put(batch))
table2 = trainer2.state.params.table
local2 = np.concatenate(
    [np.asarray(s.data).ravel() for s in table2.addressable_shards]
)
print("FINGERPRINT2", float(np.abs(local2).sum()),
      float(trainer2.state.metrics.loss_sum))
"""


@pytest.mark.slow
def test_two_process_distributed_training(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(i)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:
        for p in procs:  # reap stragglers if init hung or a worker failed
            if p.poll() is None:
                p.kill()
                p.communicate()
    fps = [l for o in outs for l in o.splitlines()
           if l.startswith("FINGERPRINT ")]
    assert len(fps) == 2
    # Same global metrics on both processes (replicated state agrees).
    m0 = float(fps[0].split()[2])
    m1 = float(fps[1].split()[2])
    np.testing.assert_allclose(m0, m1, rtol=1e-6)
    # Loss is finite and training actually ran.
    assert m0 > 0 and np.isfinite(m0)
    # Phase 2: shardmap + entries exchange across process boundaries.
    fps2 = [l for o in outs for l in o.splitlines()
            if l.startswith("FINGERPRINT2")]
    assert len(fps2) == 2
    e0 = float(fps2[0].split()[2])
    e1 = float(fps2[1].split()[2])
    np.testing.assert_allclose(e0, e1, rtol=1e-6)
    assert e0 > 0 and np.isfinite(e0)


_WORKER_FILES = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
# CPU cross-process collectives need the gloo transport; without it
# every multi-process computation fails with "Multiprocess
# computations aren't implemented on the CPU backend".
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
import jax.numpy as jnp
import numpy as np
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.train.loop import Trainer

data_dir = sys.argv[3]
cfg = FmConfig(
    vocabulary_size=256, factor_num=4, max_features=8, batch_size=64,
    mesh_data=2, mesh_model=2,
    train_files=[data_dir + "/a.libsvm", data_dir + "/b.libsvm"],
    # ONE shared checkpoint path: Orbax multi-host save is collective
    # (process 0 writes metadata, each process writes its shards) —
    # per-process paths deadlock the save barrier.
    model_file=data_dir + "/model_mp",
    epoch_num=2, log_steps=0, thread_num=1, seed=5,
)
t = Trainer(cfg)
res = t.train()
fp = float(jax.jit(lambda x: jnp.sum(jnp.abs(x)))(t.state.params.table))
print("FINGERPRINT", fp, float(t.state.metrics.loss_sum),
      res["train"]["examples"], res["train"]["steps"])
"""


def _gen_dist_files(tmp_path, n_lines=256):
    rng = np.random.default_rng(11)
    for name in ("a", "b"):
        with open(tmp_path / f"{name}.libsvm", "w") as f:
            for _ in range(n_lines):
                toks = [str(rng.integers(0, 2))]
                toks += [f"{rng.integers(0, 256)}:{rng.uniform(0.1, 1):.4f}"
                         for _ in range(6)]
                f.write(" ".join(toks) + "\n")


@pytest.mark.slow
def test_host_sharded_input_matches_single_process(tmp_path):
    """Each process parses only its strided share of the input at LOCAL
    batch size; the global batch assembles via
    make_array_from_process_local_data.  The training result must equal a
    single-process run over the SAME global batches (the union of the
    hosts' shards)."""
    _gen_dist_files(tmp_path)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    script = tmp_path / "worker_files.py"
    script.write_text(_WORKER_FILES)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(i), str(tmp_path)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    fps = [l for o in outs for l in o.splitlines()
           if l.startswith("FINGERPRINT")]
    assert len(fps) == 2
    fp0 = [float(x) for x in fps[0].split()[1:]]
    fp1 = [float(x) for x in fps[1].split()[1:]]
    np.testing.assert_allclose(fp0, fp1, rtol=1e-6)
    # Coverage: 512 lines x 2 epochs, every line trained exactly once per
    # epoch (16 local groups -> 8 complete rounds -> 8 global batches).
    assert fp0[2] == 1024.0
    assert fp0[3] == 16.0  # 8 steps x 2 epochs

    # Single-process equivalence: rebuild the SAME global batches by
    # concatenating the two shards' streams and train on a local 2x2 mesh
    # with identical seeds.
    import dataclasses

    import jax

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.libsvm import Batch
    from fast_tffm_tpu.data.pipeline import BatchPipeline
    from fast_tffm_tpu.train.loop import Trainer

    cfg = FmConfig(
        vocabulary_size=256, factor_num=4, max_features=8, batch_size=64,
        mesh_data=2, mesh_model=2,
        train_files=[str(tmp_path / "a.libsvm"), str(tmp_path / "b.libsvm")],
        model_file=str(tmp_path / "model_sp"),
        epoch_num=2, log_steps=0, thread_num=1, seed=5,
    )
    trainer = Trainer(cfg)
    pipe_cfg = dataclasses.replace(cfg, batch_size=32)
    for epoch in range(cfg.epoch_num):
        shards = [
            list(BatchPipeline(cfg.train_files, pipe_cfg, epochs=1,
                               shuffle=True, seed=cfg.seed + epoch,
                               shard=(i, 2)))
            for i in range(2)
        ]
        for b0, b1 in zip(shards[0], shards[1]):
            gb = Batch(*(np.concatenate([getattr(b0, k), getattr(b1, k)])
                         for k in ("labels", "ids", "vals", "fields",
                                   "weights")))
            trainer.state = trainer._train_step(
                trainer.state, trainer._put(gb)
            )
    import jax.numpy as jnp

    fp_sp = float(jax.jit(lambda x: jnp.sum(jnp.abs(x)))(
        trainer.state.params.table))
    np.testing.assert_allclose(fp0[0], fp_sp, rtol=1e-5)
    np.testing.assert_allclose(
        fp0[1], float(trainer.state.metrics.loss_sum), rtol=1e-5
    )
