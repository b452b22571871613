"""End-to-end: train on planted-structure data to a logloss threshold,
checkpoint/warm-start, predict (SURVEY.md §4 "do better" items 3-4)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig, load_config
from fast_tffm_tpu.train.loop import Trainer, predict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sample_data(tmp_path_factory):
    out = tmp_path_factory.mktemp("sample_data")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "gen_sample_data.py"),
         "--out", str(out), "--train", "4000", "--valid", "500",
         "--vocab", "300", "--n_feat", "8"],
        check=True,
    )
    return out


def _cfg(sample_data, tmp_path, **kw):
    defaults = dict(
        vocabulary_size=300,
        factor_num=4,
        model_file=str(tmp_path / "model"),
        train_files=[str(sample_data / "train.libsvm")],
        validation_files=[str(sample_data / "valid.libsvm")],
        predict_files=[str(sample_data / "valid.libsvm")],
        score_path=str(tmp_path / "scores.txt"),
        epoch_num=10,
        batch_size=256,
        max_features=8,
        learning_rate=1.0,
        adagrad_initial_accumulator=0.01,
        init_value_range=0.05,
        log_steps=0,
        thread_num=2,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


@pytest.mark.slow
def test_train_reduces_logloss_and_checkpoints(sample_data, tmp_path):
    cfg = _cfg(sample_data, tmp_path)
    trainer = Trainer(cfg)
    result = trainer.train()
    # Planted FM structure (Bayes logloss ~0.41): must decisively beat the
    # trivial 0.693 and reach decent AUC.
    assert result["validation"]["logloss"] < 0.55
    assert result["validation"]["auc"] > 0.72
    assert os.path.isdir(os.path.join(cfg.model_file, "params"))

    # Warm start must resume from the checkpoint, not from scratch.
    trainer2 = Trainer(cfg)
    assert trainer2._restored_step == result["train"]["steps"]
    ev = trainer2.evaluate(cfg.validation_files)
    np.testing.assert_allclose(
        ev["logloss"], result["validation"]["logloss"], rtol=1e-5
    )


@pytest.mark.slow
def test_flat_interaction_trains_multi_device(sample_data, tmp_path):
    """interaction=flat is plain XLA and must train under the 8-virtual-
    device GSPMD mesh (the Pallas path needs shard_map there); same
    convergence bar as the default path."""
    cfg = _cfg(sample_data, tmp_path, interaction="flat")
    result = Trainer(cfg).train()
    assert result["validation"]["logloss"] < 0.55
    assert result["validation"]["auc"] > 0.72


@pytest.mark.slow
def test_sorted_data_converges_with_line_shuffle(sample_data, tmp_path):
    """Convergence on a LABEL-SORTED file (the norm for CTR logs): fast
    ingest's line-level shuffle must recover most of the loss an
    unshuffled pass gives up — group-granularity shuffling (batches of
    contiguous lines reordered) cannot mix labels within batches and
    trained visibly worse on sorted data (VERDICT r3 missing #2)."""
    src = sample_data / "train.libsvm"
    lines = open(src).read().splitlines()
    lines.sort(key=lambda ln: ln.split(" ", 1)[0])  # all 0s then all 1s
    sorted_path = tmp_path / "sorted.libsvm"
    sorted_path.write_text("\n".join(lines) + "\n")

    results = {}
    for shuffle in (True, False):
        cfg = _cfg(
            sample_data, tmp_path,
            train_files=[str(sorted_path)],
            model_file=str(tmp_path / f"model_{shuffle}"),
            epoch_num=3, shuffle_buffer=2000,
        )
        assert cfg.fast_ingest
        trainer = Trainer(cfg)
        if not shuffle:
            # Force the unshuffled stream through the same trainer path.
            import unittest.mock as mock

            from fast_tffm_tpu.data.pipeline import BatchPipeline as BP

            orig_init = BP.__init__

            def no_shuffle_init(self, files, cfg_, **kw):
                kw["shuffle"] = False
                orig_init(self, files, cfg_, **kw)

            with mock.patch.object(BP, "__init__", no_shuffle_init):
                results[shuffle] = trainer.train()
        else:
            results[shuffle] = trainer.train()
    # Shuffled training on sorted data must clearly beat unshuffled.
    assert (
        results[True]["validation"]["logloss"]
        < results[False]["validation"]["logloss"] - 0.01
    )
    assert results[True]["validation"]["auc"] > 0.72


@pytest.mark.slow
def test_predict_writes_scores(sample_data, tmp_path):
    cfg = _cfg(sample_data, tmp_path, epoch_num=1)
    Trainer(cfg).train()
    n = predict(cfg)
    assert n == 500
    scores = np.loadtxt(cfg.score_path)
    assert scores.shape == (500,)
    assert np.all((scores >= 0) & (scores <= 1))  # sigmoid probabilities
    # Predictions must correlate with labels.
    labels = np.array(
        [float(line.split()[0])
         for line in open(sample_data / "valid.libsvm")]
    )
    assert np.mean(scores[labels == 1]) > np.mean(scores[labels == 0])


@pytest.mark.slow
def test_ftrl_optimizer_trains(sample_data, tmp_path):
    cfg = _cfg(sample_data, tmp_path, optimizer="ftrl", epoch_num=5,
               ftrl_l1=0.001, ftrl_l2=0.001)
    result = Trainer(cfg).train()
    assert result["validation"]["logloss"] < 0.65


@pytest.mark.slow
def test_warm_start_across_optimizers(sample_data, tmp_path):
    """Adagrad-vs-FTRL sweep warm start (BASELINE config 3)."""
    cfg = _cfg(sample_data, tmp_path, epoch_num=1)
    Trainer(cfg).train()
    cfg2 = _cfg(sample_data, tmp_path, optimizer="ftrl", epoch_num=1)
    trainer2 = Trainer(cfg2)  # must not crash on incompatible opt state
    assert trainer2._restored_step > 0


@pytest.mark.slow
def test_cli_train_and_predict(sample_data, tmp_path):
    cfg_path = tmp_path / "sample.cfg"
    cfg_path.write_text(f"""
[General]
vocabulary_size = 300
factor_num = 4
model_file = {tmp_path}/model_cli

[Train]
train_files = {sample_data}/train.libsvm
validation_files = {sample_data}/valid.libsvm
epoch_num = 1
batch_size = 256
learning_rate = 0.1
log_steps = 0

[Predict]
predict_files = {sample_data}/valid.libsvm
score_path = {tmp_path}/scores_cli.txt

[Tpu]
max_features = 8
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "train",
         str(cfg_path)],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "validation logloss" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "run_tffm.py"), "predict",
         str(cfg_path)],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.exists(tmp_path / "scores_cli.txt")


@pytest.mark.slow
def test_kitchen_sink_ffm_bf16_weights_resume_predict(tmp_path, rng):
    """Every subsystem at once: field-aware FM + bf16 compute + weight
    files (line ingest path) + periodic validation/save + metrics JSONL +
    mid-epoch resume + predict.  Interaction bugs between features hide
    from single-feature tests."""
    import json

    n, p_num = 512, 3
    train = tmp_path / "train.libsvm"
    with open(train, "w") as f:
        for i in range(n):
            toks = " ".join(
                f"{rng.integers(0, p_num)}:{rng.integers(0, 200)}:"
                f"{rng.uniform(0.1, 1):.4f}"
                for _ in range(6)
            )
            f.write(f"{i % 2} {toks}\n")
    wf = tmp_path / "w.txt"
    wf.write_text("1.5\n" * n)

    cfg = FmConfig(
        vocabulary_size=256, factor_num=4, field_num=p_num, max_features=8,
        batch_size=64, epoch_num=2, learning_rate=0.1,
        compute_dtype="bfloat16",
        train_files=[str(train)], weight_files=[str(wf)],
        validation_files=[str(train)], validation_steps=5,
        predict_files=[str(train)], score_path=str(tmp_path / "scores.txt"),
        model_file=str(tmp_path / "model"),
        metrics_file=str(tmp_path / "metrics.jsonl"),
        save_steps=6, log_steps=4, thread_num=2, seed=1,
    )
    r1 = Trainer(cfg).train()
    assert r1["train"]["steps"] == 16  # 8 batches x 2 epochs
    assert r1["train"]["examples"] == 1024.0  # unweighted count
    assert abs(r1["train"]["weight_sum"] - 1024 * 1.5) < 1e-3
    assert np.isfinite(r1["validation"]["logloss"])
    recs = [json.loads(line) for line in open(cfg.metrics_file)]
    assert any("validation_loss" in r for r in recs)

    # Simulate an interruption at epoch 1, batch 3; resume finishes the
    # remaining 5 batches of that epoch (+ nothing else).
    from conftest import set_data_state

    set_data_state(cfg.model_file, epoch=1, batches_done=3)
    r2 = Trainer(cfg).train()
    assert r2["train"]["steps"] == 5

    n_scores = predict(cfg)
    assert n_scores == n
    scores = [float(s) for s in open(cfg.score_path)]
    assert all(0.0 <= s <= 1.0 for s in scores)


def test_metrics_file_and_profiler(tmp_path, rng):
    """Observability: metrics JSONL stream + jax.profiler trace dir."""
    import json

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.train.loop import Trainer

    data = tmp_path / "train.libsvm"
    with open(data, "w") as f:
        for i in range(256):
            f.write(f"{i % 2} {rng.integers(0, 64)}:1 {rng.integers(0, 64)}:0.5\n")
    cfg = FmConfig(
        vocabulary_size=64, factor_num=4, max_features=4, batch_size=32,
        train_files=[str(data)], epoch_num=2, log_steps=4,
        model_file=str(tmp_path / "model"),
        metrics_file=str(tmp_path / "metrics.jsonl"),
        profile_dir=str(tmp_path / "trace"),
        profile_start_step=2, profile_steps=2,
    )
    Trainer(cfg).train()
    lines = open(tmp_path / "metrics.jsonl").read().splitlines()
    assert lines, "metrics stream empty"
    recs = [json.loads(line) for line in lines]
    # Self-describing stream: header first, exact final report last.
    assert recs[0]["record"] == "run_header"
    assert recs[-1]["record"] == "final"
    trains = [r for r in recs if r["record"] == "train"]
    assert trains, "no train interval records"
    rec = trains[-1]
    assert {"step", "examples", "loss", "auc", "examples_per_sec",
            "elapsed"} <= set(rec)
    assert rec["examples"] == 512
    assert any(os.scandir(tmp_path / "trace")), "no profiler trace written"
