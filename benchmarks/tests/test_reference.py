"""The plain reference against the program at toy size on the CPU: it
agrees with the trainer on both apply paths (XLA scatter, the tile
kernels in interpret mode), and a bfloat16 run fails it."""

import copy
import os
import time

import numpy as np
import pytest

from fmbench import compare, harness

train = harness.load_by_path("drivers", "train")


def _three_steps(tmp_path, apply_path: str, control: str = ""):
    cell = copy.deepcopy(harness.load_cell("criteo1tb-train-shard"))
    cell["config"]["rehearse"]["sparse_apply"] = apply_path
    work = str(tmp_path / apply_path)
    os.makedirs(work)
    out = train.run(cell=cell, seed=2**31 + 5, seconds=0.2, trace=False,
                    rehearse=True, control=control, fault="", rate=0.0,
                    via_checkpoint=False, work=work, t0=time.time())
    return out["checks"]


@pytest.mark.parametrize("apply_path", ["scatter", "tile"])
def test_reference_agrees_with_the_program(tmp_path, apply_path):
    checks = _three_steps(tmp_path, apply_path)
    assert checks.correct, checks.as_dict()
    names = [n for n, _, _ in checks.rows]
    assert {"loss_gap", "grad_gap", "change_gap", "score_gap"} <= set(names)


def test_bfloat16_run_fails_the_reference(tmp_path):
    checks = _three_steps(tmp_path, "scatter", control="bf16")
    assert not checks.correct
    over = {n for n, v, lim in checks.rows if v > lim}
    assert "score_gap" in over


def test_worst_leaf_gap_rule():
    ref = {"a": 1.0, "b": 1e-6, "c": 2.0}
    prog = {"a": 1.1, "b": 2e-6, "c": 2.0}
    # b's own norm is tiny: its gap is measured against the median leaf
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(0.1)
    assert compare.nought_leaves({"a": 1.0, "b": 1e-6, "c": 2.0}) == {"b"}
    assert compare.worst_leaf_gap({"a": 3.0, "b": 0, "c": 2.0}, ref,
                                  skip={"a"}) == pytest.approx(1e-6)


def test_reference_serving_math_by_hand():
    import jax.numpy as jnp

    from reference import fm as ref

    table = jnp.asarray(np.array([[0.1, 1.0, 2.0], [0.2, -1.0, 0.5],
                                  [9.0, 9.0, 9.0]], np.float32))
    ids = jnp.asarray([[0, 1, 2]], jnp.int32)
    vals = jnp.asarray([[1.0, 2.0, 0.0]], jnp.float32)  # third is padding
    w0 = jnp.asarray(-0.5, jnp.float32)
    # linear 0.1 + 0.4; pair term <v0, v1> x0 x1 = (-1 + 1) * 2 = 0
    want = 1 / (1 + np.exp(-(-0.5 + 0.5 + 0.0)))
    got = float(ref.probabilities(w0, table, ids, vals)[0])
    assert got == pytest.approx(want, abs=1e-6)
