"""The program's FTRL-Proximal train step against the benchmark's plain
reference (``benchmarks/reference/ftrl.py``, loaded by path: the one
copy that also decides the ``criteo1tb-ftrl-train-shard`` cell's
``correct``), at small size on the CPU.

Three steps of ``train.sparse.sparse_step`` with ``optimizer = ftrl`` on
the one-device unique-row apply are followed by the reference from the
same seeded weights, through both writers -- the stream writer (forced:
on the CPU its kernel runs interpreted) and the scatter loop -- at
payloads of one, two and three 128-lane tiles (k = 8, the cell's row;
k = 63; k = 127).  Every batch repeats ids (a Zipf head) and pads a
tenth of the slots.  Compared: the scores, each batch's loss, the first
gradient as FTRL got it (worked back from the program's own state:
``g = z' - z + sigma w``), all six leaves' change over the three steps,
and which touched weights L1 holds at exactly 0.  Rows no step touched
keep their bits in all three tables.  Two controls must fail: the
interaction's operands rounded to bfloat16 (``compute_dtype =
bfloat16``) and the program run with ``ftrl.l1 = 0`` against the
reference's lambda1."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.train import sparse as sparse_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, BATCH, F, STEPS = 1024, 32, 8, 3
FACTORS = [8, 63, 127]  # payload 2 (1 + k) + 3 lanes: 1, 2 and 3 tiles
L1 = 0.02  # the shard cell's 1e-3 over batches of 32: zeros show

# Tolerances, each between the largest sound reading and the smallest
# control reading over both writers and the three widths (this file's
# own runs, on the CPU, where both sides are float32 and differ by the
# order of their sums; the two writers read the same to the digit):
#   scores  largest |score - reference|: sound <= 3.7e-9; bfloat16
#           >= 4.9e-5; l1 dropped >= 8.2e-4
#   loss    relative gap of a batch's mean logloss: sound <= 2.6e-10;
#           bfloat16 >= 2.3e-6; l1 dropped >= 1.1e-4
#   grad    the first gradient, largest element gap over the largest
#           element: sound <= 7.0e-7; bfloat16 >= 6.0e-4 (a dropped L1
#           moves no gradient: 7.9e-8 -- 6.9e-7)
#   change  each leaf's change over the three steps, largest element
#           gap over the leaf's largest change: sound <= 3.9e-7;
#           bfloat16 >= 1.1e-3; l1 dropped >= 0.25
#   zeros   share of the touched weights that are 0 in one and not in
#           the other: sound 0; l1 dropped >= 3.3e-4 (bfloat16 0)
TOL = {"scores": 1e-6, "loss": 1e-7, "grad": 2e-5, "change": 2e-5,
       "zeros": 1e-5}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_ftrl",
        os.path.join(REPO, "benchmarks", "reference", "ftrl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(k, **over):
    return FmConfig(**{**dict(
        vocabulary_size=VOCAB, factor_num=k, max_features=F,
        batch_size=BATCH, optimizer="ftrl", learning_rate=0.05,
        adagrad_initial_accumulator=0.1, ftrl_l1=L1, ftrl_l2=1e-3,
        ftrl_beta=1.0, factor_lambda=1e-6, bias_lambda=1e-6,
        init_value_range=0.01, sparse_update=True, sparse_apply="scatter",
    ), **over})


def _keys(cfg):
    """The cfg keys the reference reads, as a configuration file has
    them."""
    return {
        "vocabulary_size": cfg.vocabulary_size,
        "factor_num": cfg.factor_num, "optimizer": "ftrl",
        "loss_type": "logistic", "learning_rate": cfg.learning_rate,
        "adagrad.initial_accumulator": cfg.adagrad_initial_accumulator,
        "ftrl.l1": L1, "ftrl.l2": cfg.ftrl_l2, "ftrl.beta": cfg.ftrl_beta,
        "factor_lambda": cfg.factor_lambda, "bias_lambda": cfg.bias_lambda,
        "init_value_range": cfg.init_value_range, "seed": 11,
    }


def _batches(seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(STEPS):
        vals = rng.uniform(0.1, 1.0, (BATCH, F)).astype(np.float32)
        vals[rng.uniform(size=vals.shape) < 0.1] = 0.0  # padding slots
        out.append(Batch(
            labels=rng.integers(0, 2, BATCH).astype(np.float32),
            # a Zipf head: a few rows take most occurrences
            ids=(rng.zipf(1.3, (BATCH, F)) % VOCAB).astype(np.int32),
            vals=vals,
            fields=np.zeros((BATCH, F), np.int32),
            weights=rng.uniform(0.5, 1.5, BATCH).astype(np.float32),
        ))
    return out


def _logloss(scores, batch):
    s = np.asarray(scores, np.float64)
    per = np.logaddexp(0.0, s) - batch.labels * s
    return float((per * batch.weights).sum() / batch.weights.sum())


def _f64(x):
    return np.asarray(x, np.float64)


def _gap(got, want):
    return float(np.abs(_f64(got) - _f64(want)).max()
                 / max(np.abs(_f64(want)).max(), 1e-30))


def _run(ref, monkeypatch, k, writer, **over):
    """The numbers of TOL, and the untouched rows' bits before and after,
    for the program's three steps against the reference's."""
    monkeypatch.setattr(sparse_apply, "takes_stream",
                        lambda *a: writer == "stream")
    cfg = _cfg(k, **over)
    keys = _keys(cfg)
    state = ref.init_state(keys)
    first = [np.asarray(x) for x in state]
    w0, table = first[:2]
    params = fm.FmParams(w0=jnp.asarray(w0), table=jnp.asarray(table))
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    start = [np.asarray(t) for t in (params.table, opt.z.table, opt.n.table)]
    step = jax.jit(partial(sparse_lib.sparse_step, cfg, health=True))
    ref_step = ref.make_step(keys)
    out = {"scores": 0.0, "loss": 0.0}
    batches = _batches(100 + k)
    for i, batch in enumerate(batches):
        pre = (params, opt)
        params, opt, scores, aux = step(params, opt, batch)
        state, raux = ref_step(state, {
            n: jnp.asarray(getattr(batch, n))
            for n in ("ids", "vals", "labels", "weights")})
        out["scores"] = max(out["scores"], float(np.abs(
            np.asarray(scores) - np.asarray(raux["scores"])).max()))
        want = _logloss(raux["scores"], batch)
        out["loss"] = max(out["loss"],
                          abs(_logloss(scores, batch) - want) / want)
        if i == 0:
            written, merged = (int(x) for x in aux.apply_rows)
            uniq = np.unique(batch.ids)
            assert written == len(uniq) and merged == BATCH * F
            (p0, o0), sigma = pre, (
                np.sqrt(_f64(opt.n.table)) - np.sqrt(_f64(pre[1].n.table))
            ) / cfg.learning_rate
            g = (_f64(opt.z.table) - _f64(o0.z.table)
                 + sigma * _f64(p0.table))[uniq]
            out["grad"] = _gap(g, np.asarray(
                raux["grad"]["params.table"])[:len(uniq)])
    got = [params.w0, params.table, opt.z.w0, opt.z.table, opt.n.w0,
           opt.n.table]
    out["change"] = max(_gap(_f64(p) - _f64(f0), _f64(r) - _f64(f0))
                        for p, r, f0 in zip(got, state, first))
    touched = np.unique(np.concatenate([b.ids.reshape(-1) for b in batches]))
    out["zeros"] = float(np.mean(
        (np.asarray(params.table)[touched] == 0)
        != (np.asarray(state[1])[touched] == 0)))
    rest = np.setdiff1d(np.arange(VOCAB), touched)
    bits = {"before": [t[rest].view(np.uint32) for t in start],
            "after": [np.asarray(t)[rest].view(np.uint32)
                      for t in (params.table, opt.z.table, opt.n.table)]}
    return out, bits, touched


@pytest.mark.parametrize("writer", ["stream", "scatter_loop"])
@pytest.mark.parametrize("k", FACTORS)
def test_three_steps_agree_with_the_reference(ref, monkeypatch, k, writer):
    gaps, bits, touched = _run(ref, monkeypatch, k, writer)
    assert all(gaps[n] <= TOL[n] for n in TOL), gaps
    # rows no step touched keep their bits in all three tables
    assert 0 < len(touched) < VOCAB
    for before, after in zip(bits["before"], bits["after"]):
        np.testing.assert_array_equal(before, after)


@pytest.mark.parametrize("writer", ["stream", "scatter_loop"])
@pytest.mark.parametrize("k", FACTORS)
def test_bfloat16_operands_fail_the_float32_tolerance(
        ref, monkeypatch, k, writer):
    gaps, _, _ = _run(ref, monkeypatch, k, writer,
                      compute_dtype="bfloat16")
    # every number but the zeros catches the lower precision
    assert all(gaps[n] > 10 * TOL[n] for n in TOL if n != "zeros"), gaps


@pytest.mark.parametrize("writer", ["stream", "scatter_loop"])
@pytest.mark.parametrize("k", FACTORS)
def test_a_dropped_l1_fails(ref, monkeypatch, k, writer):
    gaps, _, _ = _run(ref, monkeypatch, k, writer, ftrl_l1=0.0)
    assert gaps["zeros"] > 10 * TOL["zeros"], gaps
    assert gaps["change"] > 10 * TOL["change"], gaps


def test_reference_step_is_algorithm_one_by_hand(ref):
    """One coordinate through the reference's formula, by hand: n
    grows by g^2, z by g - sigma w, and w is the closed form (0 inside
    the L1 band)."""
    lr, l1, l2, beta = 0.5, 0.1, 0.2, 1.0
    w, z, n, g = 0.3, -0.7, 0.25, 0.4
    n2 = n + g * g
    sigma = (np.sqrt(n2) - np.sqrt(n)) / lr
    z2 = z + g - sigma * w
    want = -(z2 - np.sign(z2) * l1) / ((beta + np.sqrt(n2)) / lr + l2)
    got = ref.ftrl_solve(jnp.float32(z2), jnp.float32(n2), lr=lr, l1=l1,
                         l2=l2, beta=beta)
    assert float(got) == pytest.approx(want, rel=1e-6)
    assert float(ref.ftrl_solve(jnp.float32(0.05), jnp.float32(n2), lr=lr,
                                l1=l1, l2=l2, beta=beta)) == 0.0
    # the initial z gives the seeded weights back through the closed form
    keys = _keys(_cfg(4))
    w0, table, z_w0, z_table, n_w0, n_table = ref.init_state(keys)
    back = ref.ftrl_solve(z_table, n_table, lr=0.05, l1=L1, l2=1e-3,
                          beta=1.0)
    np.testing.assert_allclose(np.asarray(back), np.asarray(table),
                               rtol=1e-6, atol=1e-9)
    assert float(n_table[0, 0]) == pytest.approx(0.1)


def test_program_leaves_and_first_gradient_by_path(ref):
    keys = _keys(_cfg(4))
    leaves = ref.program_leaves(keys)
    assert leaves["params"] == list(ref.LEAVES) and not leaves["optimizer"]
    pre = {"params.w0": np.float64(0.2), "params.table": np.array([[0.1]]),
           "opt_state.z.w0": np.float64(1.0),
           "opt_state.z.table": np.array([[-2.0]]),
           "opt_state.n.w0": np.float64(0.25),
           "opt_state.n.table": np.array([[1.0]])}
    g0, g = 0.5, -3.0
    post = dict(pre)
    for leaf, gr, w in (("w0", g0, 0.2), ("table", g, 0.1)):
        n = pre["opt_state.n." + leaf]
        n2 = n + gr * gr
        post["opt_state.n." + leaf] = n2
        post["opt_state.z." + leaf] = (pre["opt_state.z." + leaf] + gr
                                       - (np.sqrt(n2) - np.sqrt(n)) / 0.05
                                       * w)
    got = ref.first_gradient(keys, pre, post)
    assert got["params.w0"] == pytest.approx(abs(g0))
    assert got["params.table"] == pytest.approx(abs(g))
    with pytest.raises(NotImplementedError):
        ref.program_leaves({**keys, "optimizer": "adagrad"})


def test_the_sparse_and_the_dense_ftrl_init_agree(ref):
    """The program's two FTRL initialisations (the sparse path's and
    optax's) give the same z and n bit for bit, and the reference's
    the same to a float32 ulp (its z is one fused expression)."""
    from fast_tffm_tpu.train.optimizers import make_optimizer

    cfg = _cfg(4)
    keys = _keys(cfg)
    _, table, _, z_ref, _, n_ref = ref.init_state(keys)
    params = fm.FmParams(w0=jnp.zeros(()), table=jnp.asarray(table))
    sparse = sparse_lib.init_sparse_opt_state(cfg, params)
    dense = make_optimizer(dataclasses.replace(cfg, sparse_update=False)
                           ).init(params)
    for a, b in ((sparse.z, dense.z), (sparse.n, dense.n)):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))
    np.testing.assert_array_max_ulp(np.asarray(sparse.z.table),
                                    np.asarray(z_ref), maxulp=1)
    np.testing.assert_array_equal(np.asarray(sparse.n.table),
                                  np.asarray(n_ref))


def test_reference_sums_before_it_adds(ref):
    """At the cell's batch size each occurrence's g^2 is under half an
    ulp of n = 0.1, while a row's sum over its occurrences is not: the
    reference's n must take the whole sum (XLA, left alone, adds the
    occurrences into n one at a time, and n never moves)."""
    v, b, f = 64, 16384, 2
    keys = {**_keys(_cfg(2)), "vocabulary_size": v, "factor_num": 2}
    rng = np.random.default_rng(3)
    ids = rng.integers(0, v, (b, f)).astype(np.int32)
    batch = {"ids": jnp.asarray(ids),
             "vals": jnp.asarray(rng.uniform(0.1, 1, (b, f)), jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 2, b), jnp.float32),
             "weights": jnp.ones((b,), jnp.float32)}
    state = ref.init_state(keys)
    n0 = np.asarray(state[5])
    state, aux = ref.make_step(keys)(state, batch)
    g = np.asarray(aux["grad"]["params.table"], np.float64)
    # each occurrence's square is far under half an ulp of 0.1 ...
    assert np.abs(g[:v]).max() / (b * f / v) < 1e-4
    # ... and every touched row's n moved by its whole sum (the linear
    # weight's: the factors' sums stay under an ulp at this size)
    moved = np.asarray(state[5], np.float64) - n0
    touched = np.unique(ids)
    assert (moved[touched, 0] > 0).all()
    assert (moved[np.setdiff1d(np.arange(v), touched)] == 0).all()
