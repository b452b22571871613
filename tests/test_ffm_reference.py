"""The program's field-aware train step against the benchmark's plain
reference (``benchmarks/reference/ffm.py``, loaded by path: the one copy
that also decides the cell's ``correct``), at small size on the CPU.

Three steps of ``train.sparse.sparse_step`` on the one-device unique-row
apply are followed by the reference from the same seeded weights, at
payloads of one, two and three 128-lane tiles -- the last is LIBFFM's
Criteo row, 1 + 39 * 4 = 157 floats.  Every batch repeats ids (1,248
occurrences over 512 rows at the widest), draws each slot's field at
random (so fields repeat and fields are absent in an example) and pads a
tenth of the slots.  Two controls must fail the same tolerances: the
program's step with its gathered rows and its values rounded to bfloat16
first (what ``compute_dtype=bfloat16`` does on the chip; on the CPU
``platform.ffm_compute_dtype`` turns that back into float32, so the
test rounds the operands itself), and the step with every field set
to 0.
"""

from __future__ import annotations

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu import platform as pf
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import interaction, sparse_apply
from fast_tffm_tpu.train import sparse as sparse_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, BATCH, STEPS = 512, 32, 3
# (field_num, factor_num): payload 2 * D + 2 = 44 / 140 / 316 floats
WIDTHS = [(5, 4), (17, 4), (39, 4)]

# Tolerances, each between the largest sound reading and the smallest
# control reading over the three widths (this file's own runs, on the
# CPU, where both sides are float32 and differ by the order of their
# sums):
#   scores  largest |score - reference|, scores of O(1): sound <= 1.1e-6;
#           bfloat16 operands >= 4.5e-4; fields zeroed >= 3.2e-2
#   loss    relative gap of a batch's mean logloss: sound <= 6.5e-8;
#           bfloat16 >= 3.0e-5 (32 examples average part of the operand
#           rounding out); fields zeroed >= 1.8e-3
#   grad    first gradient as Adagrad got it, worked back from the
#           state's change (w moved by -lr g / sqrt(acc')): largest
#           element gap over the largest element.  sound <= 2.5e-7 (the
#           float32 ulp of a weight of 0.1 over the 1e-3 it moved);
#           bfloat16 >= 1.1e-3; fields zeroed >= 7.6e-2
#   change  parameters' change over the three steps, same norm: sound
#           <= 8.2e-7; bfloat16 >= 7.2e-4; fields zeroed >= 7.7e-2
TOL = {"scores": 2e-5, "loss": 2e-6, "grad": 2e-5, "change": 2e-5}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_ffm",
        os.path.join(REPO, "benchmarks", "reference", "ffm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(field_num, factor_num):
    return FmConfig(
        vocabulary_size=VOCAB, factor_num=factor_num, field_num=field_num,
        max_features=field_num, batch_size=BATCH, optimizer="adagrad",
        learning_rate=0.2, adagrad_initial_accumulator=1.0,
        factor_lambda=2e-5, bias_lambda=2e-5, init_value_range=0.1,
        sparse_update=True, sparse_apply="scatter",
    )


def _keys(cfg):
    """The cfg keys the reference reads, as a configuration file has
    them."""
    return {
        "vocabulary_size": cfg.vocabulary_size, "field_num": cfg.field_num,
        "factor_num": cfg.factor_num, "optimizer": "adagrad",
        "loss_type": "logistic", "learning_rate": cfg.learning_rate,
        "adagrad.initial_accumulator": cfg.adagrad_initial_accumulator,
        "factor_lambda": cfg.factor_lambda, "bias_lambda": cfg.bias_lambda,
        "init_value_range": cfg.init_value_range, "seed": 7,
    }


def _batches(cfg, seed):
    rng = np.random.default_rng(seed)
    f = cfg.max_features
    out = []
    for _ in range(STEPS):
        vals = rng.uniform(0.1, 1.0, (BATCH, f)).astype(np.float32)
        vals[rng.uniform(size=vals.shape) < 0.1] = 0.0  # padding slots
        out.append(Batch(
            labels=rng.integers(0, 2, BATCH).astype(np.float32),
            # a Zipf head: a few rows take most occurrences
            ids=(rng.zipf(1.3, (BATCH, f)) % VOCAB).astype(np.int32),
            vals=vals,
            fields=rng.integers(0, cfg.field_num, (BATCH, f)).astype(
                np.int32),
            weights=rng.uniform(0.5, 1.5, BATCH).astype(np.float32),
        ))
    return out


def _logloss(scores, batch):
    s = np.asarray(scores, np.float64)
    per = np.logaddexp(0.0, s) - batch.labels * s
    return float((per * batch.weights).sum() / batch.weights.sum())


def _gaps(ref, field_num, factor_num, *, zero_fields=False):
    """The four numbers: the program's three steps against the
    reference's, from the reference's own seeded initial state."""
    cfg = _cfg(field_num, factor_num)
    keys = _keys(cfg)
    w0, table, acc_w0, acc_table = ref.init_state(keys)
    params = fm.FmParams(w0=w0, table=jnp.array(table))
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    step = jax.jit(partial(sparse_lib.sparse_step, cfg, health=True))
    ref_step = ref.make_step(keys)
    state = (w0, table, acc_w0, acc_table)
    first = {"w0": np.float64(w0), "table": np.asarray(table, np.float64)}
    out = {"scores": 0.0, "loss": 0.0}
    for i, batch in enumerate(_batches(cfg, 100 + field_num)):
        fed = batch._replace(fields=np.zeros_like(batch.fields)) \
            if zero_fields else batch
        pre = params
        params, opt, scores, aux = step(params, opt, fed)
        state, raux = ref_step(state, {
            n: jnp.asarray(getattr(batch, n))
            for n in ("ids", "vals", "fields", "labels", "weights")})
        out["scores"] = max(out["scores"], float(np.abs(
            np.asarray(scores) - np.asarray(raux["scores"])).max()))
        want = _logloss(raux["scores"], batch)
        out["loss"] = max(out["loss"],
                          abs(_logloss(scores, batch) - want) / want)
        if i == 0:
            written, merged = (int(x) for x in aux[2])
            assert 0 < written < merged == BATCH * cfg.max_features
            uniq = np.unique(batch.ids)
            assert written == len(uniq)  # the unique-row apply ran
            moved = (np.asarray(params.table, np.float64)
                     - np.asarray(pre.table, np.float64))[uniq]
            got = -moved * np.sqrt(
                np.asarray(opt.acc.table, np.float64)[uniq]
                + ref.ADAGRAD_EPS) / cfg.learning_rate
            g_ref = np.asarray(raux["grad"]["params.table"],
                               np.float64)[:len(uniq)]
            out["grad"] = float(np.abs(got - g_ref).max()
                                / np.abs(g_ref).max())
    d_prog = np.asarray(params.table, np.float64) - first["table"]
    d_ref = np.asarray(state[1], np.float64) - first["table"]
    out["change"] = max(
        float(np.abs(d_prog - d_ref).max() / np.abs(d_ref).max()),
        abs(float(params.w0) - float(state[0]))
        / abs(float(state[0]) - first["w0"]))
    return out


@pytest.mark.parametrize("field_num,factor_num", WIDTHS)
def test_three_steps_agree_with_the_reference(ref, field_num, factor_num):
    gaps = _gaps(ref, field_num, factor_num)
    assert all(gaps[n] <= TOL[n] for n in TOL), gaps


@pytest.mark.parametrize("field_num,factor_num", WIDTHS)
def test_bfloat16_operands_fail_the_float32_tolerance(
        ref, monkeypatch, field_num, factor_num):
    op = interaction.ffm_interaction

    def rounded(rows, vals, *rest):
        def r(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return op(r(rows), r(vals), *rest)

    monkeypatch.setattr(sparse_lib.interaction, "ffm_interaction", rounded)
    gaps = _gaps(ref, field_num, factor_num)
    # every number catches the lower precision, at every width
    assert all(gaps[n] > 10 * TOL[n] for n in TOL), gaps


@pytest.mark.parametrize("field_num,factor_num", WIDTHS)
def test_fields_set_to_zero_fail_every_tolerance(ref, field_num,
                                                 factor_num):
    gaps = _gaps(ref, field_num, factor_num, zero_fields=True)
    assert all(gaps[n] > 10 * TOL[n] for n in TOL), gaps


def test_reference_is_the_double_sum_over_pairs_by_hand(ref):
    # 3 features, 2 fields, k = 2: rows = [w | v for field 0 | field 1]
    rows = jnp.asarray(np.array([[
        [0.5, 1.0, 2.0, 3.0, 4.0],
        [-1.0, 0.5, -0.5, 2.0, 1.0],
        [9.0, 9.0, 9.0, 9.0, 9.0],  # padding: value 0
    ]], np.float32))
    vals = jnp.asarray([[2.0, 0.5, 0.0]], jnp.float32)
    fields = jnp.asarray([[0, 1, 1]], jnp.int32)
    # the one live pair: <V[0, f_1 = 1], V[1, f_0 = 0]> x_0 x_1
    #   = <(3, 4), (0.5, -0.5)> * 2 * 0.5 = -0.5
    want = 0.25 + (0.5 * 2.0 - 1.0 * 0.5) - 0.5
    got = ref.pair_scores(jnp.asarray(0.25), rows, vals, fields,
                          field_num=2, factor_num=2)
    assert float(got[0]) == pytest.approx(want, abs=1e-6)
    # two features of one field still interact (LIBFFM sums over i < j)
    same = ref.pair_scores(jnp.asarray(0.0), rows, jnp.asarray(
        [[1.0, 1.0, 0.0]]), jnp.asarray([[1, 1, 0]], jnp.int32),
        field_num=2, factor_num=2)
    assert float(same[0]) == pytest.approx(
        0.5 - 1.0 + (3.0 * 2.0 + 4.0 * 1.0), abs=1e-6)


@pytest.mark.parametrize("dtype,want", [
    (jnp.float32, jax.lax.Precision.HIGHEST), (jnp.bfloat16, None)])
def test_float32_asks_the_mxu_for_float32(dtype, want):
    """On the chip a float32 dot without a stated precision is ONE
    bfloat16 pass; a CPU run cannot see that, the traced program can:
    every dot of the field-aware forward and backward carries HIGHEST
    at ``compute_dtype=float32`` and the default at bfloat16."""
    b, f, p, k = 4, 3, 3, 2

    def loss(rows, vals, fields):
        return jnp.sum(interaction.ffm_interaction(
            rows, vals, fields, k, p, dtype))

    with pf.force_compiled():  # keep bf16 operands as on the chip
        jaxpr = jax.make_jaxpr(jax.grad(loss))(
            jnp.ones((b, f, 1 + p * k)), jnp.ones((b, f)),
            jnp.zeros((b, f), jnp.int32))

    def dots(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn.params["precision"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(jaxpr.jaxpr))
    assert len(found) >= 5  # S, v_own, cross; v_own and T again backward
    for prec in found:
        if want is None:
            assert prec is None
        else:
            assert prec == (want, want)


@pytest.mark.parametrize("d", [9, 69, 157])
def test_k1_sums_a_payload_of_any_number_of_lane_tiles(d):
    """K1 (interpreted) against XLA's sorted segment sums through the
    unique-row stream, three passes: one, two and three 128-lane
    tiles of payload."""
    n, vocab = 2 * sparse_apply.CHUNK, 1 << 12
    rng = np.random.default_rng(d)
    ids = jnp.asarray((rng.zipf(1.2, n) % vocab).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    cap = sparse_apply.entries_cap(n, vocab)
    got, want = (
        sparse_apply.unique_entries(
            ids, g, vocab=vocab, cap=cap, pad_first=True, segment_sums=seg)
        for seg in (partial(sparse_apply._k1_dedup,
                            passes=sparse_apply._EXACT_PASSES),
                    sparse_apply._xla_segment_sums))
    count = int(want[2])
    assert int(got[2]) == count == len(np.unique(np.asarray(ids)))
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1].shape == (cap, 2 * d)
    # sums of many occurrences differ by their order (the hottest row
    # has hundreds); a row that occurs once comes back bit for bit
    np.testing.assert_allclose(got[1][:count], want[1][:count],
                               rtol=1e-4, atol=1e-4)
    _, first, times = np.unique(np.asarray(ids), return_index=True,
                                return_counts=True)
    once = times == 1
    assert once.sum() > 10
    np.testing.assert_array_equal(
        np.asarray(got[1])[:count][once][:, :d], np.asarray(g)[first[once]])
