"""The sharded training job's step on a 2x2 mesh (``lookup=shardmap``,
entries exchange, the merged stream written by the stream writer) at
small size on the CPU's virtual devices:

(a) three steps against the benchmark's plain reference
    (``benchmarks/reference/fm.py``, loaded by path: the copy that
    decides ``criteo1tb-train-2x2``'s ``correct``) on the GLOBAL batch,
    from the reference's own seeded weights; bfloat16-rounded operands
    and one data shard's half of the batch left out must fail;
(b) the share ties to the whole: the 2x2 step's tables, accumulators and
    scores are the one-device step's on the same global batch, for every
    row-local optimizer and both callers of the exchange;
(c) the merged stream in the stream writer's form gives bit for bit
    what ``k2_apply`` gives from ``merge_entries``;
(d) the exchange recovers exact rows where a shard's tile index needs
    more than two bf16 passes (``vocab_local / TILE`` = 2^17, 2^18);
(e) the compact rung: a step whose streams all fit ``entries_rung``
    gathers and merges that many slots and writes, bit for bit, what the
    full-capacity exchange writes; one stream that does not fit sends
    the whole mesh over capacity; the gauges say which ran.
"""

from __future__ import annotations

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import shardmap_step, sparse as sparse_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# two model shards of four subtiles each; 32 examples a data shard
VOCAB, BATCH, F, K, STEPS = 2048, 64, 8, 8, 3
TILE = sparse_apply.TILE

# Tolerances of (a), each between the largest sound reading and the
# smallest control reading (this file's own runs, on the CPU, where both
# sides are float32 and differ by the order of their sums: the mesh adds
# a psum of two partial terms and a merge of two partial row sums):
#   scores  largest |score - reference|: sound 3.0e-8; bfloat16 operands
#           1.3e-3; a data shard left out 2.3e-2
#   loss    relative gap of a batch's mean logloss: sound 1.2e-9;
#           bfloat16 7.8e-5; shard left out 8.0e-4
#   grad    first gradient as Adagrad got it, worked back from the
#           state's change, largest element gap over the largest
#           element: sound 1.6e-7 (the float32 ulp of a weight of 0.1
#           over the 1e-3 it moved); bfloat16 5.3e-4; shard left out 0.36
#   change  parameters' change over the three steps, same norm: sound
#           1.9e-7; bfloat16 3.9e-4; shard left out 0.61
TOL = {"scores": 2e-6, "loss": 1e-7, "grad": 1e-5, "change": 1e-5}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_fm",
        os.path.join(REPO, "benchmarks", "reference", "fm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def stream_writer(monkeypatch):
    """The exchange ends in the stream writer as it does where kernels
    run compiled (interpreted, the rule keeps K2).  Gives the list of
    data-shard counts the rule was asked with."""
    asked = []
    monkeypatch.setattr(
        sparse_apply, "exchange_takes_stream",
        lambda data_shards: asked.append(data_shards) or data_shards > 1)
    return asked


def _mesh(data=2, model=2):
    devs = np.array(jax.devices()[:data * model]).reshape(data, model)
    return Mesh(devs, (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))


def _cfg(optimizer="adagrad", **over):
    return FmConfig(**{**dict(
        vocabulary_size=VOCAB, factor_num=K, max_features=F,
        batch_size=BATCH, optimizer=optimizer, learning_rate=0.05,
        adagrad_initial_accumulator=0.1, ftrl_l1=0.01, ftrl_l2=0.1,
        factor_lambda=1e-6, bias_lambda=1e-6, init_value_range=0.1,
        sparse_update=True, lookup="shardmap", sparse_exchange="entries",
    ), **over})


def _batches(seed, steps=STEPS):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        vals = rng.uniform(0.1, 1.0, (BATCH, F)).astype(np.float32)
        vals[rng.uniform(size=vals.shape) < 0.1] = 0.0  # padding slots
        out.append(Batch(
            labels=rng.integers(0, 2, BATCH).astype(np.float32),
            # a Zipf head spread over both model shards by a multiplier:
            # rows repeat within a data shard and across the two
            ids=((rng.zipf(1.3, (BATCH, F)) * 977) % VOCAB).astype(
                np.int32),
            vals=vals,
            fields=np.zeros((BATCH, F), np.int32),
            weights=rng.uniform(0.5, 1.5, BATCH).astype(np.float32),
        ))
    return out


def _logloss(scores, batch):
    s = np.asarray(scores, np.float64)
    per = np.logaddexp(0.0, s) - batch.labels * s
    return float((per * batch.weights).sum() / batch.weights.sum())


# ------------------------------------------------- (a) the plain reference


def _gaps(ref, *, compute_dtype="float32", drop_shard=False):
    cfg = _cfg(compute_dtype=compute_dtype)
    keys = {
        "vocabulary_size": VOCAB, "factor_num": K, "optimizer": "adagrad",
        "loss_type": "logistic", "learning_rate": cfg.learning_rate,
        "adagrad.initial_accumulator": cfg.adagrad_initial_accumulator,
        "factor_lambda": cfg.factor_lambda, "bias_lambda": cfg.bias_lambda,
        "init_value_range": cfg.init_value_range, "seed": 11,
    }
    mesh = _mesh()
    w0, table, acc_w0, acc_table = ref.init_state(keys)
    params = fm.FmParams(w0=w0, table=jnp.array(table))
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    step = jax.jit(partial(
        shardmap_step.sparse_step_shardmap, cfg, mesh=mesh, health=True))
    ref_step = ref.make_step(keys)
    state = (w0, table, acc_w0, acc_table)
    first = {"w0": np.float64(w0), "table": np.asarray(table, np.float64)}
    out = {"scores": 0.0, "loss": 0.0}
    for i, batch in enumerate(_batches(21)):
        fed = batch
        if drop_shard:  # the second data shard's examples count for nothing
            w = batch.weights.copy()
            w[BATCH // 2:] = 0.0
            fed = batch._replace(weights=w)
        pre = params
        params, opt, scores, aux = step(params, opt, fed)
        state, raux = ref_step(state, {
            n: jnp.asarray(getattr(batch, n))
            for n in ("ids", "vals", "labels", "weights")})
        out["scores"] = max(out["scores"], float(np.abs(
            np.asarray(scores) - np.asarray(raux["scores"])).max()))
        want = _logloss(raux["scores"], batch)
        out["loss"] = max(out["loss"],
                          abs(_logloss(scores, batch) - want) / want)
        if i == 0:
            uniq = np.unique(batch.ids)
            merged, capacity = (int(x) for x in aux[2])
            # the exchange ran: every touched row once a model shard,
            # over two shards' two all-gathered streams of 512 slots
            assert merged == len(uniq)
            assert capacity == 2 * 2 * sparse_apply.entries_cap(
                BATCH // 2 * F, VOCAB // 2)
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


def test_three_mesh_steps_agree_with_the_reference(ref, stream_writer):
    gaps = _gaps(ref)
    assert all(gaps[n] <= TOL[n] for n in TOL), gaps
    assert set(stream_writer) == {2}  # the rule was asked, and said yes


def test_bfloat16_operands_fail_the_float32_tolerance(ref, stream_writer):
    gaps = _gaps(ref, compute_dtype="bfloat16")
    assert all(gaps[n] > 5 * TOL[n] for n in TOL), gaps


def test_a_data_shard_left_out_fails_every_tolerance(ref, stream_writer):
    gaps = _gaps(ref, drop_shard=True)
    assert all(gaps[n] > 5 * TOL[n] for n in TOL), gaps


# ------------------------------------- (b) the share ties to the whole


def _opt_tables(opt):
    return [t.table for t in opt] if opt else []


@pytest.mark.parametrize("caller", ["shardmap", "sharded"])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_mesh_step_is_the_one_device_step(stream_writer, optimizer, caller):
    """Both callers of the exchange — the hand-sharded step's
    ``_apply_stream`` and the GSPMD sharded apply's ``_sharded_call`` —
    through the stream writer, against ``sparse_step`` on one device on
    the same global batch."""
    mesh = _mesh()
    if caller == "shardmap":
        cfg = _cfg(optimizer)
        step = jax.jit(partial(
            shardmap_step.sparse_step_shardmap, cfg, mesh=mesh))
    else:
        cfg = _cfg(optimizer, lookup="auto", sparse_apply="tile")
        assert sparse_lib.apply_mode(cfg, mesh) == "sharded"
        step = jax.jit(partial(sparse_lib.sparse_step, cfg, mesh=mesh))
    one = jax.jit(partial(
        sparse_lib.sparse_step, _cfg(optimizer, sparse_apply="scatter")))
    params = fm.init_params(jax.random.PRNGKey(3), cfg)
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    p_m, o_m, p_1, o_1 = params, opt, params, opt
    for batch in _batches(31):
        p_m, o_m, s_m = step(p_m, o_m, batch)
        p_1, o_1, s_1 = one(p_1, o_1, batch)
        np.testing.assert_allclose(s_m, s_1, rtol=1e-5, atol=1e-6)
    assert set(stream_writer) == {2}  # the rule was asked, and said yes
    # a weight moves by ~1e-3 a step; the two sides sum a row's
    # occurrences in another order (a shard's partial sums, then two)
    np.testing.assert_allclose(p_m.table, p_1.table, rtol=1e-5, atol=2e-7)
    np.testing.assert_allclose(p_m.w0, p_1.w0, rtol=1e-5, atol=1e-7)
    for got, want in zip(_opt_tables(o_m), _opt_tables(o_1)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    # rows the batches never touched keep their bits
    touched = np.unique(np.concatenate([b.ids for b in _batches(31)]))
    rest = np.setdiff1d(np.arange(VOCAB), touched)
    assert len(rest) > VOCAB // 2
    np.testing.assert_array_equal(
        np.asarray(p_m.table)[rest], np.asarray(params.table)[rest])


def test_gauges_say_what_the_mesh_step_holds(monkeypatch):
    mesh, cfg = _mesh(), _cfg()
    assert sparse_lib.exchange_mode(cfg, mesh) == "entries"
    assert sparse_lib.exchange_mode(
        _cfg(sparse_exchange="dense"), mesh) == "dense"
    assert sparse_lib.exchange_mode(cfg, None) is None
    assert sparse_lib.exchange_mode(_cfg(lookup="auto"), mesh) is None
    # interpreted kernels keep K2; compiled, the stream writer runs
    assert sparse_lib.apply_stream(cfg, mesh) is False
    monkeypatch.setattr(sparse_apply, "_use_interpret", lambda: False)
    assert sparse_lib.apply_stream(cfg, mesh) is True
    assert sparse_lib.apply_stream(
        _cfg(sparse_exchange="dense"), mesh) is False
    # one data shard keeps its short cut into K2
    assert sparse_lib.apply_stream(cfg, _mesh(1, 2)) is False
    # streams of one chunk hold no compact rung: exchange_fill counts
    # against capacity and exchange_compact is not published
    # (test_gauges_say_which_rung_the_exchange_took: where one is held)
    assert sparse_lib.exchange_slots(cfg, mesh) == (None, 2 * 2 * 512)


# ------------------------------- (c) the merged stream, bit for bit


@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_merged_stream_is_k2_from_merge_entries_bit_for_bit(optimizer):
    """Two shards' entry streams whose sums fit 16 bits (so that K2's
    two bf16 passes lose nothing): ``merged_stream_apply`` writes what
    ``k2_apply(merge_entries(...))`` writes, to the last bit, and
    leaves every other row alone."""
    d, vocab, cap = 1 + K, 4 * TILE, sparse_apply.CHUNK
    rng = np.random.default_rng(5)
    rows, pay = [], []
    for shard in range(2):
        n = 300 + 40 * shard
        r = np.sort(rng.choice(vocab, n, replace=False)).astype(np.int32)
        g = rng.integers(-64, 65, (n, d)) / 256.0
        g2 = rng.integers(0, 129, (n, d)) / 1024.0
        rows.append(np.concatenate([r, np.full(cap - n, vocab, np.int32)]))
        pay.append(np.concatenate(
            [np.concatenate([g, g2], axis=1),
             np.zeros((cap - n, 2 * d))]).astype(np.float32))
    rows, pay = jnp.asarray(np.concatenate(rows)), jnp.asarray(
        np.concatenate(pay))
    n_tables = {"adagrad": 2, "ftrl": 3, "sgd": 1}[optimizer]
    tables = tuple(
        jnp.asarray(rng.uniform(0.1, 1.0, (vocab, d)).astype(np.float32))
        for _ in range(n_tables))
    update = {
        "adagrad": partial(sparse_apply.adagrad_update, lr=0.05, eps=1e-7),
        "ftrl": partial(sparse_apply.ftrl_update, lr=0.05, l1=0.01, l2=0.1,
                        beta=1.0),
        "sgd": partial(sparse_apply.sgd_update, lr=0.05),
    }[optimizer]

    @jax.jit
    def by_k2(rows, pay, *tables):
        u, ts = sparse_apply.merge_entries(rows, pay, vocab=vocab)
        return tuple(sparse_apply.k2_apply(update, ts, u, tables)), ts[-1]

    @jax.jit
    def by_stream(rows, pay, *tables):
        return sparse_apply.merged_stream_apply(update, tables, rows, pay)

    want, n_want = by_k2(rows, pay, *tables)
    got, n_got, touched = by_stream(rows, pay, *tables)
    assert touched is None  # no mask asked for
    real = np.unique(np.asarray(rows)[np.asarray(rows) < vocab])
    assert int(n_got) == int(n_want) == len(real)
    hit = np.zeros(vocab, bool)
    hit[real] = True
    for g, w, old in zip(got, want, tables):
        g, w, old = np.asarray(g), np.asarray(w), np.asarray(old)
        np.testing.assert_array_equal(g[hit], w[hit])
        # K2 recomputes an untouched row (FTRL: not its stored bits);
        # the stream writer leaves it alone
        np.testing.assert_array_equal(g[~hit], old[~hit])
        assert (g[hit] != old[hit]).any()


# --------------------- (d) exact rows past two passes' seventeen bits


def _planted(vocab_local, seed):
    """One data shard's local ids: the top quarter of the shard (tile
    indices with every bit in play: one next to a power of two splits
    into two bf16 terms exactly), the sentinel (off-shard) and a few low
    rows, with repeats."""
    rng = np.random.default_rng(seed)
    n = sparse_apply.CHUNK
    top = vocab_local - 1 - rng.integers(0, vocab_local // 4, n // 2)
    low = rng.integers(0, 2 * TILE, n // 4)
    off = np.full(n - n // 2 - n // 4, vocab_local)
    return rng.permutation(np.concatenate([top, low, off])).astype(np.int32)


@pytest.mark.parametrize("log2_tiles", [17, 18])
def test_exchange_recovers_exact_rows_in_the_top_tiles(log2_tiles):
    """The whole id plane of the exchange at a shard of 2^25 rows (the
    benchmark's: its sentinel's tile index is 2^17, the first that two
    bf16 passes cannot carry) and of 2^26: the gathered rows and the
    merged stream's lrow / tidx columns are the planted rows, exactly."""
    vocab_local = TILE << log2_tiles
    mesh = _mesh(2, 1)
    lids = np.stack([_planted(vocab_local, s) for s in (1, 2)])
    d = 2
    g = np.ones((2, lids.shape[1], d), np.float32)
    g[lids == vocab_local] = 0.0
    group = 16

    def body(lids_l, g_l):
        stream, count = sparse_apply._unique_stream(
            lids_l[0], g_l[0], vocab_local, sparse_apply._k1_exact,
            pad_first=True)
        rows, pay = sparse_apply.gather_entries(
            stream, count, d=d, vocab_local=vocab_local,
            slots=sparse_apply.entries_cap(lids_l.shape[1], vocab_local),
            data_axis=mesh_lib.DATA_AXIS)
        u_tiles, starts = sparse_apply.merge_entries_stream(
            rows, pay, vocab=vocab_local, group=group,
            segment_sums=partial(sparse_apply._k1_dedup,
                                 passes=sparse_apply._EXACT_PASSES))
        return rows, u_tiles[0], starts

    rows, u, starts = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(mesh_lib.DATA_AXIS),) * 2,
        out_specs=P(), check_vma=False,
    ))(jnp.asarray(lids), jnp.asarray(g))
    cap = sparse_apply.entries_cap(lids.shape[1], vocab_local)
    rows = np.asarray(rows).reshape(2, cap)
    for shard in range(2):
        want = np.unique(lids[shard][lids[shard] < vocab_local])
        np.testing.assert_array_equal(rows[shard][:len(want)], want)
        assert (rows[shard][len(want):] == vocab_local).all()
    merged = np.unique(lids[lids < vocab_local])
    u, starts = np.asarray(u), np.asarray(starts)
    assert starts[-1] == len(merged)
    got = (u[:len(merged), 2 * d + 1].astype(np.int64) * TILE
           + u[:len(merged), 2 * d].astype(np.int64))
    np.testing.assert_array_equal(got, merged)
    # a row of both shards sums both; the writer finds a block's entries
    both = np.intersect1d(*(np.unique(x) for x in lids))
    both = both[both < vocab_local]
    assert len(both) > 0
    times = np.array([(lids == r).sum() for r in merged])
    np.testing.assert_array_equal(u[:len(merged), 0], times)
    block = TILE * group
    np.testing.assert_array_equal(
        starts, np.searchsorted(merged, np.arange(
            0, vocab_local + 1, block, dtype=np.int64)))


@pytest.mark.parametrize("passes,exact", [(2, False), (3, True)])
def test_two_passes_lose_the_top_tiles_of_a_2_to_26_row_shard(passes, exact):
    vocab_local = TILE << 18
    lids = _planted(vocab_local, 3)
    g = np.ones((len(lids), 2), np.float32)
    rows, _, count = sparse_apply.unique_entries(
        jnp.asarray(lids), jnp.asarray(g), vocab=vocab_local,
        cap=sparse_apply.entries_cap(len(lids), vocab_local),
        segment_sums=partial(sparse_apply._k1_dedup, passes=passes))
    want = np.unique(lids[lids < vocab_local])
    assert int(count) == len(want)
    same = np.array_equal(np.asarray(rows)[:len(want)], want)
    assert same is exact


# ------------------------------------------------ (e) the compact rung

# A data shard's 256 x 8 occurrences over a model shard of 4,096 rows:
# streams of 2,048 slots, the rung a quarter of that.
R_VOCAB, R_BATCH = 8192, 512
R_CAP = sparse_apply.entries_cap(R_BATCH // 2 * F, R_VOCAB // 2)
R_RUNG = sparse_apply.entries_rung(R_CAP, 2)


def _rung_batch(seed, ids):
    """One global batch of R_BATCH examples; ``ids(rng, n)`` draws a
    data shard's ``[n, F]`` ids, the first shard's first."""
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.1, 1.0, (R_BATCH, F)).astype(np.float32)
    vals[rng.uniform(size=vals.shape) < 0.1] = 0.0
    return Batch(
        labels=rng.integers(0, 2, R_BATCH).astype(np.float32),
        ids=np.concatenate([
            draw(rng, R_BATCH // 2) for draw in ids]).astype(np.int32),
        vals=vals, fields=np.zeros((R_BATCH, F), np.int32),
        weights=rng.uniform(0.5, 1.5, R_BATCH).astype(np.float32),
    )


def _zipf(rng, n):  # ~250 distinct rows a stream: fits the rung
    return (rng.zipf(1.3, (n, F)) * 977) % R_VOCAB


def _uniform(rng, n):  # ~800 distinct rows a stream: does not
    return rng.integers(0, R_VOCAB, (n, F))


def _streams(batch):
    """Real entries of each (data shard, model shard) stream."""
    half, vl = R_BATCH // 2, R_VOCAB // 2
    return [len(np.unique(ids[(ids >= m * vl) & (ids < (m + 1) * vl)]))
            for ids in (batch.ids[:half], batch.ids[half:])
            for m in range(2)]


def _rung_cfg(optimizer="adagrad", **over):
    return _cfg(optimizer, vocabulary_size=R_VOCAB, batch_size=R_BATCH,
                **over)


def _mesh_step(caller, optimizer, mesh):
    if caller == "shardmap":
        return jax.jit(partial(
            shardmap_step.sparse_step_shardmap, _rung_cfg(optimizer),
            mesh=mesh, health=True))
    cfg = _rung_cfg(optimizer, lookup="auto", sparse_apply="tile")
    assert sparse_lib.apply_mode(cfg, mesh) == "sharded"
    return jax.jit(partial(sparse_lib.sparse_step, cfg, mesh=mesh))


def _init(optimizer):
    cfg = _rung_cfg(optimizer)
    params = fm.init_params(jax.random.PRNGKey(3), cfg)
    return params, sparse_lib.init_sparse_opt_state(cfg, params)


@pytest.fixture(params=["k2", "stream"])
def writer(request):
    """Both ends of the exchange: the row-major K2 that interpreted
    kernels keep, and the stream writer (``stream_writer``)."""
    if request.param == "stream":
        request.getfixturevalue("stream_writer")
    return request.param


def test_the_rung_is_a_quarter_of_capacity_or_none():
    assert (R_CAP, R_RUNG) == (2048, 512)
    # the four-chip cell's streams
    assert sparse_apply.entries_rung(2_555_904, 2) == 638_976
    assert sparse_apply.entries_rung(2_555_904, 4) == 638_976
    # rounded UP to whole chunks; none where that is the capacity
    assert sparse_apply.entries_rung(5 * 512, 2) == 1024
    assert sparse_apply.entries_rung(1024, 2) == 512
    assert sparse_apply.entries_rung(512, 2) is None
    # one data shard exchanges nothing
    assert sparse_apply.entries_rung(2_555_904, 1) is None
    assert sparse_apply.entries_rung(R_CAP, 1) is None
    # the small meshes of (a)-(d) hold no conditional
    assert sparse_apply.entries_rung(
        sparse_apply.entries_cap(BATCH // 2 * F, VOCAB // 2), 2) is None


@pytest.mark.parametrize("caller", ["shardmap", "sharded"])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_compact_rung_writes_the_full_exchanges_bits(
        monkeypatch, writer, optimizer, caller):
    """A batch whose four streams fit the rung: the step with the
    conditional (the compact branch runs: the aux says 2 x 2 x rung
    slots) against the same step traced with no rung at all."""
    mesh = _mesh()
    batch = _rung_batch(41, [_zipf, _zipf])
    assert max(_streams(batch)) <= R_RUNG
    params, opt = _init(optimizer)
    with_rung = _mesh_step(caller, optimizer, mesh)
    got = with_rung(params, opt, batch)
    monkeypatch.setattr(sparse_apply, "entries_rung", lambda cap, s: None)
    no_rung = _mesh_step(caller, optimizer, mesh)
    want = no_rung(params, opt, batch)
    # rows and sums all-gathered once; with the rung, once a branch
    gathers = [str(jax.make_jaxpr(f)(params, opt, batch)).count(
        "all_gather[") for f in (with_rung, no_rung)]
    assert gathers == [4, 2]
    for g, w in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want[:3])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert (np.asarray(got[0].table) != np.asarray(params.table)).any()
    if caller == "shardmap":
        merged = len(np.unique(batch.ids))
        assert [int(x) for x in got[3][2]] == [merged, 2 * 2 * R_RUNG]
        assert [int(x) for x in want[3][2]] == [merged, 2 * 2 * R_CAP]


@pytest.mark.parametrize("caller", ["shardmap", "sharded"])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
@pytest.mark.parametrize("shards", ["both", "second"])
def test_a_stream_over_the_rung_sends_the_mesh_over_capacity(
        stream_writer, shards, optimizer, caller):
    """Uniform ids on both data shards, or on the second alone (the
    first's streams fit): every device takes the full branch — the
    predicate is one scalar for the mesh — and the step is still the
    one-device step."""
    mesh = _mesh()
    batch = _rung_batch(
        43, [_uniform if shards == "both" else _zipf, _uniform])
    real = _streams(batch)
    assert max(real[2:]) > R_RUNG
    assert (max(real[:2]) > R_RUNG) == (shards == "both")
    params, opt = _init(optimizer)
    p_m, o_m, s_m, *aux = _mesh_step(caller, optimizer, mesh)(
        params, opt, batch)
    p_1, o_1, s_1 = jax.jit(partial(
        sparse_lib.sparse_step, _rung_cfg(optimizer, sparse_apply="scatter")
    ))(params, opt, batch)
    if caller == "shardmap":
        assert [int(x) for x in aux[0][2]] == [
            len(np.unique(batch.ids)), 2 * 2 * R_CAP]
    np.testing.assert_allclose(s_m, s_1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p_m.table, p_1.table, rtol=1e-5, atol=2e-7)
    for got, want in zip(_opt_tables(o_m), _opt_tables(o_1)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    rest = np.setdiff1d(np.arange(R_VOCAB), np.unique(batch.ids))
    np.testing.assert_array_equal(
        np.asarray(p_m.table)[rest], np.asarray(params.table)[rest])


@pytest.mark.parametrize("ids", ["zipf", "uniform"])
def test_prefetched_id_plane_is_cut_to_the_rung_bit_for_bit(writer, ids):
    """The overlap's ``rows_all`` keeps its full-capacity shape and is
    cut a shard on the compact branch: the sharded apply with the id
    plane handed in writes what it writes gathering the rows itself,
    on either branch."""
    mesh = _mesh()
    batch = _rung_batch(47, [_zipf if ids == "zipf" else _uniform] * 2)
    assert (max(_streams(batch)) <= R_RUNG) == (ids == "zipf")
    rng = np.random.default_rng(7)
    d = 1 + K
    table, acc = (jnp.asarray(rng.uniform(0.1, 1.0, (R_VOCAB, d)).astype(
        np.float32)) for _ in range(2))
    flat = jnp.asarray(batch.ids.reshape(-1))
    g = jnp.asarray(rng.normal(size=(R_BATCH * F, d)).astype(np.float32))
    axes = dict(mesh=mesh, data_axis=mesh_lib.DATA_AXIS,
                model_axis=mesh_lib.MODEL_AXIS)
    apply = jax.jit(partial(
        sparse_apply.adagrad_apply_sharded, lr=0.05, eps=1e-7,
        exchange="entries", **axes))
    rows_all = jax.jit(sparse_apply.make_entries_prefetch(
        mesh, mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS, R_VOCAB))(flat)
    assert rows_all.shape == (2 * 2 * R_CAP,)
    want = apply(table, acc, flat, g)
    got = apply(table, acc, flat, g, rows_all=rows_all)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(got[0]) != np.asarray(table)).any()


def _write_libsvm(path, batches):
    with open(path, "w") as f:
        for b in batches:
            for y, ids, vals in zip(b.labels, b.ids, b.vals):
                f.write(f"{int(y)} " + " ".join(
                    f"{i}:{max(v, 0.05):.3f}" for i, v in zip(ids, vals))
                    + "\n")


@pytest.mark.parametrize("ids,compact,fill", [
    ("zipf", 1.0, R_RUNG), ("uniform", 0.0, R_CAP)])
def test_gauges_say_which_rung_the_exchange_took(
        tmp_path, ids, compact, fill):
    """``Trainer.train()`` on the 2x2 mesh: ``train.exchange_compact`` is
    the share of the newest dispatch's steps on the compact rung, and
    ``train.exchange_fill`` counts against the slots those steps
    gathered — the rung's where it ran."""
    from fast_tffm_tpu.train.loop import Trainer

    draw = _zipf if ids == "zipf" else _uniform
    batches = [_rung_batch(s, [draw, draw]) for s in (51, 52, 53, 54)]
    _write_libsvm(tmp_path / "train.libsvm", batches)
    cfg = _rung_cfg(
        train_files=[str(tmp_path / "train.libsvm")],
        model_file=str(tmp_path / "model"), epoch_num=1, log_steps=0,
        thread_num=1, steps_per_dispatch=2, mesh_data=2, mesh_model=2,
    )
    mesh = _mesh()
    assert sparse_lib.exchange_slots(cfg, mesh) == (
        2 * 2 * R_RUNG, 2 * 2 * R_CAP)
    trainer = Trainer(cfg, mesh=mesh)
    trainer.train()
    gauges = trainer.telemetry.snapshot()["gauges"]
    assert gauges["train.exchange_mode"] == 1
    assert gauges["train.exchange_compact"] == compact
    # the newest dispatch read back: two batches of the (shuffled) file
    merged = sum(len(np.unique(b.ids)) for b in batches) / 2
    assert gauges["train.exchange_fill"] == pytest.approx(
        merged / (2 * 2 * 2 * fill), rel=0.05)
