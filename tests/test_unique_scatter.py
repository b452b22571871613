"""The single-device scatter apply: sort and segment-sum the occurrences,
write each touched row once (ops.sparse_apply.scatter_apply_unique).

Kept apart from test_sparse.py (and named to be collected last): these
cases compile a dozen small programs, and next to that file's 150-step
8-device test they made its known XLA:CPU rendezvous abort under load
more likely.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import sparse
from fast_tffm_tpu.train.loop import Trainer

from test_sparse import _cfg, _dup_batch


# The oracle is the per-occurrence formula that the apply replaced on one
# device (and that the GSPMD scatter still runs), in float64 numpy.


def _occurrence_oracle(optimizer, cfg, tables, ids, g):
    """New ``tables`` after one apply, every occurrence added singly."""
    tables = [np.asarray(t, np.float64) for t in tables]
    g = np.asarray(g, np.float64)
    g1 = np.zeros_like(tables[0])
    g2 = np.zeros_like(tables[0])
    np.add.at(g1, ids, g)
    np.add.at(g2, ids, g * g)  # per occurrence: sum of squares
    lr = cfg.learning_rate
    if optimizer == "sgd":
        (w,) = tables
        return [w - lr * g1]
    if optimizer == "adagrad":
        w, acc = tables
        acc = acc + g2
        return [w - lr * g1 / np.sqrt(acc + sparse.ADAGRAD_EPS), acc]
    w, z, n = tables
    n_new = n + g2
    z_new = z + g1 - (np.sqrt(n_new) - np.sqrt(n)) / lr * w
    denom = (cfg.ftrl_beta + np.sqrt(n_new)) / lr + cfg.ftrl_l2
    w_new = np.where(
        np.abs(z_new) <= cfg.ftrl_l1, 0.0,
        -(z_new - np.sign(z_new) * cfg.ftrl_l1) / denom,
    )
    return [w_new, z_new, n_new]


def _occurrence_ids(rng, scenario, vocab):
    if scenario == "no_duplicates":
        return rng.permutation(vocab)[:1024]
    if scenario == "zipf_duplicates":
        return rng.zipf(1.1, size=1024) % vocab
    if scenario == "one_id_half_the_batch":
        ids = rng.integers(0, vocab, size=1024)
        ids[rng.permutation(1024)[:512]] = 77
        return ids
    # Neither a CHUNK (512) nor a SCATTER_CHUNK (4096) multiple, and more
    # unique rows than one trip of the apply's loop holds.
    assert scenario == "ragged_n"
    return rng.integers(0, vocab, size=6000)


@pytest.mark.parametrize("scenario", [
    "no_duplicates", "zipf_duplicates", "one_id_half_the_batch", "ragged_n",
])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_unique_scatter_apply_matches_per_occurrence_oracle(
        optimizer, scenario, tmp_path):
    from fast_tffm_tpu.models import fm

    vocab, d = 16384, 5
    rng = np.random.default_rng(
        [len(optimizer), len(scenario)])  # a seed per case
    cfg = FmConfig(
        vocabulary_size=vocab, factor_num=d - 1, optimizer=optimizer,
        learning_rate=0.1, ftrl_l1=0.01, ftrl_l2=0.001,
        model_file=str(tmp_path / "unused"),
    )
    ids = _occurrence_ids(rng, scenario, vocab).astype(np.int32)
    n = len(ids)
    g = rng.normal(size=(n, d)).astype(np.float32) * 0.1
    if scenario == "one_id_half_the_batch":
        g[ids == 77] = np.linspace(0.05, 0.25, d, dtype=np.float32)
    table = rng.uniform(-0.1, 0.1, size=(vocab, d)).astype(np.float32)
    params = fm.FmParams(w0=jnp.zeros(()), table=jnp.asarray(table))
    opt = sparse.init_sparse_opt_state(cfg, params)
    if optimizer == "ftrl":  # a state that has seen gradients before
        opt = opt._replace(n=opt.n._replace(table=jnp.asarray(
            rng.uniform(0.1, 0.5, size=(vocab, d)).astype(np.float32))))
    before = [table] + [
        np.asarray(s.table) for s in
        ((opt.acc,) if optimizer == "adagrad" else
         (opt.z, opt.n) if optimizer == "ftrl" else ())
    ]

    p2, o2, unique, _ = jax.jit(
        lambda p, o, i, gr: sparse._APPLY[optimizer](
            cfg, p, o, i, gr, jnp.zeros(()), p.table[i], mode="unique")
    )(params, opt, jnp.asarray(ids), jnp.asarray(g))
    after = [np.asarray(p2.table)] + [
        np.asarray(s.table) for s in
        ((o2.acc,) if optimizer == "adagrad" else
         (o2.z, o2.n) if optimizer == "ftrl" else ())
    ]

    want = _occurrence_oracle(optimizer, cfg, before, ids, g)
    touched = np.unique(ids)
    untouched = np.setdiff1d(np.arange(vocab), touched)
    # float32 against float64: 1e-6, but a running float32 sum of k terms
    # may be off by k half-ulps (the per-occurrence scatter-add was too).
    kmax = np.bincount(ids).max()
    rtol = max(1e-6, kmax * 2.0 ** -24)
    for got, ref, old in zip(after, want, before):
        np.testing.assert_allclose(
            got[touched], ref[touched], rtol=rtol, atol=1e-6)
        # the padding rows (>= vocab) and every other row write nothing
        np.testing.assert_array_equal(got[untouched], old[untouched])
    # the counter behind train.apply_unique_frac: rows written
    assert int(unique) == len(touched)
    if scenario == "one_id_half_the_batch" and optimizer != "sgd":
        # k copies of one gradient add k*g^2 to the accumulator, not
        # (k*g)^2: sum of squares per occurrence.
        k = int(np.sum(ids == 77))
        g77 = np.linspace(0.05, 0.25, d, dtype=np.float32).astype(np.float64)
        np.testing.assert_allclose(
            after[-1][77] - before[-1][77], k * g77 ** 2, rtol=1e-5)
        assert k >= 512


def test_sparse_step_reports_the_unique_share(tmp_path):
    """``sparse_step(..., health=True)`` appends [rows written,
    occurrences] on one device (what ``train.apply_unique_frac`` is the
    ratio of) and leaves the aux as it was on a multi-device mesh."""
    rng = np.random.default_rng(11)
    cfg = _cfg(tmp_path, "uf", optimizer="adagrad", vocabulary_size=64)
    t = Trainer(cfg, mesh=mesh_lib.make_mesh(cfg, jax.devices()[:1]))
    b = _dup_batch(rng, cfg, cfg.batch_size)
    *_, aux = jax.jit(
        lambda p, o, bb: sparse.sparse_step(cfg, p, o, bb, health=True)
    )(t.state.params, t.state.opt_state, jax.tree.map(jnp.asarray, b))
    assert aux[2].dtype == jnp.uint32  # exact counts, summed wrapping
    written, merged = np.asarray(aux[2])
    assert merged == b.ids.size
    assert written == len(np.unique(b.ids)) < merged
    cfg8 = _cfg(tmp_path, "uf8", optimizer="adagrad", mesh_data=4,
                mesh_model=2)
    t8 = Trainer(cfg8)
    b8 = t8._put(_dup_batch(rng, cfg8, cfg8.batch_size))
    *_, aux8 = jax.jit(
        lambda p, o, bb: sparse.sparse_step(
            cfg8, p, o, bb, mesh=t8.mesh, health=True)
    )(t8.state.params, t8.state.opt_state, b8)
    assert aux8.apply_rows is None and aux8.touched is None


@pytest.mark.parametrize("passes,vocab,exact", [
    (2, 1 << 25, True), (2, 1 << 27, False),
    (3, 1 << 27, True), (3, (1 << 31) - 4096, True),
])
def test_k1_passes_carry_the_row_index(passes, vocab, exact):
    """The unique stream's rows come back through K1's matmuls as
    integer-valued f32 columns.  Two bf16 passes hold 17 bits of the
    tile index (vocab <= 2^25 at TILE = 256) and recover WRONG rows
    beyond; the three that scatter_apply_unique asks for hold all 24, and
    return a value that occurs once bit for bit.  (K1 interpreted.)"""
    from functools import partial

    from fast_tffm_tpu.ops import sparse_apply

    rng = np.random.default_rng(passes * 31 + vocab % 97)
    n, d = 1024, 3
    ids = rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)
    g = rng.normal(size=(n, d)).astype(np.float32)
    rows, pay, count = jax.jit(
        lambda i, gr: sparse_apply.unique_entries(
            i, gr, vocab=vocab, cap=sparse_apply.entries_cap(n, vocab),
            segment_sums=partial(sparse_apply._k1_dedup, passes=passes),
        )
    )(jnp.asarray(ids), jnp.asarray(g))
    uniq, first, counts = np.unique(
        ids, return_index=True, return_counts=True)
    assert int(count) == len(uniq)
    rows = np.asarray(rows)[: len(uniq)]
    assert np.array_equal(rows, uniq) == exact
    if passes == sparse_apply._EXACT_PASSES:
        once = counts == 1
        assert once.sum() > n // 2
        np.testing.assert_array_equal(
            np.asarray(pay)[: len(uniq)][once, :d], g[first[once]])


@pytest.mark.parametrize("n", [1536, 4096])
def test_payload_padded_first_is_the_plain_payload_bit_for_bit(n):
    """_payload_padded_first pads to 128 lanes before the permutation
    gather and selects the metadata lanes in afterwards (the cheaper
    order at the unique-row scatter's size on the TPU); it must equal
    the tile path's _payload of the sorted rows."""
    from fast_tffm_tpu.ops import sparse_apply

    rng = np.random.default_rng(n)
    g = rng.normal(size=(n, 9)).astype(np.float32)
    perm = rng.permutation(n).astype(np.int32)
    lrow = rng.integers(0, 256, size=n).astype(np.float32)
    tidx = rng.integers(0, 1 << 23, size=n).astype(np.float32)
    got = jax.jit(sparse_apply._payload_padded_first)(g, perm, lrow, tidx)
    want = jax.jit(
        lambda gr, pm, lr, ti: sparse_apply._payload(gr[pm], lr, ti)
    )(g, perm, lrow, tidx)
    assert got.shape == want.shape == (n, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------- the two writers (PR 34)
#
# scatter_apply_unique moves the unique rows either by XLA's scatter loop
# or by the transposed tile stream (a Pallas kernel, interpreted here).
# Same prep, same update formula, same float32 operations: bit for bit.

_STREAM_V = 2048  # eight 256-row subtiles, two grid steps at D = 157


def _writer_ids(rng, scenario):
    v, tile = _STREAM_V, 256
    if scenario == "heavy_duplicates":
        return rng.zipf(1.1, size=2048) % v
    if scenario == "subtile_edges":
        # first and last row of subtiles, of the table, and repeats
        edge = np.array([0, 255, 256, 511, 1023, 1024, v - 256, v - 1])
        return np.concatenate([edge, edge[::2], rng.integers(0, v, 100)])
    if scenario == "empty_subtiles":
        # nothing lands in subtile 2 nor in the last one
        ids = rng.integers(0, v, size=1500)
        return ids[(ids // tile != 2) & (ids // tile != v // tile - 1)]
    assert scenario == "full_subtile"
    # every row of subtile 1 (a window of 256 entries), some twice
    return np.concatenate([
        np.arange(tile, 2 * tile), rng.integers(tile, 2 * tile, 64),
        rng.integers(0, v, 200),
    ])


def _writer_case(optimizer, d, rng):
    """(update, additive, tables): tables no optimizer would have made
    (the weights are NOT ftrl_solve(z, n)), so a recomputed untouched
    row would show."""
    from functools import partial

    from fast_tffm_tpu.ops import sparse_apply

    v = _STREAM_V
    w = rng.uniform(-0.1, 0.1, size=(v, d)).astype(np.float32)
    s1 = rng.uniform(-1.0, 1.0, size=(v, d)).astype(np.float32)
    s2 = rng.uniform(0.1, 0.5, size=(v, d)).astype(np.float32)
    if optimizer == "adagrad":
        return (partial(sparse_apply.adagrad_update, lr=0.1, eps=1e-7),
                True, [w, s2])
    if optimizer == "sgd":
        return partial(sparse_apply.sgd_update, lr=0.1), True, [w]
    return (partial(sparse_apply.ftrl_update, lr=0.1, l1=0.01, l2=0.001,
                    beta=1.0), False, [w, s1, s2])


def _run_writer(update, additive, tables, ids, g, stream):
    from fast_tffm_tpu.ops import sparse_apply

    out, count, _ = jax.jit(
        lambda i, gr, *t: sparse_apply.scatter_apply_unique(
            update, t, i, gr, additive=additive, stream=stream)
    )(jnp.asarray(ids, jnp.int32), jnp.asarray(g), *tables)
    return [np.asarray(x) for x in out], int(count)


@pytest.mark.parametrize("scenario", [
    "heavy_duplicates", "subtile_edges", "empty_subtiles", "full_subtile",
])
@pytest.mark.parametrize("d", [9, 157])  # one payload lane tile, three
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_stream_writer_is_the_scatter_writer_bit_for_bit(
        optimizer, d, scenario):
    rng = np.random.default_rng([len(optimizer), d, len(scenario)])
    ids = _writer_ids(rng, scenario)
    g = (rng.normal(size=(len(ids), d)) * 0.1).astype(np.float32)
    update, additive, tables = _writer_case(optimizer, d, rng)
    want, n_want = _run_writer(update, additive, tables, ids, g, False)
    got, n_got = _run_writer(update, additive, tables, ids, g, True)
    assert n_got == n_want == len(np.unique(ids))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(
            a.view(np.uint32), b.view(np.uint32))
    if scenario == "full_subtile":
        assert len(np.unique(ids[(ids >= 256) & (ids < 512)])) == 256


@pytest.mark.parametrize("d", [9, 157])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
def test_stream_writer_leaves_untouched_rows_their_bits(optimizer, d):
    """The stream reads and writes EVERY row; a row the batch did not
    touch must come back as it went in, under FTRL too, whose update of
    zero sums would recompute the weight from (z, n)."""
    from fast_tffm_tpu.ops import sparse_apply

    rng = np.random.default_rng([d, len(optimizer)])
    ids = rng.integers(0, _STREAM_V, size=700)
    g = (rng.normal(size=(len(ids), d)) * 0.1).astype(np.float32)
    update, additive, tables = _writer_case(optimizer, d, rng)
    if optimizer == "ftrl":  # the seeds are such a state
        w, z, n = tables
        solved = np.asarray(sparse_apply.ftrl_solve(
            jnp.asarray(z), jnp.asarray(n), 0.1, 0.01, 0.001, 1.0))
        assert np.mean(solved != w) > 0.99
    got, count = _run_writer(update, additive, tables, ids, g, True)
    touched = np.unique(ids)
    rest = np.setdiff1d(np.arange(_STREAM_V), touched)
    assert count == len(touched) and len(rest) > 1000
    for a, old in zip(got, tables):
        np.testing.assert_array_equal(
            a[rest].view(np.uint32), old[rest].view(np.uint32))
        assert np.all(np.any(a[touched] != old[touched], axis=1))


@pytest.mark.parametrize("n,vocab,d,tables,stream", [
    # the two train cells of the benchmark (PERF.md §4): the stream
    (65536 * 39, 1 << 25, 9, 2, True),
    (16384 * 39, 1 << 22, 157, 2, True),
    (16384 * 39, 1 << 22, 157, 3, True),  # the same under FTRL
    # a small batch over a huge table: 2.8 ms of scatter, 29 of stream
    (40_000, 1 << 25, 9, 2, False),
    # a vocabulary that is not whole subtiles: never the stream
    (65536 * 39, (1 << 25) + 8, 9, 2, False),
    (16384 * 39, 255, 157, 2, False),
    # Criteo-Kaggle as examples/criteo_kaggle.cfg runs it
    (4096 * 39, 1 << 22, 9, 2, True),
])
def test_the_rule_between_the_two_writers(n, vocab, d, tables, stream):
    """stream_wins is a pure function of static shapes; takes_stream is
    the rule where the kernels run compiled, and the scatter loop where
    they would be interpreted (here)."""
    from fast_tffm_tpu import platform as pf
    from fast_tffm_tpu.ops import sparse_apply

    assert sparse_apply.stream_wins(n, vocab, d, tables) is stream
    assert sparse_apply.takes_stream(n, vocab, d, tables) is False
    with pf.force_compiled():
        assert sparse_apply.takes_stream(n, vocab, d, tables) is stream


@pytest.mark.parametrize("case,want", [
    ("one_device_scatter", True), ("mesh_of_eight", False),
    ("tile_mode", False), ("dense_optimizer", False),
])
def test_apply_stream_names_the_writer_the_step_holds(case, want, tmp_path):
    """train.sparse.apply_stream (gauge ``train.apply_stream``): 1 only
    for the one-device scatter apply at shapes where the rule takes the
    stream, and never where the kernels would be interpreted."""
    from fast_tffm_tpu import platform as pf

    kw = dict(vocabulary_size=1 << 22, batch_size=16384, max_features=39,
              factor_num=4, field_num=39, sparse_apply="scatter",
              model_file=str(tmp_path / "unused"))
    mesh = None
    if case == "mesh_of_eight":
        kw.update(mesh_data=4, mesh_model=2)
    elif case == "tile_mode":
        kw.update(sparse_apply="tile")
    elif case == "dense_optimizer":
        kw.update(optimizer="adam")
    cfg = FmConfig(**kw)
    if case == "mesh_of_eight":
        mesh = mesh_lib.make_mesh(cfg)
    assert sparse.apply_stream(cfg, mesh) is False  # interpreted here
    with pf.force_compiled():
        assert sparse.apply_stream(cfg, mesh) is want
