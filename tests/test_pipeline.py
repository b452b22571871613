"""Input-pipeline tests: epochs, shuffling, weights, ordering."""

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import BatchPipeline, iter_lines


@pytest.fixture
def data_files(tmp_path):
    a = tmp_path / "a.libsvm"
    a.write_text("".join(f"1 {i}:1.0\n" for i in range(10)))
    b = tmp_path / "b.libsvm"
    b.write_text("".join(f"0 {i}:2.0\n" for i in range(10, 15)))
    return [str(a), str(b)]


def _cfg(**kw):
    defaults = dict(
        vocabulary_size=100, batch_size=4, max_features=4, thread_num=2,
        queue_size=4, shuffle_buffer=8,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def test_iter_lines_all_files(data_files):
    lines = list(iter_lines(data_files))
    assert len(lines) == 15
    assert all(w == 1.0 for _, w in lines)


def test_iter_lines_weight_files(data_files, tmp_path):
    wa = tmp_path / "wa.txt"
    wa.write_text("".join(f"{0.1 * (i + 1):.2f}\n" for i in range(10)))
    wb = tmp_path / "wb.txt"
    wb.write_text("".join("2.0\n" for _ in range(5)))
    lines = list(iter_lines(data_files, [str(wa), str(wb)]))
    ws = [w for _, w in lines]
    np.testing.assert_allclose(ws[:10], [0.1 * (i + 1) for i in range(10)])
    np.testing.assert_allclose(ws[10:], [2.0] * 5)


def test_iter_lines_weights_align_past_blank_lines(tmp_path):
    """Regression: weight line i pairs with data line i even when the data
    file has blank/comment lines (which are skipped with their weights)."""
    data = tmp_path / "d.libsvm"
    data.write_text("1 1:1\n\n# comment\n0 2:1\n")
    wf = tmp_path / "w.txt"
    wf.write_text("0.5\n\n\n2.0\n")
    lines = list(iter_lines([str(data)], [str(wf)]))
    assert [w for _, w in lines] == [0.5, 2.0]


def test_iter_lines_short_weight_file_raises(tmp_path):
    data = tmp_path / "d.libsvm"
    data.write_text("1 1:1\n0 2:1\n")
    wf = tmp_path / "w.txt"
    wf.write_text("0.5\n")
    with pytest.raises(ValueError, match="does not pair"):
        list(iter_lines([str(data)], [str(wf)]))


def test_pipeline_covers_all_examples(data_files):
    pipe = BatchPipeline(data_files, _cfg(), epochs=1, shuffle=False)
    batches = list(pipe)
    total = sum(int(np.sum(b.weights > 0)) for b in batches)
    assert total == 15
    # All batches padded to the static shape.
    assert all(b.ids.shape == (4, 4) for b in batches)


def test_pipeline_epochs(data_files):
    pipe = BatchPipeline(data_files, _cfg(), epochs=3, shuffle=False)
    total = sum(int(np.sum(b.weights > 0)) for b in pipe)
    assert total == 45


def test_pipeline_shuffle_changes_order(data_files):
    cfg = _cfg(thread_num=1)
    ordered = BatchPipeline(data_files, cfg, epochs=1, shuffle=False, ordered=True)
    shuffled = BatchPipeline(
        data_files, cfg, epochs=1, shuffle=True, seed=7, ordered=True
    )
    ids_a = np.concatenate([b.ids[b.vals > 0] for b in ordered])
    ids_b = np.concatenate([b.ids[b.vals > 0] for b in shuffled])
    assert sorted(ids_a.tolist()) == sorted(ids_b.tolist())
    assert ids_a.tolist() != ids_b.tolist()


def test_pipeline_ordered_preserves_input_order(data_files):
    pipe = BatchPipeline(data_files, _cfg(), epochs=1, shuffle=False, ordered=True)
    ids = np.concatenate([b.ids[b.vals > 0] for b in pipe])
    assert ids.tolist() == list(range(15))


def test_pipeline_raises_on_malformed_line(tmp_path):
    """Regression: a bad line must raise promptly, not hang the pipeline."""
    bad = tmp_path / "bad.libsvm"
    bad.write_text("1 3:0.5 bad::token:extra\n")
    pipe = BatchPipeline([str(bad)], _cfg(), epochs=1, shuffle=False)
    with pytest.raises(ValueError):
        list(pipe)


def test_pipeline_raises_on_missing_weight_file(data_files):
    pipe = BatchPipeline(
        data_files, _cfg(), weight_files=["/nonexistent_w.txt", "/nope.txt"],
        epochs=1, shuffle=False,
    )
    with pytest.raises(FileNotFoundError):
        list(pipe)


def _raw_groups(files, batch_size, **kw):
    """File-order groups of <= batch_size lines, sliced from windows the
    way BatchPipeline does when it does not shuffle."""
    from fast_tffm_tpu.data.pipeline import _iter_raw_windows

    for buf, starts, ends in _iter_raw_windows(
        files, batch_size, batch_size, **kw
    ):
        for i in range(0, len(starts), batch_size):
            yield buf, starts[i:i + batch_size], ends[i:i + batch_size]


def test_raw_groups_cross_chunk_boundaries(tmp_path):
    """Fast-ingest chunking must carry partial lines/groups across reads."""
    from fast_tffm_tpu.data import native

    path = tmp_path / "d.libsvm"
    lines = [f"1 {i}:1.0" for i in range(257)]
    path.write_text("\n".join(lines) + "\n")
    # Absurdly small chunk size forces many boundary crossings.
    groups = list(_raw_groups([str(path)], batch_size=10, chunk_bytes=17))
    parser = native.NativeParser(1000, 4, num_threads=1)
    got = []
    for buf, starts, ends in groups:
        assert len(starts) <= 10
        b = parser.parse_raw(buf, starts, ends, 10)
        got.extend(b.ids[b.vals > 0].tolist())
    assert got == list(range(257))


def test_raw_groups_pack_across_file_boundaries(tmp_path):
    """Batches pack across files (like the line path); a missing trailing
    newline at a file boundary must not merge lines."""
    from fast_tffm_tpu.data import native

    a = tmp_path / "a.libsvm"
    a.write_bytes(b"1 0:1.0\n1 1:1.0\n1 2:1.0")  # no trailing newline
    b = tmp_path / "b.libsvm"
    b.write_bytes(b"1 3:1.0\n1 4:1.0\n1 5:1.0\n1 6:1.0\n")
    groups = list(_raw_groups([str(a), str(b)], batch_size=4))
    parser = native.NativeParser(1000, 4, num_threads=1)
    batches = [parser.parse_raw(buf, s, e, 4) for buf, s, e in groups]
    # 7 lines -> one full group of 4 (spanning the file boundary) + tail 3.
    assert [int((bb.weights > 0).sum()) for bb in batches] == [4, 3]
    got = [i for bb in batches for i in bb.ids[bb.vals > 0].tolist()]
    assert got == list(range(7))


def test_raw_parse_blank_and_comment_weight_zero(tmp_path):
    from fast_tffm_tpu.data import native

    buf = b"1 5:1.0\n\n# comment\n0 7:2.0\n"
    starts = native.find_line_offsets(buf)
    ends = np.append(starts[1:], len(buf))
    parser = native.NativeParser(100, 4, num_threads=1)
    b = parser.parse_raw(buf, starts, ends, 8)
    np.testing.assert_array_equal(b.weights[:4], [1, 0, 0, 1])
    assert b.ids[0, 0] == 5 and b.ids[3, 0] == 7


def test_raw_pipeline_matches_line_pipeline(tmp_path):
    """Fast ingest and line path parse identical batches (unshuffled)."""
    path = tmp_path / "d.libsvm"
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(100):
            toks = " ".join(
                f"{rng.integers(0, 99)}:{rng.uniform(0, 2):.4f}"
                for _ in range(rng.integers(1, 5))
            )
            f.write(f"{rng.integers(0, 2)} {toks}\n")
    cfg_fast = _cfg(fast_ingest=True)
    cfg_line = _cfg(fast_ingest=False)
    fast = list(BatchPipeline([str(path)], cfg_fast, epochs=1, shuffle=False,
                              ordered=True))
    line = list(BatchPipeline([str(path)], cfg_line, epochs=1, shuffle=False,
                              ordered=True))
    assert len(fast) == len(line)
    for bf, bl in zip(fast, line):
        np.testing.assert_array_equal(bf.ids, bl.ids)
        np.testing.assert_array_equal(bf.vals, bl.vals)
        np.testing.assert_array_equal(bf.labels, bl.labels)
        np.testing.assert_array_equal(bf.weights, bl.weights)


def test_fast_ingest_line_level_shuffle_mixes_sorted_labels(tmp_path):
    """A label-sorted file (the norm for CTR logs) must yield label-mixed
    batches under fast ingest: the shuffle permutes LINES within a
    shuffle_buffer window, not just batch-group order — group-granularity
    shuffling would deliver single-label batches no matter the order."""
    path = tmp_path / "sorted.libsvm"
    n = 4096
    with open(path, "w") as f:
        for i in range(n):
            f.write(f"{0 if i < n // 2 else 1} {i % 97}:1.0\n")
    cfg = _cfg(batch_size=64, shuffle_buffer=2048, thread_num=2)
    assert cfg.fast_ingest
    mixed = 0
    total = 0
    for b in BatchPipeline([str(path)], cfg, epochs=1, shuffle=True, seed=3):
        labels = b.labels[b.weights > 0]
        total += 1
        if 0 < labels.sum() < len(labels):
            mixed += 1
    assert total == n // 64
    # With line-level mixing virtually every batch holds both labels.
    assert mixed / total > 0.9


def test_pipeline_ordered_parallel_matches_single_thread(data_files):
    """ordered=True must deliver identical batches in identical order
    regardless of thread_num (model-axis-spanning hosts rely on this) —
    parsing fans out to workers, delivery reorders by sequence number."""
    one = _keys(BatchPipeline(
        data_files, _cfg(thread_num=1), epochs=2, shuffle=True, seed=5,
        ordered=True,
    ))
    four = _keys(BatchPipeline(
        data_files, _cfg(thread_num=4), epochs=2, shuffle=True, seed=5,
        ordered=True,
    ))
    assert one == four


def test_pipeline_drop_remainder(data_files):
    pipe = BatchPipeline(
        data_files, _cfg(), epochs=1, shuffle=False, drop_remainder=True
    )
    batches = list(pipe)
    assert all(int(np.sum(b.weights > 0)) == 4 for b in batches)
    assert len(batches) == 3  # 15 // 4


def _keys(pipe):
    return [
        (b.labels.tobytes(), b.ids.tobytes(), b.vals.tobytes())
        for b in pipe
    ]


def test_pipeline_shard_disjoint_and_complete(tmp_path):
    """Host-sharded input: shards partition the identically-seeded stream
    batch-for-batch (shard s takes items s, n+s, 2n+s, ...)."""
    path = tmp_path / "data.libsvm"
    path.write_text("".join(f"{i % 2} {i % 90}:1.0\n" for i in range(40)))
    cfg = _cfg(thread_num=1)  # deterministic batch order
    full = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=True))
    assert len(full) == 10
    s0 = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=True,
                             shard=(0, 2)))
    s1 = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=True,
                             shard=(1, 2)))
    assert s0 == full[0::2]
    assert s1 == full[1::2]


def test_pipeline_shard_drops_partial_round(tmp_path):
    """Every shard must emit the SAME batch count (a host with one extra
    step would deadlock the others), so the tail round is dropped when the
    stream length is not a multiple of num_shards."""
    path = tmp_path / "data.libsvm"
    path.write_text("".join(f"1 {i % 90}:1.0\n" for i in range(20)))
    cfg = _cfg(thread_num=1)  # 5 groups (last one partial)
    s0 = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=False,
                             ordered=True, shard=(0, 2)))
    s1 = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=False,
                             ordered=True, shard=(1, 2)))
    assert len(s0) == len(s1) == 2  # floor(5 / 2) rounds


def test_pipeline_shard_with_skip(tmp_path):
    """Mid-epoch resume composes with sharding: skip applies to MY share."""
    path = tmp_path / "data.libsvm"
    path.write_text("".join(f"1 {i % 90}:1.0\n" for i in range(40)))
    cfg = _cfg(thread_num=1)
    s0 = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=True,
                             shard=(0, 2)))
    s0_skip = _keys(BatchPipeline([str(path)], cfg, epochs=1, shuffle=True,
                                  shard=(0, 2), skip_batches=2))
    assert s0_skip == s0[2:]


def test_sort_meta_out_of_range_warns_per_batch(tmp_path, caplog):
    """An out-of-range-id sort_meta rejection is a data/vocabulary_size
    integrity bug, not a transient native failure: the pipeline must keep
    the spec and keep warning on EVERY bad batch instead of going quiet
    while the device path silently drops those updates (ADVICE r5)."""
    import logging

    pytest.importorskip("ctypes")
    from fast_tffm_tpu.data import native
    from fast_tffm_tpu.ops import sparse_apply

    try:
        native.sort_meta(np.zeros(4, np.int32), sparse_apply.TILE,
                         sparse_apply.CHUNK, sparse_apply.TILE)
    except native.OutOfRangeIdsError:  # pragma: no cover - impossible here
        pass
    except Exception:  # pragma: no cover - env-dependent
        pytest.skip("native lib unavailable")

    # Spec vocab SMALLER than the parser's modulus: the last two of four
    # batches hold ids out of the spec's [0, TILE) range — the shape of a
    # config/data mismatch.
    tile = sparse_apply.TILE
    path = tmp_path / "oor.libsvm"
    path.write_text("".join(
        f"1 {i}:1.0\n" for i in list(range(8)) + [tile + 5] * 8
    ))
    cfg = _cfg(thread_num=1, vocabulary_size=4 * tile)
    spec = (tile, sparse_apply.CHUNK, tile)
    pipe = BatchPipeline(
        [str(path)], cfg, epochs=1, shuffle=False, ordered=True,
        sort_meta_spec=spec,
    )
    with caplog.at_level(logging.WARNING):
        batches = list(pipe)
    assert len(batches) == 4  # batches still train (device-sort path)
    bad = [b for b in batches if b.ids.max() >= tile]
    good = [b for b in batches if b.ids.max() < tile]
    assert len(bad) == 2 and len(good) == 2
    assert all(b.sort_meta is None for b in bad)
    # The spec survives the bad batches: good ones still get host prep.
    assert all(b.sort_meta is not None for b in good)
    assert pipe._sort_meta_spec is not None
    msgs = [r.message for r in caplog.records
            if "vocabulary_size is wrong" in r.message]
    assert len(msgs) == len(bad)  # one warning PER bad batch


def test_sort_meta_transient_failure_disables_once(data_files, caplog,
                                                   monkeypatch):
    """Any OTHER native failure degrades to device sort with ONE warning
    and disables the spec for the rest of the run."""
    import logging

    from fast_tffm_tpu.data import native
    from fast_tffm_tpu.ops import sparse_apply

    def boom(*a, **kw):
        raise OSError("native lib vanished")

    monkeypatch.setattr(native, "sort_meta", boom)
    cfg = _cfg(thread_num=1)
    spec = (cfg.vocabulary_size, sparse_apply.CHUNK, sparse_apply.TILE)
    pipe = BatchPipeline(
        data_files, cfg, epochs=1, shuffle=False, ordered=True,
        sort_meta_spec=spec,
    )
    with caplog.at_level(logging.WARNING):
        batches = list(pipe)
    assert len(batches) == 4
    msgs = [r.message for r in caplog.records
            if "falling back to device sort" in r.message]
    assert len(msgs) == 1
    assert pipe._sort_meta_spec is None


def test_cache_epochs_replays_same_batches_permuted(data_files):
    """cache_epochs: epoch 0 parses, later epochs replay the SAME batches
    (bitwise) in a seeded per-epoch permutation — no re-parse, identical
    coverage."""
    cfg = _cfg(thread_num=1)
    key = lambda b: (b.labels.tobytes(), b.ids.tobytes(), b.vals.tobytes())
    plain = [key(b) for b in BatchPipeline(
        data_files, cfg, epochs=1, shuffle=True, ordered=True)]
    cached = [key(b) for b in BatchPipeline(
        data_files, cfg, epochs=3, shuffle=True, ordered=True,
        cache_epochs=True)]
    assert len(cached) == 3 * len(plain)
    assert cached[:len(plain)] == plain  # epoch 0 is the normal stream
    for e in (1, 2):
        ep = cached[e * len(plain):(e + 1) * len(plain)]
        assert sorted(ep) == sorted(plain)  # same batches...
    assert cached[len(plain):2 * len(plain)] != \
        cached[2 * len(plain):]  # ...different order per epoch


def test_cache_epochs_budget_falls_back_to_reparse(data_files):
    """Blowing the byte budget abandons the cache and re-parses later
    epochs — every epoch still delivers the full stream."""
    cfg = _cfg(thread_num=1)
    got = list(BatchPipeline(
        data_files, cfg, epochs=2, shuffle=True, ordered=True,
        cache_epochs=True, cache_max_bytes=1,
    ))
    n = sum(int(np.sum(b.weights > 0)) for b in got)
    assert n == 2 * 15  # both epochs complete


def test_cache_epochs_ignored_for_single_epoch_and_sharded(data_files):
    cfg = _cfg()
    p1 = BatchPipeline(data_files, cfg, epochs=1, cache_epochs=True)
    assert not p1._cache_epochs
    p2 = BatchPipeline(data_files, cfg, epochs=2, cache_epochs=True,
                       shard=(0, 2))
    assert not p2._cache_epochs
    # A resume position no longer disables the cache: the cached path
    # re-parses epoch 0 to rebuild the replay cache (skip applies to
    # delivery only), so resumed runs replay later epochs from memory.
    p3 = BatchPipeline(data_files, cfg, epochs=2, cache_epochs=True,
                       skip_batches=1)
    assert p3._cache_epochs
