"""Fast ingest-equivalence matrix (tier-1, not slow): raw/line path ×
thread/process workers × cache off/on/prestacked on a tiny synthetic
libsvm file.

Every mode must deliver element-wise IDENTICAL batches in identical
(ordered) delivery order with identical epoch markers — a regression in
any ingest mode (parse content, sequencing, marker placement, cache
replay coverage) fails tier-1 here instead of surfacing as a training
drift on hardware.  The module also pins the two resource guarantees of
the SHM paths: descriptor-only work messages when the inbound ring is
on (raw window bytes never cross the worker queue), and zero leaked
/dev/shm segments once every pipeline in the module has torn down.
"""

import os

import numpy as np
import pytest

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import BatchPipeline, EpochEnd, SuperBatch


def _shm_listing():
    """This process's pipeline segments only: every one carries the
    parent's pid in its tag (procpool.make_shm_tag), so another xdist
    worker's live ring never reads as a leak here.  Untagged ``psm_*``
    segments are out of scope on purpose: procpool creates none (every
    ``SharedMemory(create=True)`` there passes a tag), and one seen in
    /dev/shm cannot be told from another worker's."""
    return {
        n for n in os.listdir("/dev/shm")
        if n.startswith(f"tffm{os.getpid()}p")
    }


@pytest.fixture(scope="module", autouse=True)
def no_leaked_shm_segments():
    """Every test in this module spins up SHM-using pipelines (worker
    result segments + the inbound ring); after they ALL finish, /dev/shm
    must hold nothing new — the tier-1 leak check for procpool's
    unlink-on-every-exit-path contract."""
    before = _shm_listing()
    yield
    leaked = _shm_listing() - before
    assert not leaked, f"leaked /dev/shm segments: {sorted(leaked)}"


def _write_data(path, lines=60):
    rng = np.random.default_rng(7)
    with open(path, "w") as f:
        for _ in range(lines):
            toks = " ".join(
                f"{rng.integers(0, 99)}:{rng.uniform(0, 2):.4f}"
                for _ in range(rng.integers(1, 5))
            )
            f.write(f"{rng.integers(0, 2)} {toks}\n")
    return str(path)


@pytest.fixture(scope="module")
def data_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("matrix")
    return _write_data(d / "d.libsvm")


@pytest.fixture(scope="module")
def big_data_file(tmp_path_factory):
    """Enough lines that window bytes dwarf descriptor bytes — the
    payload-accounting test needs a real margin."""
    d = tmp_path_factory.mktemp("matrix_big")
    return _write_data(d / "big.libsvm", lines=2000)


def _cfg(**kw):
    defaults = dict(
        vocabulary_size=100, batch_size=8, max_features=4, thread_num=2,
        queue_size=4, shuffle_buffer=16,
    )
    defaults.update(kw)
    return FmConfig(**defaults)


def _stream(path, cfg, cache, prestack_k=0, telemetry=None):
    """Flattened delivery: SuperBatch items unpack to their per-batch
    tuples, so streams compare element-wise across storage formats."""
    out = []
    pipe = BatchPipeline(
        [path], cfg, epochs=2, shuffle=True, seed=11, ordered=True,
        cache_epochs=cache, prestack_k=prestack_k, epoch_marks=True,
        telemetry=telemetry,
    )
    for b in pipe:
        if isinstance(b, EpochEnd):
            out.append(("mark", b.epoch))
            continue
        if isinstance(b, SuperBatch):
            sb = b.batch
            for i in range(b.n):
                out.append((
                    sb.labels[i].tobytes(), sb.ids[i].tobytes(),
                    sb.vals[i].tobytes(), sb.fields[i].tobytes(),
                    sb.weights[i].tobytes(),
                ))
            continue
        out.append((
            b.labels.tobytes(), b.ids.tobytes(), b.vals.tobytes(),
            b.fields.tobytes(), b.weights.tobytes(),
        ))
    return out


# mode -> (cache_epochs, prestack_k)
_MODES = {"stream": (False, 0), "cache": (True, 0), "prestack": (True, 3)}


@pytest.mark.parametrize("mode", list(_MODES), ids=list(_MODES))
@pytest.mark.parametrize("fast_ingest", [True, False], ids=["raw", "line"])
def test_process_workers_match_threads(data_file, fast_ingest, mode):
    """parse_processes output is element-wise identical to the
    in-process parser — same batches, same ordered delivery, same epoch
    markers — for every (ingest path × cache storage) combination.
    The procs run exercises the SHM ring on the raw path (ring_slots
    default > 0)."""
    cache, k = _MODES[mode]
    threads = _stream(
        data_file, _cfg(fast_ingest=fast_ingest), cache, prestack_k=k
    )
    procs = _stream(
        data_file, _cfg(fast_ingest=fast_ingest, parse_processes=2),
        cache, prestack_k=k,
    )
    assert threads == procs
    assert threads[-1] == ("mark", 1)  # both epochs end in their marker
    assert ("mark", 0) in threads


def test_cache_replays_epoch0_batches(data_file):
    """Cache on: epoch 1 is a permutation of epoch 0's parsed batches;
    cache off: epoch 1 reshuffles at LINE granularity (different
    batches).  Epoch 0 is byte-identical either way."""
    on = _stream(data_file, _cfg(), True)
    off = _stream(data_file, _cfg(), False)
    m = on.index(("mark", 0))
    assert on[:m + 1] == off[:m + 1]
    e1_on = [x for x in on[m + 1:] if x[0] != "mark"]
    e1_off = [x for x in off[m + 1:] if x[0] != "mark"]
    assert sorted(e1_on) == sorted(on[:m])  # replay: same batch multiset
    assert e1_on != e1_off  # ...but streaming re-mixes lines


def test_prestacked_matches_batch_cache_epoch0_and_multiset(data_file):
    """Prestacked storage changes only the replay PERMUTATION
    granularity: epoch 0 is byte-identical to the batch cache (groups
    are stacked from the same delivered batches), and epoch 1 replays
    the same batch multiset — grouped, so consecutive runs of a group's
    batches stay in epoch-0 order."""
    plain = _stream(data_file, _cfg(), True)
    pre = _stream(data_file, _cfg(), True, prestack_k=3)
    m = plain.index(("mark", 0))
    assert pre[:m + 1] == plain[:m + 1]
    e1_pre = [x for x in pre[m + 1:] if x[0] != "mark"]
    e1_plain = [x for x in plain[m + 1:] if x[0] != "mark"]
    assert sorted(e1_pre) == sorted(e1_plain)
    assert e1_pre != e1_plain  # super-batch vs batch permutation


def test_ring_work_messages_are_descriptor_only(big_data_file):
    """THE zero-copy acceptance check: with the SHM ring on, raw window
    bytes never cross the worker queue — every window lands in a ring
    slot (no fallbacks here: windows fit the slot size) and the pickled
    work messages total a tiny fraction of the window bytes.  With
    ring_slots=0 the same run ships the windows through the queue."""
    tel = obs.Telemetry()
    ringed = _stream(
        big_data_file, _cfg(parse_processes=2, ring_slots=3), False,
        telemetry=tel,
    )
    c = tel.snapshot()["counters"]
    assert c["ingest.ring_windows"] >= 1
    assert c["ingest.ring_fallback_windows"] == 0
    window_bytes = c["ingest.ring_window_bytes"]
    msg_bytes = c["ingest.work_msg_bytes"]
    assert window_bytes > 0
    # Descriptors are slot ids + group sizes (+ the line-path epoch
    # marks); give them 5% headroom over the ~60 KB of window text.
    assert msg_bytes < 0.05 * window_bytes, (msg_bytes, window_bytes)

    tel_off = obs.Telemetry()
    plain = _stream(
        big_data_file, _cfg(parse_processes=2, ring_slots=0), False,
        telemetry=tel_off,
    )
    assert plain == ringed  # ring is a transport, not a semantic
    c_off = tel_off.snapshot()["counters"]
    assert c_off["ingest.ring_windows"] == 0
    # The fallback path pickles every window's bytes through the queue.
    assert c_off["ingest.work_msg_bytes"] > window_bytes


def test_oversized_window_falls_back_to_queue(data_file):
    """A ring whose slots are too small for the window must deliver the
    identical stream through the pickled fallback (counted, never
    wrong).  Forced here by monkeypatching the slot-size estimate down
    to a few bytes."""
    cfg = _cfg(parse_processes=2, ring_slots=2)
    tel = obs.Telemetry()
    pipe = BatchPipeline(
        [data_file], cfg, epochs=2, shuffle=True, seed=11, ordered=True,
        epoch_marks=True, telemetry=tel,
    )
    pipe._ring_slot_bytes = lambda: 32  # every window overflows
    out = []
    for b in pipe:
        if isinstance(b, EpochEnd):
            out.append(("mark", b.epoch))
        else:
            out.append((
                b.labels.tobytes(), b.ids.tobytes(), b.vals.tobytes(),
                b.fields.tobytes(), b.weights.tobytes(),
            ))
    assert out == _stream(data_file, _cfg(), False)
    c = tel.snapshot()["counters"]
    assert c["ingest.ring_windows"] == 0
    assert c["ingest.ring_fallback_windows"] >= 1


def test_worker_crash_raises_and_leaves_no_shm(data_file):
    """Killing a parse worker mid-run surfaces as a RuntimeError (not a
    hang) and the teardown sweep reclaims every tagged segment — the
    'worker crash' leg of the SHM hygiene contract."""
    import multiprocessing as mp

    before = _shm_listing()
    cfg = _cfg(parse_processes=2, queue_size=2, ring_slots=2)
    existing = set(mp.active_children())
    it = iter(BatchPipeline(
        [data_file], cfg, epochs=50, shuffle=True, ordered=True,
    ))
    next(it)
    workers = [p for p in mp.active_children() if p not in existing]
    assert workers, "no spawned parse workers found"
    for w in workers:
        w.kill()
    with pytest.raises(RuntimeError, match="parse worker died"):
        for _ in it:
            pass
    assert _shm_listing() - before == set()
