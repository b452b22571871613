"""Two-tier embedding table: device-resident hot rows over a host cold store.

The flagship config pins ``vocabulary_size`` to what a dense ``[V, D]``
device table (plus its optimizer slots) can afford in device memory.  CTR
vocabularies want 2^28+ rows, but CTR id streams are Zipf-skewed: a small
hot set of rows absorbs almost every occurrence.  This module exploits
that: the device holds a compact HOT table of ``hot_rows`` (H) rows —
params and optimizer slots — while the full logical table lives in host
RAM as a lazily-materialized COLD store.

Division of labor (see EMBEDDING.md for the dataflow diagram):

- :class:`TieredTable` (this module, host-side) owns the logical->hot-slot
  map, occupancy-driven LRU migration planning, the cold stores, and the
  delayed write-back ledger.  ``plan()`` runs in the DevicePrefetcher's
  transfer thread: each stacked super-batch's ids are remapped to hot-slot
  indices, misses are fetched from the cold store, and the resulting
  migration plan ships to the device alongside the batch on the same
  async H2D path — migration hides behind the transfer that already
  happens.
- The fused scan step (train.sparse / ops.sparse_apply) runs UNCHANGED
  against the hot table: it already operates on touched-row streams, and
  a remapped batch is indistinguishable from a small-vocab batch.
- Eviction values come back on a one-dispatch-delayed async D2H read
  (``Trainer._apply_migration`` gathers the evicted slots right after the
  previous dispatch and hands the device arrays to
  :meth:`TieredTable.push_writeback`); the cold store absorbs them once
  the copy lands, never stalling the dispatch loop.

Consistency rules the implementation leans on:

- plans are created in emission order (single transfer thread) and applied
  in the same order (single dispatch loop), so the planning-view slot map
  may run AHEAD of the device while the applied view
  (``id_of_slot_applied``) tracks exactly what the device tables hold;
- an eviction's value is "pending" from plan creation until its D2H lands;
  a re-fetch of a pending id waits for the fill (the dispatch loop never
  waits on the planner, so this cannot deadlock);
- checkpoint/eval sync uses the APPLIED view: unapplied plans' evicted
  rows are still device-resident and are swept with everything else.

Cold-store modes:

- EXACT (small logical tables, <= :data:`EXACT_BYTES_MAX` bytes): the full
  logical array is materialized once via the same jax init the dense path
  uses, so tiered training is element-wise identical to dense training
  (pinned by tests/test_tiered_table.py) and checkpoints in the ordinary
  dense format — tier-layout-independent, interchangeable with dense runs.
- VIRTUAL (V >= 2^26-ish): rows materialize on demand — a deterministic
  per-row hash init plus a sorted sparse overlay of every row ever written
  back, so host memory scales with rows TOUCHED, not V.  Checkpoints use
  the sparse overlay format (train.checkpoint.save_tiered).
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import NamedTuple, Optional

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.ops import quant

log = logging.getLogger(__name__)

# Cold arrays at or below this byte size are materialized EXACTLY via the
# same jax init the dense path uses (bitwise parity with dense training,
# dense-format checkpoints); larger stores use the virtual row-hash init
# with a sparse written-row overlay.  Module attribute so tests can force
# the virtual path at tiny vocabularies.
EXACT_BYTES_MAX = 1 << 28

# slot_of states: >= 0 resident at that hot slot.
_NEVER = -1  # never touched this run/restore: cold value is the row init
_EVICTED = -2  # was resident; latest value lives in (or is bound for) cold


def _bucket(n: int, lo: int = 8) -> int:
    """Round up to a power of two >= lo — migration arrays are padded to
    bucketed lengths so the gather/load jits retrace O(log) times, not
    once per distinct miss count."""
    b = lo
    while b < n:
        b <<= 1
    return b


# ----------------------------------------------------------------------
# Optimizer-state plumbing: which [V, D] tables ride beside the params
# table, and how to rebuild the sparse opt-state pytree around new ones.
# ----------------------------------------------------------------------


def opt_table_names(optimizer: str) -> tuple:
    """Names of the table-shaped optimizer slots, in pytree order."""
    return {"adagrad": ("acc",), "ftrl": ("z", "n"), "sgd": ()}[optimizer]


def get_opt_tables(optimizer: str, opt_state) -> tuple:
    if optimizer == "adagrad":
        return (opt_state.acc.table,)
    if optimizer == "ftrl":
        return (opt_state.z.table, opt_state.n.table)
    return ()


def set_opt_tables(optimizer: str, opt_state, tables: tuple):
    if optimizer == "adagrad":
        return opt_state._replace(acc=opt_state.acc._replace(table=tables[0]))
    if optimizer == "ftrl":
        return opt_state._replace(
            z=opt_state.z._replace(table=tables[0]),
            n=opt_state.n._replace(table=tables[1]),
        )
    return opt_state


def get_opt_scalars(optimizer: str, opt_state) -> dict:
    """The non-table (w0) optimizer slots, as host scalars."""
    if optimizer == "adagrad":
        return {"acc_w0": np.asarray(opt_state.acc.w0)}
    if optimizer == "ftrl":
        return {
            "z_w0": np.asarray(opt_state.z.w0),
            "n_w0": np.asarray(opt_state.n.w0),
        }
    return {}


def set_opt_scalars(optimizer: str, opt_state, scalars: dict, put):
    if optimizer == "adagrad":
        return opt_state._replace(
            acc=opt_state.acc._replace(w0=put(scalars["acc_w0"]))
        )
    if optimizer == "ftrl":
        return opt_state._replace(
            z=opt_state.z._replace(w0=put(scalars["z_w0"])),
            n=opt_state.n._replace(w0=put(scalars["n_w0"])),
        )
    return opt_state


# ----------------------------------------------------------------------
# Cold store: one logical [V, D] f32 array in host RAM
# ----------------------------------------------------------------------


def _hash_uniform(ids: np.ndarray, dim: int, seed: int,
                  scale: float) -> np.ndarray:
    """Deterministic per-row uniform(-scale, scale) init, vectorized.

    splitmix64 over (id * dim + column) xor a seed constant: any row of
    the virtual table is computable without materializing any other row —
    the property the lazy cold store needs (jax.random's table draw can't
    be sliced without materializing [V, D], which is the thing a 2^28+
    vocabulary cannot do).  Not bitwise-equal to the dense jax init; the
    virtual mode only exists where a dense table cannot.
    """
    with np.errstate(over="ignore"):
        x = ids.astype(np.uint64)[:, None] * np.uint64(dim) + np.arange(
            dim, dtype=np.uint64
        )[None, :]
        x ^= np.uint64((seed * 0x9E3779B97F4A7C15 + 1) & 0xFFFFFFFFFFFFFFFF)
        x += np.uint64(0x9E3779B97F4A7C15)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    u = (x >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))
    return ((u * 2.0 - 1.0) * scale).astype(np.float32)


class ColdStore:
    """Host-RAM backing for one logical ``[vocab, dim]`` f32 table.

    Two modes:

    - dense-backed (``from_dense`` / exact init): one real ndarray;
      gather/scatter are plain fancy indexing; ``to_dense`` is free.
    - virtual: ``init_rows(ids) -> [n, dim]`` computes any row on demand
      and a sorted (ids, rows) overlay holds every row ever written.
      Memory scales with written rows, not vocab.

    Storage format: rows live PACKED through an
    :class:`ops.quant.RowCodec` (``cold_dtype``): fp32 is the identity
    codec (bit-exact, the historical behavior), bf16/int8 store
    compact packed rows — encoded on every write (scatter /
    write-back), decoded on every read (gather / hot-load).  The
    overlay machinery never looks inside a row, so it is entirely
    dtype-agnostic; ``nbytes`` reports the real compact footprint.
    """

    def __init__(self, vocab: int, dim: int, descriptor: dict,
                 init_rows=None, dense: Optional[np.ndarray] = None,
                 codec: Optional[quant.RowCodec] = None):
        self.vocab = vocab
        self.dim = dim
        self.descriptor = dict(descriptor)
        self._init_rows = init_rows
        self._codec = codec if codec is not None else quant.RowCodec(
            "fp32", dim
        )
        self._dense = dense
        # Sorted sparse overlay (virtual mode): _ids ascending, _rows[i]
        # is the stored (packed) value of row _ids[i].  Writes land in
        # an unsorted TAIL of (sorted ids, rows) batches first and merge
        # into the main arrays only when the tail outgrows a fraction of
        # them — rebuilding the whole overlay per write-back flush would
        # be O(written_rows) per super-batch (quadratic over a run).
        self._ids = np.empty((0,), np.int64)
        self._rows = self._codec.empty(0)
        self._tail: list = []  # [(sorted unique ids, rows), ...] newest last
        self._tail_n = 0

    @property
    def cold_dtype(self) -> str:
        return self._codec.dtype

    @classmethod
    def from_dense(cls, arr: np.ndarray, descriptor: dict,
                   codec: Optional[quant.RowCodec] = None) -> "ColdStore":
        vocab, dim = arr.shape
        if codec is not None and codec.dtype != "fp32":
            return cls(vocab, dim, descriptor, dense=codec.encode(arr),
                       codec=codec)
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        if not arr.flags.writeable:  # np.asarray(jax_array) is read-only
            arr = arr.copy()
        return cls(vocab, dim, descriptor, dense=arr, codec=codec)

    @property
    def dense_backed(self) -> bool:
        return self._dense is not None

    @property
    def nbytes(self) -> int:
        if self._dense is not None:
            return self._dense.nbytes
        return (
            self._ids.nbytes + self._rows.nbytes
            + sum(i.nbytes + r.nbytes for i, r in self._tail)
        )

    @property
    def written_rows(self) -> int:
        if self._dense is not None:
            return self.vocab
        self._compact()
        return len(self._ids)

    def _overlay(self, out, ids, o_ids, o_rows) -> None:
        """out[k] = decode(o_rows[j]) wherever ids[k] == o_ids[j]
        (o_ids sorted; ``out`` is f32)."""
        if not len(o_ids):
            return
        pos = np.searchsorted(o_ids, ids)
        pos_c = np.minimum(pos, len(o_ids) - 1)
        hit = o_ids[pos_c] == ids
        if hit.any():
            out[hit] = self._codec.decode(o_rows[pos_c[hit]])

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Current f32 value of each logical row (written value, else
        init) — quantized stores dequantize on the way out (the
        hot-load path)."""
        ids = ids.astype(np.int64, copy=False)
        if self._dense is not None:
            # Fancy indexing is already a copy; fp32's decode is the
            # identity on it.
            return self._codec.decode(self._dense[ids])
        out = self._init_rows(ids)
        self._overlay(out, ids, self._ids, self._rows)
        for t_ids, t_rows in self._tail:  # newest last = newest wins
            self._overlay(out, ids, t_ids, t_rows)
        return out

    def scatter(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Write f32 rows (ids unique) into the store — quantized
        stores re-encode on the way in (the write-back path)."""
        if not len(ids):
            return
        ids = ids.astype(np.int64, copy=False)
        if self._dense is not None and self._codec.dtype == "fp32":
            self._dense[ids] = rows
            return
        self._store_packed(
            ids, self._codec.encode(np.asarray(rows, np.float32))
        )

    def _store_packed(self, ids: np.ndarray, packed: np.ndarray) -> None:
        """Write already-packed rows (the overlay-restore path — no
        decode/re-encode round trip, so a checkpointed row restores
        bit-exactly whatever the codec)."""
        if packed.shape[1:] != (self._codec.width,):
            raise ValueError(
                f"packed rows have width {packed.shape[1:]} but this "
                f"{self._codec.dtype} store expects "
                f"[{self._codec.width}]"
            )
        if self._dense is not None:
            self._dense[ids] = packed
            return
        order = np.argsort(ids, kind="stable")
        self._tail.append((
            ids[order].copy(),
            np.ascontiguousarray(packed[order]),
        ))
        self._tail_n += len(ids)
        if self._tail_n > max(4096, len(self._ids) // 2):
            self._compact()

    def _compact(self) -> None:
        """Merge the write tail into the sorted main overlay (newest
        write wins per id) — amortized O(total log total)."""
        if not self._tail:
            return
        all_ids = np.concatenate([self._ids] + [i for i, _ in self._tail])
        all_rows = np.concatenate(
            [self._rows] + [r for _, r in self._tail]
        )
        # Keep the LAST occurrence of each id: unique() keeps the first,
        # so dedupe over the reversed arrays.
        rev_ids = all_ids[::-1]
        u, first = np.unique(rev_ids, return_index=True)
        self._ids = u
        self._rows = np.ascontiguousarray(all_rows[::-1][first])
        self._tail = []
        self._tail_n = 0

    def to_dense(self) -> np.ndarray:
        """The full logical array as f32 (dense checkpoint / merged
        eval); only legal for dense-backed or small-enough virtual
        stores."""
        if self._dense is None:
            if self.vocab * self.dim * 4 > EXACT_BYTES_MAX:
                raise ValueError(
                    f"cold store [{self.vocab}, {self.dim}] is too large "
                    "to materialize densely; use the tiered overlay "
                    "checkpoint format"
                )
            self._compact()
            dense = self._init_rows(np.arange(self.vocab, dtype=np.int64))
            if len(self._ids):
                dense[self._ids] = self._codec.decode(self._rows)
            self._dense = (
                dense if self._codec.dtype == "fp32"
                else self._codec.encode(dense)
            )
            self._ids = np.empty((0,), np.int64)
            self._rows = self._codec.empty(0)
        return self._codec.decode(self._dense)

    def export(self) -> dict:
        """Sparse overlay payload for the tiered checkpoint format.
        ``rows`` is the PACKED storage array (codec-specific width) —
        the descriptor's dtype names the format, and a restore stores
        the packed rows verbatim (no decode/re-encode drift).

        Dense-backed stores export EVERY row (the overlay degenerates to
        the dense slice) — the rank-sharded checkpoint path needs this:
        no single rank can assemble the merged dense array, so each
        shard's store serializes in the overlay format regardless of
        backing.  Single-process dense-backed saves keep using the
        ordinary dense format (``dense_save_ok``)."""
        if self._dense is not None:
            return {
                "ids": np.arange(self.vocab, dtype=np.int64),
                "rows": self._dense.copy(),
            }
        self._compact()
        return {"ids": self._ids.copy(), "rows": self._rows.copy()}

    def import_overlay(self, payload: dict) -> None:
        ids = payload["ids"].astype(np.int64, copy=False)
        if len(ids):
            self._store_packed(
                ids,
                np.asarray(payload["rows"], self._codec.storage_dtype),
            )


def _virtual_descriptor(cfg: FmConfig, name: str) -> dict:
    if name == "table":
        desc = {"kind": "uniform", "seed": cfg.seed,
                "range": cfg.init_value_range}
    elif name in ("acc", "n"):
        desc = {"kind": "const", "value": cfg.adagrad_initial_accumulator}
    elif name == "z":
        denom0 = float(
            (cfg.ftrl_beta + np.sqrt(cfg.adagrad_initial_accumulator))
            / cfg.learning_rate + cfg.ftrl_l2
        )
        desc = {"kind": "ftrl_z", "seed": cfg.seed,
                "range": cfg.init_value_range, "denom0": denom0,
                "l1": cfg.ftrl_l1}
    else:
        raise ValueError(f"unknown store {name!r}")
    # Storage-format identity rides the descriptor (empty for fp32, so
    # pre-quantization checkpoints keep matching byte-for-byte): an
    # overlay written under one cold_dtype refuses to restore under
    # another — its packed rows are not the other format's bytes.
    desc.update(quant.cold_codec(cfg).descriptor())
    return desc


def _virtual_store(cfg: FmConfig, name: str, *, vocab: Optional[int] = None,
                   id_offset: int = 0) -> ColdStore:
    """Virtual cold store over ``vocab`` rows.  ``id_offset`` keys the
    hash init in GLOBAL id space: a rank-sharded store over local ids
    [0, vs) initializes row i exactly like the host-global store
    initializes row ``id_offset + i`` — the property that makes sharded
    and global tiering element-wise identical (and shard overlays
    layout-independent once ids are globalized)."""
    vocab = cfg.vocabulary_size if vocab is None else vocab
    dim = cfg.embedding_dim
    off = np.int64(id_offset)
    desc = _virtual_descriptor(cfg, name)
    if desc["kind"] == "uniform":
        seed, r = desc["seed"], desc["range"]

        def init_rows(ids):
            return _hash_uniform(ids + off, dim, seed, r)
    elif desc["kind"] == "const":
        v = desc["value"]

        def init_rows(ids):
            return np.full((len(ids), dim), v, np.float32)
    else:  # ftrl_z, derived from the params row init (see module note:
        # any params row that ever deviated from init has a written z
        # row beside it, so deriving from the INIT formula is exact).
        seed, r = desc["seed"], desc["range"]
        denom0, l1 = np.float32(desc["denom0"]), np.float32(desc["l1"])

        def init_rows(ids):
            p = _hash_uniform(ids + off, dim, seed, r)
            return -p * denom0 - np.sign(p) * l1
    return ColdStore(vocab, dim, desc, init_rows=init_rows,
                     codec=quant.cold_codec(cfg))


def _exact_stores(cfg: FmConfig, names: tuple,
                  params_table: Optional[np.ndarray],
                  row_range: Optional[tuple] = None) -> dict:
    """Dense-backed stores materialized via the SAME jax init the dense
    trainer uses — bit-identical starting point, pinned by tier-1.

    ``row_range=(lo, hi)`` slices the GLOBAL init down to a rank shard's
    id span: the full table is drawn once (exact mode only exists where
    that fits) and everything outside the shard is dropped, so a sharded
    shard's rows are bitwise the rows a host-global store holds.  A
    provided ``params_table`` is already in the caller's (possibly
    local) space — the optimizer init is elementwise, so no slicing."""
    import jax

    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.train import sparse as sparse_lib

    if params_table is None:
        params = fm.init_params(jax.random.PRNGKey(cfg.seed), cfg)
        params_table = np.asarray(params.table)
        if row_range is not None:
            params_table = params_table[row_range[0]:row_range[1]].copy()
    params = fm.FmParams(w0=np.zeros((), np.float32), table=params_table)
    codec = quant.cold_codec(cfg)
    stores = {
        "table": ColdStore.from_dense(
            params_table, {"kind": "exact", **codec.descriptor()}, codec
        )
    }
    opt_names = tuple(n for n in names if n != "table")
    if opt_names:
        opt = sparse_lib.init_sparse_opt_state(cfg, params)
        for name, tab in zip(opt_names, get_opt_tables(cfg.optimizer, opt)):
            stores[name] = ColdStore.from_dense(
                np.asarray(tab), {"kind": "exact", **codec.descriptor()},
                codec,
            )
    return stores


# ----------------------------------------------------------------------
# Migration plan + manager
# ----------------------------------------------------------------------


class ShardSpec(NamedTuple):
    """Which slice of the logical table a :class:`TieredTable` instance
    manages under rank-sharded tiering (train.tiered_fleet).

    ``index``/``count`` carve the id space into ``count`` contiguous
    ranges; the instance then operates entirely in LOCAL coordinates
    (vocab ``V/count``, hot rows ``H/count``, local ids/slots).  With
    ``rows_enabled=False`` the instance is a metadata MIRROR: it tracks
    the slot map + LRU deterministically (every rank plans every shard
    over identical global batches, so mirrors stay in lockstep with the
    owner at zero communication) but builds no cold stores, fetches no
    rows, and keeps no write-back ledger — per-rank host bytes and
    migration traffic stay ~1/R."""

    index: int = 0
    count: int = 1
    rows_enabled: bool = True


class Plan(NamedTuple):
    """Host-side migration plan for one super-batch (pre-shipping)."""

    plan_id: int
    load_slots: np.ndarray  # [Mp] i32, padded with hot_rows (scatter-drop)
    load_ids: np.ndarray  # [n_load] i64 logical ids (applied-view update)
    load_rows: tuple  # per-store [Mp, D] f32 (pad rows are zeros)
    evict_slots: np.ndarray  # [Ep] i32, padded with 0 (ignored host-side)
    n_load: int
    n_evict: int


class Shipment(NamedTuple):
    """What DevicePrefetcher hands the dispatch loop per super-batch when
    tiering is on: the remapped device batch plus the device-side halves
    of the migration plan (shipped on the same async H2D path)."""

    batch: object  # device super-batch (remapped ids)
    load_slots: object  # device [Mp] i32
    load_rows: tuple  # device per-store [Mp, D] f32
    evict_slots: object  # device [Ep] i32
    load_slots_h: np.ndarray  # host copy for the applied-view update
    load_ids: np.ndarray
    plan_id: int
    n_load: int
    n_evict: int


class TieredTable:
    """Host-side manager of the two-tier table (see module docstring).

    Thread contract: ``plan``/``flush`` run in the transfer thread;
    ``push_writeback``/``note_applied``/``sync_from_device`` run in the
    dispatch loop; ``snapshot`` may run in the heartbeat thread.  One
    condition variable guards all state; only the transfer thread ever
    WAITS on it (for a pending write-back fill), and the fill comes from
    the dispatch loop, which never blocks on the planner — so the wait
    always resolves.
    """

    # Keep this many newest write-back entries unflushed: their D2H may
    # still be in flight, and forcing them would stall the transfer
    # thread on the device.  Anything older is one-dispatch-plus stale
    # and its copy has long landed.
    FLUSH_KEEP = 2

    def __init__(self, cfg: FmConfig, telemetry=None,
                 dense_tables: Optional[dict] = None,
                 overlay: Optional[dict] = None,
                 shard: Optional[ShardSpec] = None):
        from fast_tffm_tpu import obs

        self.cfg = cfg
        self.shard = shard if shard is not None else ShardSpec()
        v_global = cfg.vocabulary_size
        h_global = min(cfg.hot_rows, cfg.vocabulary_size)
        if v_global % self.shard.count or h_global % self.shard.count:
            raise ValueError(
                f"vocabulary_size={v_global} and hot_rows={h_global} must "
                f"both divide by the tier shard count "
                f"{self.shard.count} (contiguous id-range ownership)"
            )
        self.vocab = v_global // self.shard.count
        self.hot_rows = h_global // self.shard.count
        self.id_offset = self.shard.index * self.vocab
        self.rows_enabled = bool(self.shard.rows_enabled)
        self.dim = cfg.embedding_dim
        self.codec = quant.cold_codec(cfg)
        self.names = ("table",) + opt_table_names(cfg.optimizer)
        self._cv = threading.Condition(threading.RLock())
        self.slot_of = np.full(self.vocab, _NEVER, np.int32)
        self.id_of_slot = np.full(self.hot_rows, -1, np.int64)
        # What the DEVICE tables hold right now (advanced by note_applied
        # as the dispatch loop applies plans); the planning view above
        # runs ahead by the in-flight plan depth.
        self.id_of_slot_applied = np.full(self.hot_rows, -1, np.int64)
        self.last_used = np.zeros(self.hot_rows, np.int64)
        self._free_ptr = 0
        self._tick = 0
        self._plan_seq = 0
        # Write-back ledger: plan_id -> entry; entries fill when the
        # dispatch loop hands over the gathered device rows.
        self._entries: dict = {}
        self._entry_q: deque = deque()
        self._pending: dict = {}  # logical id -> (entry, row index)
        # Set by cancel_waits() when the dispatch loop is going away: a
        # transfer thread blocked waiting for a write-back fill must be
        # released (the fill will never come) or shutdown joins forever.
        self._cancelled = False
        # Occurrence-level cache accounting (the tiered block's
        # hot_hit_frac).
        self._hit_occ = 0
        self._miss_occ = 0
        self._oor_occ = 0
        self._rows_loaded = 0
        self._rows_evicted = 0
        self._rows_written_back = 0
        self._seen_rows = 0  # distinct logical ids ever resident
        # Mirrors never touch rows, so their counters must not inflate
        # this rank's tiered.* telemetry — the per-rank numbers are the
        # ~1/R claim tests/test_tiered_fleet.py asserts.
        if not self.rows_enabled:
            telemetry = None
        tel = telemetry if telemetry is not None else obs.NULL
        self._c_hit = tel.counter("tiered.hit_occurrences")
        self._c_miss = tel.counter("tiered.miss_occurrences")
        self._c_load = tel.counter("tiered.rows_loaded")
        self._c_evict = tel.counter("tiered.rows_evicted")
        self._c_wb = tel.counter("tiered.writeback_rows")
        self.stores = self._build_stores(dense_tables, overlay)

    # ------------------------------------------------------------------
    # construction / restore
    # ------------------------------------------------------------------

    def _build_stores(self, dense_tables, overlay) -> tuple:
        cfg = self.cfg
        if not self.rows_enabled:
            return ()
        codec = quant.cold_codec(cfg)
        # Exact-vs-virtual is decided on the GLOBAL table bytes, never
        # the shard slice: all shard counts of the same config must pick
        # the same mode, or an elastic resume would try to restore one
        # format into the other.
        exact = cfg.vocabulary_size * self.dim * 4 <= EXACT_BYTES_MAX
        if dense_tables is not None:
            # Warm start from a dense checkpoint (always small V).  The
            # caller hands arrays already sliced to this shard's id
            # range.  Any missing optimizer store initializes from the
            # RESTORED params — same semantics as the dense path's
            # opt_init on restored params (elementwise, so it works in
            # local coordinates).
            stores = {
                name: ColdStore.from_dense(
                    arr, {"kind": "restored"}, codec
                )
                for name, arr in dense_tables.items()
            }
            missing = [n for n in self.names if n not in stores]
            if missing:
                fresh = _exact_stores(
                    cfg, self.names, dense_tables["table"]
                )
                for n in missing:
                    stores[n] = fresh[n]
            return tuple(stores[n] for n in self.names)
        if exact:
            row_range = (
                None if self.shard.count == 1
                else (self.id_offset, self.id_offset + self.vocab)
            )
            built = _exact_stores(cfg, self.names, None, row_range)
        else:
            built = {
                n: _virtual_store(cfg, n, vocab=self.vocab,
                                  id_offset=self.id_offset)
                for n in self.names
            }
        if overlay is not None:
            for name in self.names:
                payload = overlay[name]
                want = built[name].descriptor
                got = payload.get("descriptor")
                # kind="dense" overlays carry EVERY row's value (a
                # rank-sharded save of a dense-backed store), so they
                # are init-independent and restore onto any store of
                # matching storage format.
                if got is not None and got.get("kind") == "dense":
                    fmt = {k: v for k, v in got.items() if k != "kind"}
                    want_fmt = codec.descriptor()
                    if fmt != want_fmt:
                        raise ValueError(
                            f"tiered checkpoint store {name!r} was packed "
                            f"as {fmt} but this run's cold_dtype expects "
                            f"{want_fmt}"
                        )
                elif got is not None and got != want:
                    raise ValueError(
                        f"tiered checkpoint store {name!r} was written "
                        f"under a different init ({got} != {want}); "
                        "seed/init_value_range/optimizer hyperparams must "
                        "match the run that saved it"
                    )
                built[name].import_overlay(payload)
        return tuple(built[n] for n in self.names)

    @property
    def dense_save_ok(self) -> bool:
        """Whether the merged logical table fits the ordinary dense
        checkpoint format (tier-layout-independent AND dense-run-
        interchangeable)."""
        return all(
            s.dense_backed or s.vocab * s.dim * 4 <= EXACT_BYTES_MAX
            for s in self.stores
        )

    # ------------------------------------------------------------------
    # transfer-thread side: remap + migration planning
    # ------------------------------------------------------------------

    def plan(self, ids: np.ndarray) -> tuple[np.ndarray, Plan]:
        """Remap a super-batch's logical ids to hot-slot indices,
        allocating slots for misses (LRU eviction when the never-used
        pool is exhausted).  Returns (remapped ids, migration plan).

        Runs in the transfer thread; the host work here (np.unique +
        cold gathers) overlaps the previous super-batch's dispatch.
        """
        H, V = self.hot_rows, self.vocab
        flat = ids.reshape(-1)
        oor = (flat < 0) | (flat >= V)
        any_oor = bool(oor.any())
        src = flat[~oor] if any_oor else flat
        u = np.unique(src)
        with self._cv:
            self._flush_entries()
            self._tick += 1
            t = self._tick
            self._plan_seq += 1
            pid = self._plan_seq
            slots_u = self.slot_of[u]
            miss = slots_u < 0
            miss_ids = u[miss].astype(np.int64)
            n_miss = int(miss_ids.size)
            # One fetch serves every occurrence of a missed id in this
            # super-batch, so a miss is counted ONCE per unique id per
            # super-batch; the remaining occurrences are hits.
            self._hit_occ += int(src.size) - n_miss
            self._miss_occ += n_miss
            self._oor_occ += int(flat.size - src.size)
            self._c_hit.add(int(src.size) - n_miss)
            self._c_miss.add(n_miss)
            evict_slots = np.empty((0,), np.int32)
            rows: tuple = ()
            if n_miss:
                if n_miss > H:
                    raise RuntimeError(
                        f"hot_rows={H} is smaller than one super-batch's "
                        f"unique id count ({n_miss}); raise hot_rows or "
                        "shrink steps_per_dispatch*batch_size*max_features"
                    )
                res_slots = slots_u[~miss]
                self.last_used[res_slots] = t
                n_fresh = min(n_miss, H - self._free_ptr)
                new_slots = np.empty(n_miss, np.int32)
                if n_fresh:
                    new_slots[:n_fresh] = np.arange(
                        self._free_ptr, self._free_ptr + n_fresh,
                        dtype=np.int32,
                    )
                    self._free_ptr += n_fresh
                    # Stamp fresh slots NOW: eviction selection below
                    # scans last_used, and a just-allocated slot (still
                    # at its never-used 0) must not be "least recently
                    # used" in the very plan that allocated it.
                    self.last_used[new_slots[:n_fresh]] = t
                n_evict = n_miss - n_fresh
                if n_evict:
                    cand = np.argpartition(
                        self.last_used, n_evict - 1
                    )[:n_evict].astype(np.int32)
                    if (
                        int(self.last_used[cand].max()) >= t
                        or int(self.id_of_slot[cand].min()) < 0
                    ):
                        raise RuntimeError(
                            f"hot_rows={H} cannot hold this super-batch's "
                            "working set: every eviction candidate is in "
                            "use by the current super-batch"
                        )
                    evict_ids = self.id_of_slot[cand].copy()
                    self.slot_of[evict_ids] = _EVICTED
                    if self.rows_enabled:
                        # Mirrors mark _EVICTED (slot-map bookkeeping)
                        # but keep no write-back ledger: the owner rank
                        # captures the values.
                        entry = {
                            "ids": evict_ids, "dev": None, "host": None,
                            "skip": set(),
                        }
                        self._entries[pid] = entry
                        self._entry_q.append(pid)
                        for j, i in enumerate(evict_ids):
                            self._pending[int(i)] = (entry, j)
                    new_slots[n_fresh:] = cand
                    evict_slots = cand
                    self._rows_evicted += n_evict
                    self._c_evict.add(n_evict)
                self._seen_rows += int(
                    np.count_nonzero(self.slot_of[miss_ids] == _NEVER)
                )
                self.slot_of[miss_ids] = new_slots
                self.id_of_slot[new_slots] = miss_ids
                self.last_used[new_slots] = t
                if self.rows_enabled:
                    rows = self._fetch(miss_ids)
                self._rows_loaded += n_miss
                self._c_load.add(n_miss)
            else:
                self.last_used[slots_u] = t
            # Remap: every present id is now resident; OOR occurrences
            # map to H so the device scatter drops their updates — the
            # same "silently dropped" contract the dense path has for
            # ids >= vocabulary_size.
            if any_oor:
                safe = np.where(oor, 0, flat)
                new_flat = np.where(oor, np.int32(H), self.slot_of[safe])
            else:
                new_flat = self.slot_of[flat]
            new_ids = new_flat.astype(np.int32).reshape(ids.shape)
            # Bucket-pad the migration arrays (bounded jit retraces).
            mp = _bucket(max(1, n_miss))
            load_slots = np.full(mp, H, np.int32)
            pad_rows = []
            if n_miss:
                load_slots[:n_miss] = self.slot_of[miss_ids]
                for r in rows:
                    pr = np.zeros((mp, r.shape[1]), np.float32)
                    pr[:n_miss] = r
                    pad_rows.append(pr)
            elif self.rows_enabled:
                pad_rows = [
                    np.zeros((mp, self.dim), np.float32) for _ in self.names
                ]
            ep = _bucket(max(1, len(evict_slots)))
            evict_pad = np.zeros(ep, np.int32)
            evict_pad[:len(evict_slots)] = evict_slots
            return new_ids, Plan(
                plan_id=pid,
                load_slots=load_slots,
                load_ids=miss_ids,
                load_rows=tuple(pad_rows),
                evict_slots=evict_pad,
                n_load=n_miss,
                n_evict=int(len(evict_slots)),
            )

    def _fetch(self, miss_ids: np.ndarray) -> tuple:
        """Cold-store rows for miss_ids, serving ids with an in-flight
        write-back from the pending ledger (waiting for the fill when the
        D2H has not landed yet).  Called under the lock."""
        n = len(miss_ids)
        pend_mask = None
        if self._pending:
            pids = np.fromiter(self._pending.keys(), np.int64,
                               len(self._pending))
            pend_mask = np.isin(miss_ids, pids)
            if not pend_mask.any():
                pend_mask = None
        if pend_mask is None:
            return tuple(s.gather(miss_ids) for s in self.stores)
        cold_ids = miss_ids[~pend_mask]
        outs = [
            np.empty((n, s.dim), np.float32) for s in self.stores
        ]
        if len(cold_ids):
            for out, s in zip(outs, self.stores):
                out[~pend_mask] = s.gather(cold_ids)
        for k in np.nonzero(pend_mask)[0]:
            i = int(miss_ids[k])
            pe = self._pending.pop(i, None)
            if pe is None:
                # A sync/flush from the dispatch loop absorbed this
                # entry into the cold store while we waited on another
                # fill (mid-run checkpoint); the cold value IS the
                # written-back one now.
                row_id = miss_ids[k:k + 1]
                for out, s in zip(outs, self.stores):
                    out[k] = s.gather(row_id)[0]
                continue
            entry, j = pe
            host = self._entry_host(entry)
            for out, hr in zip(outs, host):
                out[k] = hr[j]
            entry["skip"].add(j)
        return tuple(outs)

    def cancel_waits(self) -> None:
        """Release any transfer-thread wait on a write-back fill — the
        dispatch loop is exiting (exception, halt, interrupt) and the
        fill will never come.  The woken wait raises, which surfaces in
        the prefetcher's error channel and lets shutdown join cleanly.
        ``reopen()`` re-arms the manager for a later train() run."""
        with self._cv:
            self._cancelled = True
            self._cv.notify_all()

    def reopen(self) -> None:
        with self._cv:
            self._cancelled = False

    def _entry_host(self, entry) -> list:
        """Host copies of an entry's gathered rows, waiting for the
        dispatch loop's fill if needed.  Called under the lock; the wait
        releases it (Condition), so push_writeback can land."""
        while entry["dev"] is None and not self._cancelled:
            self._cv.wait()
        if entry["dev"] is None:
            raise RuntimeError(
                "tiered write-back wait cancelled: the dispatch loop "
                "exited before filling this plan's eviction rows"
            )
        if entry["host"] is None:
            n = len(entry["ids"])
            entry["host"] = [
                np.asarray(a)[:n] for a in entry["dev"]
            ]
            entry["dev"] = ()  # drop the device references
        return entry["host"]

    def _flush_entries(self, force: bool = False) -> None:
        """Absorb settled write-back entries into the cold stores.  The
        newest FLUSH_KEEP entries stay buffered unless forced (their D2H
        may still be in flight); unfilled entries (plans not yet applied)
        are always left alone — the applied-view sweep covers them."""
        keep = 0 if force else self.FLUSH_KEEP
        while len(self._entry_q) > keep:
            pid = self._entry_q[0]
            entry = self._entries[pid]
            if entry["dev"] is None and entry["host"] is None:
                break  # not yet applied by the dispatch loop
            self._entry_q.popleft()
            del self._entries[pid]
            host = self._entry_host(entry)
            ids = entry["ids"]
            live = np.array(
                [j for j in range(len(ids)) if j not in entry["skip"]],
                np.int64,
            )
            for i in ids[live]:
                pe = self._pending.get(int(i))
                if pe is not None and pe[0] is entry:
                    del self._pending[int(i)]
            if len(live):
                self._rows_written_back += len(live)
                self._c_wb.add(len(live))
                for s, hr in zip(self.stores, host):
                    s.scatter(ids[live], hr[live])

    # ------------------------------------------------------------------
    # dispatch-loop side
    # ------------------------------------------------------------------

    def push_writeback(self, plan_id: int, dev_rows: tuple) -> None:
        """Hand over the device arrays gathered at a plan's evict slots
        (called right after the gather is enqueued; non-blocking)."""
        with self._cv:
            entry = self._entries.get(plan_id)
            if entry is not None:
                entry["dev"] = dev_rows
                self._cv.notify_all()

    def note_applied(self, shipment: Shipment) -> None:
        """Advance the applied view once a plan's loads hit the device."""
        if shipment.n_load == 0:
            return
        with self._cv:
            self.id_of_slot_applied[
                shipment.load_slots_h[:shipment.n_load]
            ] = shipment.load_ids

    def sync_from_device(self, host_tables: list) -> None:
        """Write every device-resident row back into the cold stores
        (checkpoint/eval path).  ``host_tables`` are np copies of the
        CURRENT device hot tables, ordered like ``self.names``.  Uses
        the applied view, so plans still in flight (whose evicted rows
        are still on device) are swept correctly."""
        if not self.rows_enabled:
            raise RuntimeError(
                "sync_from_device on a mirror tier shard: only the owning "
                "rank holds this shard's cold stores"
            )
        with self._cv:
            self._flush_entries(force=True)
            slots = np.nonzero(self.id_of_slot_applied >= 0)[0]
            if len(slots):
                ids = self.id_of_slot_applied[slots]
                for s, t in zip(self.stores, host_tables):
                    s.scatter(ids, t[slots])

    def gather_logical(self, ids: np.ndarray) -> np.ndarray:
        """Current PARAMS rows for logical ids, from the cold store
        (callers sync the hot rows back first — the evaluate path).
        Locked against concurrent write-back flushes."""
        if not self.rows_enabled:
            raise RuntimeError(
                "gather_logical on a mirror tier shard: only the owning "
                "rank holds this shard's cold stores"
            )
        with self._cv:
            return self.stores[0].gather(ids)

    def merged_dense(self, host_tables: list) -> list:
        """Full logical arrays (params table first), cold+hot merged.

        Returns COPIES taken under the lock: the live cold backing keeps
        absorbing write-backs from the transfer thread, and a mid-run
        checkpoint serializing the shared array could capture torn rows.
        """
        self.sync_from_device(host_tables)
        with self._cv:
            return [s.to_dense().copy() for s in self.stores]

    def export_overlay(self, host_tables: list) -> dict:
        """Sparse overlay checkpoint payload.  Virtual stores export
        their written-row overlay under the init descriptor; dense-backed
        stores export EVERY row under ``kind="dense"`` (init-independent
        — the rank-sharded save path, where no rank can write the merged
        dense checkpoint)."""
        self.sync_from_device(host_tables)
        with self._cv:
            out = {}
            for name, s in zip(self.names, self.stores):
                payload = s.export()
                if s.dense_backed:
                    payload["descriptor"] = {
                        "kind": "dense", **self.codec.descriptor()
                    }
                else:
                    payload["descriptor"] = s.descriptor
                out[name] = payload
            return out

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def snapshot(self) -> dict:
        """Host-only counters for heartbeats/results (no device access)."""
        with self._cv:
            total = self._hit_occ + self._miss_occ
            return {
                "hot_rows": self.hot_rows,
                "vocab": self.vocab,
                "resident_rows": int(self._free_ptr),
                "rows_seen": int(self._seen_rows),
                "hit_occurrences": int(self._hit_occ),
                "miss_occurrences": int(self._miss_occ),
                "hot_hit_frac": (
                    round(self._hit_occ / total, 6) if total else 0.0
                ),
                "rows_loaded": int(self._rows_loaded),
                "rows_evicted": int(self._rows_evicted),
                "writeback_rows": int(self._rows_written_back),
                "oor_occurrences": int(self._oor_occ),
                "cold_store_bytes": int(
                    sum(s.nbytes for s in self.stores)
                ),
                "cold_written_rows": int(
                    0 if not self.stores or self.stores[0].dense_backed
                    else self.stores[0].written_rows
                ),
                # Storage-format identity of the cold rows: the dtype
                # string is for report readers (non-numeric values are
                # skipped by /metrics), the bytes-per-row gauge is the
                # compaction factor across dtypes (fp32 = 4 * D).
                "cold_dtype": self.codec.dtype,
                "cold_bytes_per_row": int(self.codec.bytes_per_row),
            }

    def health_view(self) -> dict:
        """Logical-row occupancy for the health record: with tiering on,
        the scan-carry row-touch mask counts HOT SLOTS; the manager sees
        every logical id host-side and reports the logical numbers."""
        with self._cv:
            return {
                "emb_rows_touched": int(self._seen_rows),
                "emb_row_occupancy": round(self._seen_rows / self.vocab, 9),
                "hot_slots_resident": int(self._free_ptr),
            }
