"""Rank-sharded tiered table: per-model-column ownership of the hot map.

``train.tiered.TieredTable`` is host-global: one process plans, migrates,
and checkpoints the ENTIRE logical table, which caps the trainable vocab
at what a single host holds and replicates every migration on every
rank.  This module partitions that work by id range so a fleet can train
a table no single host could (ROADMAP direction 1; the reference
system's parameter-server role, recast for SPMD):

- The logical id space splits into ``S = mesh_model`` contiguous ranges,
  one per MODEL column of the mesh.  Shard ``s`` owns ids
  ``[s*V/S, (s+1)*V/S)`` and hot slots ``[s*H/S, (s+1)*H/S)`` — exactly
  the rows of the ``P(MODEL)``-sharded device hot table that live on
  column ``s``.  Fleet tiering therefore requires every model column's
  devices to belong to ONE process (validated loudly): the process that
  holds a column's device rows is the only one that ever needs that
  shard's cold store.
- Every rank runs :class:`~fast_tffm_tpu.train.tiered.TieredTable`
  instances for ALL ``S`` shards over the SAME global batches (fleet
  tiering requires ``num_blocks == 1``), so slot maps + LRU state evolve
  in lockstep on every rank with zero coordination traffic.  Only the
  shards whose columns this process owns are full instances
  (``rows_enabled``): cold stores, write-back ledger, row fetch,
  ``tiered.*`` telemetry.  The rest are metadata MIRRORS — per-rank host
  bytes, migration H2D/D2H traffic, and telemetry all read ~1/R.
- Device-side migration runs through ``jax.shard_map`` programs
  whose bodies contain no collectives (see ``train.loop``): each column
  loads/gathers only its own rows, so cross-rank migration traffic is
  structurally zero, not merely observed to be.

Checkpointing: each rank exports ONLY its owned shards, with ids
globalized, into per-shard overlay files
(``train.checkpoint.save_tiered_shard``).  Because the payload is keyed
by GLOBAL id and the init descriptors are offset-independent, a restore
re-partitions the union of shard overlays across ANY new shard count —
the elastic-resume contract (R -> R' on super-batch boundaries).
"""

from __future__ import annotations

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.train.tiered import (
    Plan,
    ShardSpec,
    TieredTable,
    _bucket,
    opt_table_names,
)

__all__ = [
    "FleetPlan",
    "FleetShipment",
    "ShardedTiering",
    "column_owners",
    "filter_overlay_for_shard",
    "slice_dense_for_shard",
]


def column_owners(mesh) -> list:
    """The owning process index of each MODEL column of ``mesh``.

    Refuses (loudly) any column whose devices span processes: such a
    column's hot-table rows are REPLICATED across ranks, so no single
    rank could own its cold store — the geometry fleet tiering exists to
    avoid.  The canonical fleet-tiered mesh is ``(data=1, model=R)``
    with one process per column; single-process meshes trivially pass.
    """
    devs = mesh.devices  # [data, model] ndarray of jax devices
    owners = []
    for j in range(devs.shape[1]):
        procs = {d.process_index for d in devs[:, j]}
        if len(procs) != 1:
            raise ValueError(
                f"fleet tiering: mesh model column {j} spans processes "
                f"{sorted(procs)} — its hot rows would be replicated "
                "across ranks.  Use a mesh whose MODEL axis does not "
                "share columns across processes (canonically "
                "mesh_data=1, mesh_model=<process count>)."
            )
        owners.append(procs.pop())
    return owners


def filter_overlay_for_shard(overlay: dict, index: int, count: int,
                             vocab: int) -> dict:
    """Slice a GLOBAL-id overlay (the merged union of a checkpoint's
    shard files, or a legacy single-file overlay) down to one shard's
    id range, with ids localized — the restore half of elastic
    re-sharding."""
    vs = vocab // count
    lo, hi = index * vs, (index + 1) * vs
    out = {}
    for name, payload in overlay.items():
        ids = np.asarray(payload["ids"], np.int64)
        m = (ids >= lo) & (ids < hi)
        out[name] = {
            "ids": ids[m] - lo,
            "rows": np.asarray(payload["rows"])[m],
            "descriptor": payload.get("descriptor"),
        }
    return out


def slice_dense_for_shard(dense_tables: dict, index: int, count: int) -> dict:
    """Row-slice GLOBAL dense warm-start arrays to one shard's range
    (dense checkpoints re-shard trivially: contiguous row slices)."""
    out = {}
    for name, arr in dense_tables.items():
        vs = arr.shape[0] // count
        out[name] = np.ascontiguousarray(arr[index * vs:(index + 1) * vs])
    return out


class FleetPlan:
    """One super-batch's migration plan across all shards.

    ``shard_plans[s]`` is shard ``s``'s local-coordinate
    :class:`~fast_tffm_tpu.train.tiered.Plan`; ``cap_load``/``cap_evict``
    are the GLOBAL bucketed per-column capacities (max over shards,
    power-of-two padded) every rank computes identically from its
    mirrors — they size the ``P(MODEL)``-sharded device plan arrays, so
    all ranks must agree or the collective dispatch would diverge."""

    __slots__ = ("plan_id", "shard_plans", "cap_load", "cap_evict",
                 "n_load_max", "n_evict_max")

    def __init__(self, plan_id: int, shard_plans: tuple, cap_load: int,
                 cap_evict: int, n_load_max: int, n_evict_max: int):
        self.plan_id = plan_id
        self.shard_plans = shard_plans
        self.cap_load = cap_load
        self.cap_evict = cap_evict
        self.n_load_max = n_load_max
        self.n_evict_max = n_evict_max


class FleetShipment:
    """Device-side halves of a FleetPlan (built by the Trainer's put
    path): ``P(MODEL)``-sharded plan arrays where each process supplied
    only its own columns' blocks — non-owned rows never materialize on
    this rank."""

    __slots__ = ("batch", "load_slots", "load_rows", "evict_slots", "plan")

    def __init__(self, batch, load_slots, load_rows, evict_slots,
                 plan: FleetPlan):
        self.batch = batch
        self.load_slots = load_slots
        self.load_rows = load_rows
        self.evict_slots = evict_slots
        self.plan = plan


class ShardedTiering:
    """Coordinator over ``S`` shard-local :class:`TieredTable`
    instances (owned shards full, the rest mirrors — see module
    docstring).  Presents the same transfer-thread / dispatch-loop /
    heartbeat surface the host-global manager does; ``train.loop``
    branches only where device arrays are built."""

    def __init__(self, cfg: FmConfig, num_shards: int, owned,
                 telemetry=None, dense_tables: dict = None,
                 overlay: dict = None):
        if num_shards < 1:
            raise ValueError(f"num_shards={num_shards} must be >= 1")
        self.cfg = cfg
        self.num_shards = num_shards
        self.owned = frozenset(int(s) for s in owned)
        bad = [s for s in self.owned if not 0 <= s < num_shards]
        if bad:
            raise ValueError(
                f"owned shards {bad} outside [0, {num_shards})"
            )
        self.vocab = cfg.vocabulary_size
        self.hot_rows = min(cfg.hot_rows, cfg.vocabulary_size)
        if self.vocab % num_shards or self.hot_rows % num_shards:
            raise ValueError(
                f"vocabulary_size={self.vocab} and effective "
                f"hot_rows={self.hot_rows} must both divide by the tier "
                f"shard count {num_shards}"
            )
        self.vs = self.vocab // num_shards  # per-shard id span
        self.hs = self.hot_rows // num_shards  # per-shard hot slots
        self.dim = cfg.embedding_dim
        self.names = ("table",) + opt_table_names(cfg.optimizer)
        self._oor_occ = 0
        self.tables = []
        for s in range(num_shards):
            mine = s in self.owned
            self.tables.append(TieredTable(
                cfg,
                telemetry=telemetry if mine else None,
                dense_tables=(
                    slice_dense_for_shard(dense_tables, s, num_shards)
                    if mine and dense_tables is not None else None
                ),
                overlay=(
                    filter_overlay_for_shard(
                        overlay, s, num_shards, self.vocab
                    )
                    if mine and overlay is not None else None
                ),
                shard=ShardSpec(s, num_shards, rows_enabled=mine),
            ))
        self.codec = self.tables[0].codec

    # ------------------------------------------------------------------
    # transfer-thread side
    # ------------------------------------------------------------------

    def plan(self, ids: np.ndarray):
        """Remap a GLOBAL super-batch's ids to global hot-slot indices
        and produce per-shard migration plans.  Every shard — owned or
        mirror — plans every super-batch (possibly over zero ids): the
        lockstep that keeps mirrors equal to their owners."""
        H = self.hot_rows
        flat = ids.reshape(-1).astype(np.int64)
        oor = (flat < 0) | (flat >= self.vocab)
        any_oor = bool(oor.any())
        if any_oor:
            self._oor_occ += int(oor.sum())
        owner = np.where(oor, 0, flat // self.vs)
        new_flat = np.empty(flat.shape, np.int32)
        if any_oor:
            new_flat[oor] = np.int32(H)  # device scatter-drop index
        plans = []
        n_load_max = n_evict_max = 0
        for s, t in enumerate(self.tables):
            m = (owner == s) & ~oor if any_oor else owner == s
            local = flat[m] - s * self.vs
            new_local, plan_s = t.plan(local)
            new_flat[m] = new_local + np.int32(s * self.hs)
            plans.append(plan_s)
            n_load_max = max(n_load_max, plan_s.n_load)
            n_evict_max = max(n_evict_max, plan_s.n_evict)
        return new_flat.reshape(ids.shape), FleetPlan(
            plan_id=plans[0].plan_id,
            shard_plans=tuple(plans),
            cap_load=_bucket(max(1, n_load_max)),
            cap_evict=_bucket(max(1, n_evict_max)),
            n_load_max=n_load_max,
            n_evict_max=n_evict_max,
        )

    def local_load_blocks(self, plan: FleetPlan):
        """(slots_block, rows_blocks) for THIS rank's owned columns, in
        column order — the process-local data of the ``P(MODEL)``-sharded
        load arrays.  Slots are column-local with pad ``hs`` (the
        per-column scatter-drop index); rows are zero-padded."""
        cap = plan.cap_load
        slots = []
        rows = [[] for _ in self.names]
        for s in sorted(self.owned):
            p: Plan = plan.shard_plans[s]
            sl = np.full(cap, self.hs, np.int32)
            sl[:p.n_load] = p.load_slots[:p.n_load]
            slots.append(sl)
            for k, r in enumerate(p.load_rows):
                pr = np.zeros((cap, self.dim), np.float32)
                pr[:p.n_load] = r[:p.n_load]
                rows[k].append(pr)
        return (
            np.concatenate(slots),
            tuple(np.concatenate(rs) for rs in rows),
        )

    def local_evict_slots(self, plan: FleetPlan) -> np.ndarray:
        """Column-local evict-slot blocks for owned columns (pad 0 —
        garbage rows beyond each shard's ``n_evict`` are sliced off
        host-side, same contract as the host-global path)."""
        cap = plan.cap_evict
        blocks = []
        for s in sorted(self.owned):
            p: Plan = plan.shard_plans[s]
            ev = np.zeros(cap, np.int32)
            ev[:p.n_evict] = p.evict_slots[:p.n_evict]
            blocks.append(ev)
        return np.concatenate(blocks)

    def cancel_waits(self) -> None:
        for t in self.tables:
            t.cancel_waits()

    def reopen(self) -> None:
        for t in self.tables:
            t.reopen()

    # ------------------------------------------------------------------
    # dispatch-loop side
    # ------------------------------------------------------------------

    def push_writeback(self, shard: int, plan_id: int,
                       dev_rows: tuple) -> None:
        self.tables[shard].push_writeback(plan_id, dev_rows)

    def note_applied(self, plan: FleetPlan) -> None:
        for s in self.owned:
            p: Plan = plan.shard_plans[s]
            if p.n_load:
                t = self.tables[s]
                with t._cv:
                    t.id_of_slot_applied[
                        p.load_slots[:p.n_load]
                    ] = p.load_ids
        # Mirrors keep no applied view: nothing on this rank ever reads
        # their device rows back.

    def sync_from_device(self, host_tables_by_shard: dict) -> None:
        """``host_tables_by_shard[s]`` = np copies of shard ``s``'s
        device hot-table rows (this rank's columns only), ordered like
        ``self.names``."""
        for s in sorted(self.owned):
            self.tables[s].sync_from_device(host_tables_by_shard[s])

    # ------------------------------------------------------------------
    # checkpoint / eval
    # ------------------------------------------------------------------

    def export_shard_overlays(self, host_tables_by_shard: dict) -> dict:
        """{shard -> overlay payload} for OWNED shards, ids globalized —
        the elastic checkpoint unit (one ``tiered.shard{s}of{S}.npz``
        file each; see train.checkpoint)."""
        out = {}
        for s in sorted(self.owned):
            ov = self.tables[s].export_overlay(host_tables_by_shard[s])
            for payload in ov.values():
                payload["ids"] = payload["ids"] + np.int64(s * self.vs)
            out[s] = ov
        return out

    def gather_logical(self, ids: np.ndarray) -> np.ndarray:
        """Current PARAMS rows for logical (global) ids — only legal
        when every touched shard is owned (single-process sharded
        configs; fleet evaluate goes through a checkpoint instead)."""
        flat = np.asarray(ids, np.int64)
        owner = flat // self.vs
        missing = sorted(set(np.unique(owner).tolist()) - set(self.owned))
        if missing:
            raise RuntimeError(
                f"gather_logical needs shards {missing} which live on "
                "other ranks; fleet-tiered evaluation reads a checkpoint, "
                "not live remote state"
            )
        out = np.empty((len(flat), self.dim), np.float32)
        for s in self.owned:
            m = owner == s
            if m.any():
                out[m] = self.tables[s].gather_logical(flat[m] - s * self.vs)
        return out

    def merged_dense(self, host_tables_by_shard: dict) -> list:
        """Full logical arrays (params table first) — requires ALL
        shards owned (single-process sharded configs only)."""
        if len(self.owned) != self.num_shards:
            raise RuntimeError(
                "merged_dense needs every shard's cold store; this rank "
                f"owns {sorted(self.owned)} of {self.num_shards}"
            )
        self.sync_from_device(host_tables_by_shard)
        parts = [self.tables[s].stores for s in range(self.num_shards)]
        return [
            np.concatenate(
                [parts[s][k].to_dense() for s in range(self.num_shards)]
            )
            for k in range(len(self.names))
        ]

    @property
    def dense_save_ok(self) -> bool:
        """Dense-format checkpoints need the merged array: only a rank
        owning EVERY shard (single-process sharded) can write one, and
        only when the stores themselves allow it."""
        return len(self.owned) == self.num_shards and all(
            self.tables[s].dense_save_ok for s in range(self.num_shards)
        )

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    @property
    def stores(self) -> tuple:
        """All cold stores THIS rank holds (owned shards, shard-major) —
        the resource monitor sums their bytes for the per-rank
        ``cold_store_bytes`` gauge."""
        return tuple(
            s for sh in sorted(self.owned) for s in self.tables[sh].stores
        )

    def snapshot(self) -> dict:
        """Per-RANK tiered counters: owned shards summed.  Same schema
        as the host-global snapshot plus the sharding identity keys —
        ``hot_rows``/``vocab`` report this rank's OWNED capacity/span,
        which is what makes the fleet block's per-rank ~1/R claim
        directly readable."""
        snaps = [self.tables[s].snapshot() for s in sorted(self.owned)]
        hit = sum(s["hit_occurrences"] for s in snaps)
        miss = sum(s["miss_occurrences"] for s in snaps)
        total = hit + miss
        return {
            "hot_rows": self.hs * len(self.owned),
            "vocab": self.vs * len(self.owned),
            "resident_rows": sum(s["resident_rows"] for s in snaps),
            "rows_seen": sum(s["rows_seen"] for s in snaps),
            "hit_occurrences": hit,
            "miss_occurrences": miss,
            "hot_hit_frac": round(hit / total, 6) if total else 0.0,
            "rows_loaded": sum(s["rows_loaded"] for s in snaps),
            "rows_evicted": sum(s["rows_evicted"] for s in snaps),
            "writeback_rows": sum(s["writeback_rows"] for s in snaps),
            "oor_occurrences": int(self._oor_occ),
            "cold_store_bytes": sum(s["cold_store_bytes"] for s in snaps),
            "cold_written_rows": sum(
                s["cold_written_rows"] for s in snaps
            ),
            "cold_dtype": self.codec.dtype,
            "cold_bytes_per_row": int(self.codec.bytes_per_row),
            "num_shards": self.num_shards,
            "owned_shards": len(self.owned),
        }

    def health_view(self) -> dict:
        views = [self.tables[s].health_view() for s in sorted(self.owned)]
        seen = sum(v["emb_rows_touched"] for v in views)
        vocab = self.vs * max(1, len(self.owned))
        return {
            "emb_rows_touched": int(seen),
            "emb_row_occupancy": round(seen / vocab, 9),
            "hot_slots_resident": sum(
                v["hot_slots_resident"] for v in views
            ),
        }
