"""Training loop: the `local_train` / `dist_train` engine.

One jitted train step (loss+grad+optimizer+metrics) over a (data, model)
mesh replaces the reference's per-batch ``sess.run(train_op)`` hot loop and
its async PS updates (SURVEY.md §3.1/3.2).  Updates are synchronous — GSPMD
allreduces gradients over ICI — which is a deliberate semantic upgrade from
hogwild PS training (SURVEY.md §7 step 4 notes the convergence difference).

Host-side, batches parse on background threads (data.pipeline) while the
device runs the current step; the donated carry keeps the step fully
async-dispatched.

The hot loop is device-resident: ``steps_per_dispatch`` (K) parsed batches
stack into one [K, ...] super-batch, a transfer thread ships super-batch
n+1 (DevicePrefetcher) while n trains, and ONE dispatch of the
``lax.scan``-fused step (make_scan_train_step) trains all K with no
Python/host round-trips in between.  Logging / validation / save /
profiler cadences and the checkpointed mid-epoch position advance at
K-step granularity; a resume always lands on a super-batch boundary.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fast_tffm_tpu import obs, platform
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.ops import autotune as autotune_lib
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.data.pipeline import (
    BatchPipeline, DevicePrefetcher, EpochEnd,
)
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import checkpoint, metrics as metrics_lib
from fast_tffm_tpu.train import sparse as sparse_lib
from fast_tffm_tpu.train import tiered as tiered_lib
from fast_tffm_tpu.train import tiered_fleet
from fast_tffm_tpu.train.optimizers import make_optimizer

log = logging.getLogger(__name__)

class MetricState(NamedTuple):
    loss_sum: jax.Array  # weighted sum of per-example data losses
    weight_sum: jax.Array
    count: jax.Array  # UNWEIGHTED number of real (weight>0) examples
    auc: metrics_lib.AucState

    @staticmethod
    def zeros() -> "MetricState":
        return MetricState(
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
            metrics_lib.auc_init(),
        )


class TrainState(NamedTuple):
    params: fm.FmParams
    opt_state: tuple
    metrics: MetricState
    step: jax.Array


class NonFiniteGradError(RuntimeError):
    """Raised by ``nan_policy = halt`` when a dispatch produced a
    non-finite (NaN/inf) gradient.  Training stops WITHOUT overwriting
    the checkpoint with poisoned params (periodic saves from before the
    event survive); the metrics stream's final record carries the
    exception type and the health counters."""


class HealthState(NamedTuple):
    """On-device training-health monitors riding the scan carry.

    Updated once per fused-scan step from gradients the step already
    materialized, so the marginal cost is a handful of reductions plus
    the row-touch plane: an elementwise OR of the mask the stream
    writer made where the step holds it (train.sparse.touch_mask), else
    a [B*F] -> [vocab] boolean scatter of the batch's ids (a sort of
    the ids and a scatter of every occurrence: 19.8 ms of a 173.9 ms
    step at B*F = 2.56 M on v5e, PERF.md section 5).  The host reads
    these OUTSIDE the hot path: a one-dispatch-delayed async copy of the
    scalars drives ``nan_policy``, and the occupancy sums are computed
    at logging cadence (never from the heartbeat thread, which must
    stay host-only).
    """

    grad_sq_last: jax.Array  # squared global grad norm, last step
    grad_sq_sum: jax.Array  # running sum over all steps (RMS reporting)
    nonfinite_steps: jax.Array  # int32: steps with any non-finite grad
    first_nonfinite_step: jax.Array  # int32: step index, -1 = never
    # f32 instead of int: totals overflow int32 at scale, and jax's
    # default x64-disabled mode would silently truncate int64.  Exact to
    # 2^24 events per step-increment, which is plenty for a monitor.
    touch_events: jax.Array  # f32: cumulative real feature occurrences
    # uint32[2], cumulative and wrapping: rows the deduped scatter apply
    # wrote, and the occurrences it merged them from; under the
    # hand-sharded entries exchange the merged stream's real entries and
    # the slots merged for them, a step's compact rung or its
    # capacity (zeros on every other apply path).  The host reads the
    # difference of two dispatches, which the wrap leaves exact.
    apply_rows: jax.Array
    # bool[vocab]: rows ever touched by a real (val != 0) occurrence
    # this run; P(model) on a mesh where the writer's mask feeds it
    rows_touched: jax.Array

    @staticmethod
    def zeros(vocab: int) -> "HealthState":
        return HealthState(
            grad_sq_last=jnp.zeros((), jnp.float32),
            grad_sq_sum=jnp.zeros((), jnp.float32),
            nonfinite_steps=jnp.zeros((), jnp.int32),
            first_nonfinite_step=jnp.full((), -1, jnp.int32),
            touch_events=jnp.zeros((), jnp.float32),
            apply_rows=jnp.zeros((2,), jnp.uint32),
            rows_touched=jnp.zeros((vocab,), jnp.bool_),
        )


def health_sharding(cfg: FmConfig, mesh) -> HealthState:
    """Where the trainer places the health carry of the step compiled
    for ``cfg`` on ``mesh``: replicated, but for a ``rows_touched`` fed
    by the stream writer's mask on a mesh (train.sparse.touch_mask),
    which lies as the mask comes out of the step — a model shard's own
    rows on each chip (``P(model)``), replicated over data."""
    rep = NamedSharding(mesh, P())
    shardings = HealthState(**{f: rep for f in HealthState._fields})
    if mesh.size > 1 and cfg.sparse_update and sparse_lib.touch_mask(
            cfg, mesh):
        shardings = shardings._replace(
            rows_touched=NamedSharding(mesh, P(mesh_lib.MODEL_AXIS)))
    return shardings


@jax.named_scope("tffm.loss")
def _metric_update(
    ms: MetricState, scores, labels, weights, loss_type: str
) -> MetricState:
    lsum, wsum = metrics_lib.weighted_loss(scores, labels, weights, loss_type)
    return MetricState(
        loss_sum=ms.loss_sum + lsum,
        weight_sum=ms.weight_sum + wsum,
        count=ms.count + jnp.sum((weights > 0).astype(jnp.float32)),
        auc=metrics_lib.auc_update(ms.auc, scores, labels, weights),
    )


def _tree_grad_health(grads):
    """(grad_sq, nonfinite_count) over a dense gradient pytree."""
    leaves = jax.tree.leaves(grads)
    grad_sq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves
    )
    nonfinite = sum(
        jnp.sum((~jnp.isfinite(g)).astype(jnp.int32)) for g in leaves
    )
    return grad_sq, nonfinite


def make_train_step(cfg: FmConfig, optimizer, with_health: bool = False):
    """Dense train step (optax): full-table optimizer update each step.

    ``with_health=True`` returns ``(state, StepAux(grad_sq, nonfinite),
    scores)`` — the health aux the scan carry accumulates (the dense
    path reduces the full gradient pytree it already materialized) plus
    the step's raw scores, which the quality plane's scan wrapper can
    emit per-step (make_scan_train_step ``with_scores``)."""

    def step(state: TrainState, batch: Batch):
        def loss_fn(params):
            return fm.loss_and_metrics(
                params,
                batch.labels,
                batch.ids,
                batch.vals,
                batch.fields if cfg.field_num else None,
                batch.weights,
                cfg,
                compute_dtype=cfg.compute_jnp_dtype,
            )

        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        ms = _metric_update(
            state.metrics, aux["scores"], batch.labels, batch.weights,
            cfg.loss_type,
        )
        new_state = TrainState(params, opt_state, ms, state.step + 1)
        if with_health:
            return (new_state, sparse_lib.StepAux(*_tree_grad_health(grads)),
                    aux["scores"])
        return new_state

    return step


def make_sparse_train_step(cfg: FmConfig, mesh=None,
                           with_health: bool = False):
    """Sparse train step: optimizer touches only the batch's rows
    (train.sparse — the IndexedSlices path, SURVEY.md §3.2).  The mesh is
    threaded through so the Pallas kernel runs under shard_map (Mosaic
    kernels cannot be auto-partitioned by GSPMD).

    ``lookup = shardmap`` on a multi-device mesh selects the hand-sharded
    step (train.shardmap_step): partial-terms psum instead of row
    gathering, closed-form local backward, dense-delta allreduce."""
    from fast_tffm_tpu.train import shardmap_step

    use_shardmap = (
        cfg.lookup == "shardmap"
        and mesh is not None
        and mesh.size > 1
    )
    if use_shardmap and not shardmap_step.supports_shardmap(cfg, mesh):
        raise ValueError(
            "lookup=shardmap needs optimizer in adagrad/ftrl/sgd, "
            "batch-mode L2, and a vocabulary divisible by "
            f"model_shards*{sparse_lib.sparse_apply.TILE}"
        )

    def step(state: TrainState, batch: Batch, rows_all=None):
        if use_shardmap:
            if rows_all is not None:
                raise ValueError(
                    "prefetched exchange streams do not compose with "
                    "lookup=shardmap"
                )
            out = shardmap_step.sparse_step_shardmap(
                cfg, state.params, state.opt_state, batch, mesh,
                health=with_health,
            )
        else:
            out = sparse_lib.sparse_step(
                cfg, state.params, state.opt_state, batch,
                mesh=mesh, data_axis=mesh_lib.DATA_AXIS,
                health=with_health, rows_all=rows_all,
            )
        params, opt_state, scores = out[0], out[1], out[2]
        ms = _metric_update(
            state.metrics, scores, batch.labels, batch.weights, cfg.loss_type
        )
        new_state = TrainState(params, opt_state, ms, state.step + 1)
        if with_health:
            return new_state, out[3], scores
        return new_state

    return step


def make_health_update(cfg: FmConfig):
    """(health, new_state, batch, aux) -> health, applied once per scan
    step: fold the step's grad aux (a train.sparse.StepAux) into the
    carry and mark the batch's real (val != 0) ids in the row-touch
    plane — by OR-ing in the stream writer's mask where the step made
    one (``aux.touched``: the same rows), else by a scatter of the ids,
    where padded occurrences map to index ``vocab`` and drop out."""
    vocab = cfg.vocabulary_size

    @jax.named_scope("tffm.health")
    def update(health: HealthState, new_state: TrainState, batch: Batch,
               aux) -> HealthState:
        bad = aux.nonfinite > 0
        real = batch.vals.reshape(-1) != 0
        if aux.touched is not None:
            rows_touched = health.rows_touched | aux.touched
        else:
            ids = jnp.where(real, batch.ids.reshape(-1), vocab)
            rows_touched = health.rows_touched.at[ids].set(
                True, mode="drop")
        apply_rows = health.apply_rows
        if aux.apply_rows is not None:
            apply_rows = apply_rows + aux.apply_rows
        this_step = new_state.step - 1  # the step this batch trained
        return HealthState(
            grad_sq_last=aux.grad_sq,
            grad_sq_sum=health.grad_sq_sum + aux.grad_sq,
            nonfinite_steps=(
                health.nonfinite_steps + bad.astype(jnp.int32)
            ),
            first_nonfinite_step=jnp.where(
                bad & (health.first_nonfinite_step < 0),
                this_step.astype(jnp.int32),
                health.first_nonfinite_step,
            ),
            touch_events=(
                health.touch_events + jnp.sum(real, dtype=jnp.float32)
            ),
            apply_rows=apply_rows,
            rows_touched=rows_touched,
        )

    return update


def make_scan_train_step(step_fn, health_update=None,
                         with_scores: bool = False,
                         prefetch_fn=None):
    """Wrap a (state, batch) -> state train step in ``jax.lax.scan`` over
    a stacked super-batch: ONE dispatch trains K steps with zero
    intervening Python/host round-trips (the device-resident hot loop the
    reference built queue-runners for, PAPER.md §2 #6).

    The carry is the TrainState (donated at the jit boundary); xs is a
    Batch whose every leaf carries a leading K axis — including stacked
    host ``sort_meta``, so the per-step tile apply still skips its
    on-device sort.  K is baked into the trace: the jitted wrapper
    retraces per distinct K, so an epoch tail at K' = leftover costs one
    extra compile the first time that K' appears.

    With ``health_update``, ``step_fn`` must return ``(state, aux,
    scores)`` and the wrapper becomes ``(state, health, batches) ->
    (state, health)``: a :class:`HealthState` rides the scan carry
    alongside the TrainState — grad-norm / non-finite / row-touch
    monitors updated on-device every step, read back by the host only
    at dispatch boundaries.  The health carry is deliberately NOT
    donated (it is a separate argument) so the host can keep the
    previous dispatch's scalars alive for its delayed ``nan_policy``
    check without racing buffer donation.

    ``with_scores=True`` (the quality plane, cfg.quality) additionally
    stacks each step's raw scores as the scan's ys and returns
    ``(state, health, scores[K, B])`` — the per-dispatch eval feed the
    windowed online-eval monitor consumes one dispatch delayed (same
    async-D2H discipline as the health scalars).  The scores were
    already computed by every step; emitting them adds one [K, B]
    store, no math — the carry update is identical either way, so
    training stays bitwise-identical with the flag off or on (pinned
    by tests/test_quality.py).

    ``prefetch_fn`` (sparse_exchange_overlap): ``ids[flat] ->
    rows_all`` building the merged cross-rank entries stream for the
    sharded sparse apply.  The stream for step i+1 is a pure function
    of its ids — no dependency on step i's params — so the scan body
    computes it AFTER the step that consumes the carried stream: XLA
    schedules the i+1 all-gather concurrently with step i's rank-local
    apply (the no-bubble overlap).  Step 0's stream is built before
    the scan; the last body's prefetch targets a throwaway duplicate
    of the final batch (its result is discarded with the carry).
    Params are bitwise-identical to the non-overlapped path: the
    stream handed to each step is exactly the one the step would have
    computed inline (pinned by tests).
    """
    if health_update is None:
        if prefetch_fn is not None:
            raise ValueError(
                "exchange-overlap prefetch requires the health-carry "
                "scan (the trainer's only dispatch path)"
            )

        def scan_step(state: TrainState, batches: Batch) -> TrainState:
            def body(carry, batch):
                return step_fn(carry, batch), None

            state, _ = jax.lax.scan(body, state, batches)
            return state

        return scan_step

    def scan_health_step(state: TrainState, health: HealthState,
                         batches: Batch):
        if prefetch_fn is not None:
            # xs gains each step's NEXT ids (last one self-duplicated);
            # the carried stream always matches the batch it trains.
            next_ids = jnp.concatenate(
                [batches.ids[1:], batches.ids[-1:]], axis=0
            )
            streams0 = prefetch_fn(batches.ids[0].reshape(-1))

            def body(carry, xs):
                s, h, streams = carry
                batch, nids = xs
                s2, aux, scores = step_fn(s, batch, streams)
                streams2 = prefetch_fn(nids.reshape(-1))
                carry2 = (s2, health_update(h, s2, batch, aux), streams2)
                return carry2, (scores if with_scores else None)

            (state, health, _), ys = jax.lax.scan(
                body, (state, health, streams0), (batches, next_ids)
            )
            if with_scores:
                return state, health, ys
            return state, health

        def body(carry, batch):
            s, h = carry
            s2, aux, scores = step_fn(s, batch)
            carry2 = (s2, health_update(h, s2, batch, aux))
            return carry2, (scores if with_scores else None)

        (state, health), ys = jax.lax.scan(
            body, (state, health), batches
        )
        if with_scores:
            return state, health, ys
        return state, health

    return scan_health_step


def make_eval_step(cfg: FmConfig):
    def step(params: fm.FmParams, ms: MetricState, batch: Batch) -> MetricState:
        scores = fm.fm_scores(
            params,
            batch.ids,
            batch.vals,
            batch.fields if cfg.field_num else None,
            factor_num=cfg.factor_num,
            field_num=cfg.field_num,
        )
        return _metric_update(
            ms, scores, batch.labels, batch.weights, cfg.loss_type
        )

    return step


@jax.jit
def _zero_share(table: jax.Array) -> jax.Array:
    """Share of a table's coordinates at exactly 0: where FTRL's L1
    holds a weight (one reduction over the table, no copy of it)."""
    return jnp.mean(table == 0, dtype=jnp.float32)


def _finalize_metrics(ms: MetricState, loss_type: str = "logistic") -> dict:
    """Streaming means. The loss key is "logloss" for logistic training and
    "mse" for mse training (plus a loss_type-agnostic "loss" alias).

    ``examples`` is the UNWEIGHTED count of real examples (a weighted run
    used to report weight-sums as examples, inflating/deflating rates);
    ``weight_sum`` carries the loss normalizer separately."""
    wsum = max(float(ms.weight_sum), 1e-12)
    loss = float(ms.loss_sum) / wsum
    out = {
        "loss": loss,
        "auc": float(metrics_lib.auc_finalize(ms.auc)),
        "examples": float(ms.count),
        "weight_sum": float(ms.weight_sum),
    }
    out["mse" if loss_type == "mse" else "logloss"] = loss
    return out


def _config_fingerprint(cfg: FmConfig) -> str:
    """Short stable hash of the FULL config — the run-header record's
    identity, so two metrics files are comparable iff fingerprints match
    (unlike Trainer._data_fingerprint, which names only the input
    stream)."""
    blob = json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _params_template(cfg: FmConfig, param_sh):
    shapes = jax.eval_shape(partial(fm.init_params, cfg=cfg), jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes,
        param_sh,
    )


class Trainer:
    """Drives training per an FmConfig — the `local_train` engine.

    With a multi-device mesh this same class is the `dist_train` engine:
    the only difference is the mesh passed in (and, multi-host, a
    jax.distributed.initialize() call before construction — see
    train.dist).
    """

    def __init__(self, cfg: FmConfig, mesh=None):
        self.cfg = cfg
        # Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR,
        # else the compile_cache_dir knob; neither = no-op): enabled
        # before ANY jit below so restarts replay this run's step/eval
        # compiles from disk instead of re-lowering.
        platform.enable_compile_cache(cfg.compile_cache_dir)
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(cfg)
        # Run-wide telemetry registry, shared by the ingest pipeline, the
        # transfer thread, and the dispatch loop.  Disabled -> every
        # instrument is a shared no-op (zero behavior change).
        self.telemetry = obs.Telemetry(enabled=cfg.telemetry)
        # Causal batch tracer (Chrome-trace spans; obs/trace.py).  Only
        # live when cfg.trace_file names an output — otherwise every
        # span call is a shared no-op, and training is bit-identical.
        # One trace path per process: rank 0 owns the configured path,
        # ranks > 0 suffix theirs (tools/report.py --trace merges the
        # fleet).  With trace_rotate_events set, the tracer dumps and
        # resets at the watermark (trace.0.json, trace.1.json, ...) so
        # multi-hour traced runs never hit the in-memory event cap.
        self._trace_path = cfg.trace_file
        if cfg.trace_file and jax.process_index() > 0:
            self._trace_path = (
                f"{cfg.trace_file}.rank{jax.process_index()}"
            )
        self.tracer = obs.Tracer(
            enabled=bool(cfg.trace_file),
            process_name=f"trainer rank{jax.process_index()}",
            rotate_events=cfg.trace_rotate_events,
            rotate_path=self._trace_path,
        )
        # Input-pipeline position for checkpointed mid-epoch resume.
        self._epoch = 0
        self._batches_done = 0
        self.sparse = bool(cfg.sparse_update) and sparse_lib.supports_sparse(cfg)
        if cfg.sparse_update and not self.sparse:
            log.info(
                "sparse_update unsupported for optimizer=%s l2_mode=%s; "
                "using dense optax path", cfg.optimizer, cfg.l2_mode,
            )
        if self.sparse:
            self.optimizer = None
            self._opt_init_fn = partial(sparse_lib.init_sparse_opt_state, cfg)
        else:
            self.optimizer = make_optimizer(cfg)
            self._opt_init_fn = self.optimizer.init
        # Tiered embedding table (train.tiered): the device trains
        # against a compact HOT table of hot_rows rows; the full logical
        # table lives in a host-RAM cold store and rows migrate per
        # super-batch.  Everything device-side is built from a config
        # whose vocabulary_size is the hot-table size; ingest keeps the
        # LOGICAL vocabulary (parsing, hashing, OOR checks are stream
        # properties, not table-layout properties).
        self.tiered: Optional[tiered_lib.TieredTable] = None
        self._dcfg = cfg
        # Rank-sharded tiering: "shards" partitions the tier manager by
        # model column (tiered_fleet.ShardedTiering) so each rank plans/
        # migrates/checkpoints ONLY its own id range — the geometry that
        # makes fleet-tiered training scale (~1/R host bytes + migration
        # traffic per rank).  Resolved here so every later branch keys on
        # one boolean.
        self._tiering_sharded = False
        self._tier_shards = 1
        self._tier_owned: tuple = ()
        if cfg.table_tiering == "on":
            if not self.sparse:
                raise ValueError(
                    "table_tiering=on requires the sparse update path "
                    "(optimizer in adagrad/ftrl/sgd with batch-mode L2): "
                    "a dense optimizer rewrites every row every step, so "
                    "there is no cold set to keep off-device"
                )
            part = cfg.tiered_partition
            if part == "auto":
                part = "shards" if jax.process_count() > 1 else "global"
            if part == "global" and jax.process_count() > 1:
                raise ValueError(
                    "tiered_partition=global is single-process (the "
                    "hot-slot map is host-global); multi-process tiered "
                    "training needs tiered_partition=shards (or auto)"
                )
            if cfg.lookup == "shardmap":
                raise ValueError(
                    "table_tiering=on does not compose with "
                    "lookup=shardmap yet; use lookup=auto"
                )
            hot = min(cfg.hot_rows, cfg.vocabulary_size)
            if part == "shards":
                # Shard == model column: the owner of a column's device
                # rows is the one process allowed to hold its cold store.
                owners = tiered_fleet.column_owners(self.mesh)
                if mesh_lib.data_partition(self.mesh)[1] != 1:
                    raise ValueError(
                        "tiered_partition=shards requires every process "
                        "to parse the FULL global batch (one host data "
                        "block): the lockstep mirrors only stay equal to "
                        "their owners when all ranks plan identical "
                        "batches.  Use a mesh whose DATA axis does not "
                        "span processes (canonically mesh_data=1, "
                        "mesh_model=<process count>)."
                    )
                n_shards = self.mesh.shape[mesh_lib.MODEL_AXIS]
                if cfg.vocabulary_size % n_shards or hot % n_shards:
                    raise ValueError(
                        f"tiered_partition=shards needs vocabulary_size "
                        f"({cfg.vocabulary_size}) and effective hot_rows "
                        f"({hot}) divisible by the mesh model size "
                        f"({n_shards})"
                    )
                self._tiering_sharded = True
                self._tier_shards = n_shards
                self._tier_owned = tuple(
                    s for s, o in enumerate(owners)
                    if o == jax.process_index()
                )
                if (
                    cfg.validation_files
                    and len(self._tier_owned) != n_shards
                ):
                    raise ValueError(
                        "validation_files with fleet-sharded tiering: "
                        "evaluation needs every shard's cold store, but "
                        f"this rank owns {len(self._tier_owned)} of "
                        f"{n_shards} shards.  Evaluate from the saved "
                        "checkpoint instead (it merges all shards)."
                    )
            self._dcfg = dataclasses.replace(cfg, vocabulary_size=hot)
            if cfg.hot_rows >= cfg.vocabulary_size:
                log.info(
                    "table_tiering=on with hot_rows >= vocabulary_size: "
                    "every row fits the hot table (tiering is a no-op "
                    "beyond the remap)"
                )
        if cfg.batch_size % self.mesh.shape[mesh_lib.DATA_AXIS] != 0:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by data-mesh "
                f"size {self.mesh.shape[mesh_lib.DATA_AXIS]}"
            )

        param_sh = mesh_lib.param_sharding(self.mesh)
        self._param_sh = param_sh
        self._batch_sh = Batch(**mesh_lib.batch_sharding(self.mesh))
        rep = NamedSharding(self.mesh, P())

        params, opt_state = self._init_or_restore(param_sh)
        self.state = TrainState(
            params=params,
            opt_state=opt_state,
            metrics=jax.device_put(MetricState.zeros(), rep),
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
        )

        state_sh = jax.tree.map(lambda x: x.sharding, self.state)
        # Kernel autotune (ops/autotune.py): interaction_impl=auto
        # benchmarks the candidate interaction paths at THIS run's
        # (batch, dim) shapes and promotes the fastest that passes the
        # element-wise parity gate; pins and the legacy knobs resolve
        # with zero measurement.  The decision rewrites the device
        # config's legacy `interaction` field so every step builder
        # keeps its single dispatch point (cfg.interaction_resolved).
        self._autotune: Optional[autotune_lib.Decision] = None
        if self._dcfg.interaction_resolved == "auto":
            self._autotune = autotune_lib.resolve(self._dcfg, context="train")
            self._dcfg = dataclasses.replace(
                self._dcfg, interaction_impl="",
                interaction=self._autotune.interaction,
            )
        # The user-facing name of the impl this run trains with —
        # surfaced in the run header as `kernel_impl`.
        self.kernel_impl = autotune_lib.USER.get(
            self._dcfg.interaction_resolved, self._dcfg.interaction_resolved
        )
        # All device-side step math is built from _dcfg: identical to cfg
        # except that, with tiering on, vocabulary_size is the hot-table
        # size (the step's math never reads the vocab beyond table shape)
        # — and, after an autotune resolution, the measured interaction.
        dcfg = self._dcfg
        # Compute-overlapped sparse exchange: with the sharded apply's
        # "entries" exchange over >1 data shard, the deduped touched-row
        # stream for super-batch step i+1 is a pure function of its ids —
        # so the fused scan can prefetch it (all-gather) concurrently
        # with step i's rank-local apply (see make_scan_train_step).
        # Resolved ONCE from the device config the step actually runs
        # with; "on" on a path that cannot overlap refuses loudly
        # (sparse_lib.overlap_active), never goes silently inert.
        self._overlap_active = False
        if cfg.sparse_exchange_overlap != "off":
            blocked = not self.sparse or (
                cfg.lookup == "shardmap" and self.mesh.size > 1
            )
            if blocked:
                if cfg.sparse_exchange_overlap == "on":
                    raise ValueError(
                        "sparse_exchange_overlap=on requires the sparse "
                        "gather/apply step (optimizer in adagrad/ftrl/"
                        "sgd, lookup != shardmap); this run resolved to "
                        + ("the dense step" if not self.sparse
                           else "lookup=shardmap")
                    )
            else:
                self._overlap_active = sparse_lib.overlap_active(
                    dcfg, self.mesh
                )
        step_fn = (
            make_sparse_train_step(dcfg, self.mesh)
            if self.sparse
            else make_train_step(dcfg, self.optimizer)
        )
        # Visible record of the chosen execution strategy: a silent
        # fallback (e.g. interpret-mode Pallas on an unrecognized
        # platform) is orders of magnitude slower, so surface it once.
        from fast_tffm_tpu.platform import use_interpret

        log.info(
            "step build: sparse=%s apply_mode=%s interaction=%s "
            "interpret=%s backend=%s mesh=%s",
            self.sparse,
            sparse_lib.apply_mode(dcfg, self.mesh) if self.sparse else "dense",
            dcfg.interaction_resolved, use_interpret(), jax.default_backend(),
            dict(self.mesh.shape),
        )
        self._train_step = jax.jit(
            step_fn,
            in_shardings=(state_sh, self._batch_sh),
            out_shardings=state_sh,
            donate_argnums=0,
        )
        # K-step fused dispatch: the same step math under lax.scan over
        # a stacked [K, ...] super-batch, with the HealthState monitors
        # riding the carry (grad-norm, non-finite detection, row-touch
        # mask — updated on-device per step, read back at dispatch
        # boundaries).  train() always dispatches through this
        # (steps_per_dispatch == 1 is a scan of length 1, numerically
        # identical to the single step); _train_step stays for direct
        # single-batch callers (tests, tools/parity_probe.py) and
        # carries no health.
        self._super_batch_sh = Batch(**mesh_lib.super_batch_sharding(self.mesh))
        step_fn_health = (
            make_sparse_train_step(dcfg, self.mesh, with_health=True)
            if self.sparse
            else make_train_step(dcfg, self.optimizer, with_health=True)
        )
        self._health = jax.device_put(
            HealthState.zeros(dcfg.vocabulary_size),
            health_sharding(dcfg, self.mesh),
        )
        self._health_host: dict = {}  # last host-read health scalars
        self._health_step0 = int(self.state.step)  # run-start step base
        health_sh = jax.tree.map(lambda x: x.sharding, self._health)
        # Model-quality plane (obs/quality.py): with cfg.quality on,
        # the fused scan additionally emits each step's scores as the
        # scan ys — the feed for the windowed online-eval monitor,
        # consumed one dispatch delayed exactly like the health
        # scalars.  Multi-host runs skip the eval feed (the per-host
        # view of a globally sharded score array is partial); the
        # ingest-side drift sketches still run per host.  The objects
        # themselves are per-run (created in train()).
        self._with_scores = bool(cfg.quality) and jax.process_count() == 1
        self._quality: Optional[obs.QualityMonitor] = None
        self._quality_sketch: Optional[obs.StreamSketch] = None
        self._last_scores = None
        # Only the TrainState is donated: the un-donated health arrays
        # let the host keep the PREVIOUS dispatch's nonfinite/grad-norm
        # scalars alive for the delayed nan_policy check (a donated
        # carry would invalidate them under the next dispatch).
        scan_out_sh = (state_sh, health_sh)
        if self._with_scores:
            # ys [K, B] shards like the stacked labels it aligns with.
            scan_out_sh = scan_out_sh + (self._super_batch_sh.labels,)
        prefetch_fn = None
        if self._overlap_active:
            prefetch_fn = sparse_lib.sparse_apply.make_entries_prefetch(
                self.mesh, mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS,
                dcfg.vocabulary_size,
            )
            log.info(
                "sparse exchange overlap active: entries streams "
                "prefetch one scan step ahead of the rank-local apply"
            )
        self._scan_health_jit = jax.jit(
            make_scan_train_step(
                step_fn_health, make_health_update(dcfg),
                with_scores=self._with_scores,
                prefetch_fn=prefetch_fn,
            ),
            in_shardings=(state_sh, health_sh, self._super_batch_sh),
            out_shardings=scan_out_sh,
            donate_argnums=0,
        )
        # Resource plane (obs/resource.py): the fused-scan dispatch runs
        # through an AOT compile cache (.lower().compile() keyed on the
        # super-batch's exact shapes/dtypes/structure) so every compile
        # is an explicit, timed, cost-analyzed event the CompileSentinel
        # accounts for — instead of an invisible stall inside jit
        # dispatch.  The documented epoch-tail K' < K compile is
        # whitelisted; anything else (batch-shape drift, a sort-meta
        # presence flip, a foreign K) bumps recompiles_unexpected and
        # warns.  resource_metrics=off skips the cache entirely — the
        # historical implicit-jit path, bit-identical training.
        self._compile_cache: dict = {}
        self._primary_rest = None  # non-leading shape sig of compile #1
        # A short-k compile is whitelisted PROVISIONALLY: a real epoch
        # tail is followed by the EpochEnd marker (or end of stream),
        # so the dispatch loop confirms the boundary and reclassifies
        # the compile as unexpected if any other super-batch follows.
        self._tail_probation = None  # (k, step) awaiting confirmation
        self._sentinel = (
            obs.CompileSentinel(
                telemetry=self.telemetry,
                expected_k=cfg.steps_per_dispatch,
            )
            if cfg.resource_metrics else None
        )
        self._dispatches = 0  # per-run dispatch count (throughput attr.)
        self._run_steps = 0  # per-run step count, visible to the sentinel
        # Shape-derived device-memory estimate: table + optimizer-slot
        # bytes of THIS PROCESS's device state (with tiering on, the hot
        # tables).  Summed over addressable shards with replica dedupe —
        # equal to x.nbytes single-process, and ~1/R per rank for the
        # P(MODEL)-sharded tables of a fleet.  The truth where
        # the backend reports it (memory_stats on TPU); this is the
        # documented CPU fallback, computed once.
        def leaf_bytes(x):
            try:
                shards = x.addressable_shards
            except Exception:  # pragma: no cover - non-Array leaf
                return int(x.nbytes)
            uniq = {}
            for sh in shards:
                key = tuple(
                    (sl.start, sl.stop) for sl in sh.index
                )
                uniq[key] = int(sh.data.nbytes)
            return sum(uniq.values())

        self._state_bytes_est = int(sum(
            leaf_bytes(x) for x in jax.tree.leaves(
                (self.state.params, self.state.opt_state)
            )
        ))
        ms_sh = jax.tree.map(lambda _: rep, MetricState.zeros())
        self._eval_step = jax.jit(
            make_eval_step(cfg),
            in_shardings=(state_sh.params, ms_sh, self._batch_sh),
            out_shardings=ms_sh,
            donate_argnums=1,
        )
        if self.tiered is not None:
            # Migration jits: gather the evicted slots' current rows
            # (async D2H write-back source) and overwrite loaded slots
            # with cold rows (the pad slot index == hot_rows scatter-
            # drops).  Tables keep their row sharding.  The load donates
            # the old tables so the hot-table buffers are reused in
            # place.
            n_tab = 1 + len(tiered_lib.opt_table_names(cfg.optimizer))
            tab_sh = (param_sh.table,) * n_tab
            if self._tiering_sharded:
                # Fleet variant: slot/row plan arrays are P(MODEL)-
                # sharded (each process supplied only its own columns'
                # blocks in _put_super) and the bodies run under
                # shard_map with NO collectives — each column touches
                # only its own rows, so cross-rank migration traffic is
                # structurally zero.  Slots are column-LOCAL with pad
                # == hs (the per-column scatter-drop index).
                mp = mesh_lib.MODEL_AXIS
                tab_spec = (P(mp, None),) * n_tab
                slot_sh = NamedSharding(self.mesh, P(mp))
                row_sh = (param_sh.table,) * n_tab

                def _gather_fn(tables, slots):
                    return tuple(t[slots] for t in tables)

                def _load_fn(tables, slots, rows):
                    return tuple(
                        t.at[slots].set(r, mode="drop")
                        for t, r in zip(tables, rows)
                    )

                self._tier_gather_jit = jax.jit(
                    jax.shard_map(
                        _gather_fn, mesh=self.mesh,
                        in_specs=(tab_spec, P(mp)),
                        out_specs=tab_spec,
                    ),
                    in_shardings=(tab_sh, slot_sh),
                    out_shardings=tab_sh,
                )
                self._tier_load_jit = jax.jit(
                    jax.shard_map(
                        _load_fn, mesh=self.mesh,
                        in_specs=(tab_spec, P(mp), tab_spec),
                        out_specs=tab_spec,
                    ),
                    in_shardings=(tab_sh, slot_sh, row_sh),
                    out_shardings=tab_sh,
                    donate_argnums=0,
                )
            else:
                # Host-global variant: slot/row operands replicated.

                def _gather_fn(tables, slots):
                    return tuple(t[slots] for t in tables)

                def _load_fn(tables, slots, rows):
                    return tuple(
                        t.at[slots].set(r, mode="drop")
                        for t, r in zip(tables, rows)
                    )

                self._tier_gather_jit = jax.jit(
                    _gather_fn,
                    in_shardings=(tab_sh, rep),
                    out_shardings=(rep,) * n_tab,
                )
                self._tier_load_jit = jax.jit(
                    _load_fn,
                    in_shardings=(tab_sh, rep, (rep,) * n_tab),
                    out_shardings=tab_sh,
                    donate_argnums=0,
                )
            self._tiered_eval_jit = None  # built lazily (merged eval)

    def _opt_shardings(self, param_sh, params_template):
        """Sharding for each optimizer-state leaf: table-shaped accumulators
        follow the table's row sharding, everything else is replicated
        (SURVEY.md §7 hard-part 4: optimizer state never gathers)."""
        rep = NamedSharding(self.mesh, P())
        table_shape = params_template.table.shape
        opt_shapes = jax.eval_shape(self._opt_init_fn, params_template)
        return jax.tree.map(
            lambda s: param_sh.table if s.shape == table_shape else rep,
            opt_shapes,
        )

    def _init_or_restore(self, param_sh):
        if self.cfg.table_tiering == "on":
            return self._init_or_restore_tiered(param_sh)
        cfg = self.cfg
        if checkpoint.exists_tiered(cfg.model_file):
            # Refuse loudly rather than silently cold-starting over (or
            # preferring possibly-stale dense dirs beside) a tiered
            # overlay: the two formats carry no shared freshness marker,
            # and the overlay holds a table too large to restore densely.
            raise ValueError(
                f"{cfg.model_file} holds a tiered overlay checkpoint "
                "(written by table_tiering=on at a vocabulary too large "
                "for the dense format); resume it with table_tiering=on, "
                "or point model_file somewhere fresh to train dense"
            )
        if checkpoint.exists_quant(cfg.model_file):
            # Same refusal discipline for the quantized serving format:
            # training warm-starts want full-precision params (and the
            # quantized table carries no optimizer state) — silently
            # cold-starting over it would discard a model.
            raise ValueError(
                f"{cfg.model_file} holds a quantized serving checkpoint "
                "(quant.npz); training cannot warm-start from it — "
                "convert it back to the dense format first "
                "(python -m tools.convert_checkpoint <dir> --to fp32), "
                "or point model_file somewhere fresh"
            )
        template = _params_template(cfg, param_sh)
        opt_sh = self._opt_shardings(param_sh, template)
        opt_init = jax.jit(self._opt_init_fn, out_shardings=opt_sh)
        if checkpoint.exists(cfg.model_file):
            log.info("warm-starting from %s", cfg.model_file)
            params, self._restored_step = checkpoint.restore_params(
                cfg.model_file, template
            )
            params = fm.FmParams(*params)
            opt_shapes = jax.eval_shape(self._opt_init_fn, template)
            opt_template = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                opt_shapes,
                opt_sh,
            )
            opt_state = checkpoint.restore_opt(cfg.model_file, opt_template)
            if opt_state is None:
                opt_state = opt_init(params)
            elif self.sparse and cfg.optimizer == "ftrl":
                params = self._check_ftrl_invariant(params, opt_state)
            return params, opt_state
        self._restored_step = 0
        init = jax.jit(partial(fm.init_params, cfg=cfg), out_shardings=param_sh)
        params = init(jax.random.PRNGKey(cfg.seed))
        return params, opt_init(params)

    def _init_or_restore_tiered(self, param_sh):
        """Build the HOT device state + the host-side TieredTable.

        The hot tables' initial values are placeholders: a slot only
        influences training after a migration load overwrites it with
        its cold row, so any deterministic init works.  The checkpoint
        of record is the LOGICAL table: a tiered overlay
        (checkpoint.restore_tiered) when present, else the ordinary
        dense checkpoint restored to host numpy and used to seed the
        cold store — so a tiered run resumes from a dense run's
        checkpoint (and vice versa, via the merged dense save) with any
        hot_rows.
        """
        cfg, dcfg = self.cfg, self._dcfg
        rep = NamedSharding(self.mesh, P())
        template = _params_template(dcfg, param_sh)
        opt_sh = self._opt_shardings(param_sh, template)
        opt_init = jax.jit(self._opt_init_fn, out_shardings=opt_sh)
        init = jax.jit(
            partial(fm.init_params, cfg=dcfg), out_shardings=param_sh
        )
        params = init(jax.random.PRNGKey(cfg.seed))

        def put_scalar(x):
            return jax.device_put(jnp.asarray(x, jnp.float32), rep)

        if checkpoint.exists_quant(cfg.model_file):
            raise ValueError(
                f"{cfg.model_file} holds a quantized serving checkpoint "
                "(quant.npz); a tiered trainer cannot warm-start from "
                "it — convert it back to the dense format first "
                "(python -m tools.convert_checkpoint <dir> --to fp32)"
            )
        overlay = checkpoint.restore_tiered(cfg.model_file)
        if overlay is not None:
            step, scalars, stores = overlay
            self._restored_step = step
            log.info(
                "warm-starting tiered table from overlay checkpoint %s "
                "(step %d)", cfg.model_file, step,
            )
            self.tiered = self._make_tier_manager(overlay=stores)
            params = params._replace(w0=put_scalar(scalars["w0"]))
            opt_state = tiered_lib.set_opt_scalars(
                cfg.optimizer, opt_init(params), scalars, put_scalar
            )
            return params, opt_state
        if checkpoint.exists(cfg.model_file):
            log.info(
                "warm-starting tiered table from dense checkpoint %s",
                cfg.model_file,
            )
            # Restore to HOST numpy at the logical shape (templates
            # without shardings), never materializing on device.
            np_tmpl = jax.eval_shape(
                partial(fm.init_params, cfg=cfg), jax.random.PRNGKey(0)
            )
            np_params, self._restored_step = checkpoint.restore_params(
                cfg.model_file, np_tmpl
            )
            np_params = fm.FmParams(*np_params)
            opt_np = checkpoint.restore_opt(
                cfg.model_file, jax.eval_shape(self._opt_init_fn, np_tmpl)
            )
            if opt_np is not None and cfg.optimizer == "ftrl":
                # Same contract as the dense path's _check_ftrl_invariant:
                # the sparse FTRL applies rely on w == ftrl_solve(z, n),
                # so a table edited outside train.sparse is loudly
                # normalized before it seeds the cold store.
                np_params = self._ftrl_normalize_np(np_params, opt_np)
            dense_tables = {"table": np.asarray(np_params.table)}
            params = params._replace(w0=put_scalar(np_params.w0))
            # Scalar (w0) optimizer slots: restored when present, else
            # derived from the restored w0 — the same thing the dense
            # path's opt_init-on-restored-params does.
            opt_state = opt_init(params)
            if opt_np is not None:
                for name, tab in zip(
                    tiered_lib.opt_table_names(cfg.optimizer),
                    tiered_lib.get_opt_tables(cfg.optimizer, opt_np),
                ):
                    dense_tables[name] = np.asarray(tab)
                opt_state = tiered_lib.set_opt_scalars(
                    cfg.optimizer, opt_state,
                    tiered_lib.get_opt_scalars(cfg.optimizer, opt_np),
                    put_scalar,
                )
            self.tiered = self._make_tier_manager(
                dense_tables=dense_tables
            )
            return params, opt_state
        self._restored_step = 0
        self.tiered = self._make_tier_manager()
        return params, opt_init(params)

    def _make_tier_manager(self, dense_tables=None, overlay=None):
        """The tier manager this run's partition mode calls for: the
        host-global :class:`tiered_lib.TieredTable`, or (tiered_partition
        = shards) the rank-sharded coordinator — restore payloads are
        GLOBAL either way (the coordinator slices per shard itself, which
        is what makes checkpoints elastic across shard counts)."""
        if self._tiering_sharded:
            return tiered_fleet.ShardedTiering(
                self.cfg, self._tier_shards, self._tier_owned,
                telemetry=self.telemetry, dense_tables=dense_tables,
                overlay=overlay,
            )
        return tiered_lib.TieredTable(
            self.cfg, telemetry=self.telemetry,
            dense_tables=dense_tables, overlay=overlay,
        )

    def _ftrl_normalize_np(self, np_params, opt_np):
        """Host-side mirror of :meth:`_check_ftrl_invariant` for the
        tiered warm start (the restored table lives in host numpy on its
        way into the cold store, never on device)."""
        cfg = self.cfg
        solve = partial(
            sparse_lib.sparse_apply.ftrl_solve,
            lr=cfg.learning_rate, l1=cfg.ftrl_l1, l2=cfg.ftrl_l2,
            beta=cfg.ftrl_beta,
        )
        expect = fm.FmParams(
            w0=np.asarray(solve(jnp.asarray(opt_np.z.w0),
                                jnp.asarray(opt_np.n.w0))),
            table=np.asarray(solve(jnp.asarray(opt_np.z.table),
                                   jnp.asarray(opt_np.n.table))),
        )
        dev = max(
            float(np.max(np.abs(expect.w0 - np.asarray(np_params.w0)))),
            float(np.max(np.abs(expect.table - np.asarray(np_params.table)))),
        )
        if dev <= 1e-6:
            return np_params
        log.warning(
            "warm-started FTRL params violate w == ftrl_solve(z, n) "
            "(max |dev| %.3g) — the table was edited outside "
            "train.sparse.  Normalizing before seeding the tiered cold "
            "store, matching the dense restore path.", dev,
        )
        return expect

    def _check_ftrl_invariant(self, params, opt_state):
        """Enforce the FTRL closed-form invariant on a warm start.

        Every sparse FTRL path maintains ``w == ftrl_solve(z, n)``, and
        the compact-K2 tile apply RELIES on it: compact sweeps skip
        untouched rows while the full sweep recomputes them, and the two
        only agree because recompute == stored value (ops.sparse_apply.
        ftrl_apply).  A checkpoint whose table was edited outside
        train.sparse would otherwise drift silently, sweep-dependently.
        Restore-time normalization makes the violation loud and fixes it:
        ``w = ftrl_solve(z, n)`` is a no-op for invariant-respecting
        checkpoints (our own, and fresh z inits) and canonicalizes the
        rest.
        """
        cfg = self.cfg
        solve = jax.jit(
            partial(
                sparse_lib.sparse_apply.ftrl_solve,
                lr=cfg.learning_rate, l1=cfg.ftrl_l1, l2=cfg.ftrl_l2,
                beta=cfg.ftrl_beta,
            )
        )
        expect = fm.FmParams(
            w0=solve(opt_state.z.w0, opt_state.n.w0),
            table=solve(opt_state.z.table, opt_state.n.table),
        )
        dev = max(
            float(jnp.max(jnp.abs(expect.w0 - params.w0))),
            float(jnp.max(jnp.abs(expect.table - params.table))),
        )
        if dev <= 1e-6:
            return params  # invariant holds; keep the restored bits
        log.warning(
            "warm-started FTRL params violate w == ftrl_solve(z, n) "
            "(max |dev| %.3g) — the table was edited outside train.sparse. "
            "Normalizing w = ftrl_solve(z, n) so the compact-K2 apply "
            "stays sweep-independent.", dev,
        )
        return expect

    def _scan_train_step(self, state: TrainState, batches: Batch):
        """One fused K-step dispatch (the hot-loop entry point).

        Keeps the historical ``(state, batches) -> state`` surface —
        ``benchmarks/drivers/train.py`` and the resume tests wrap exactly
        this — while threading the health carry through ``self._health``
        (monitors never change the TrainState math, so scan parity with
        K single ``_train_step`` calls stays bitwise).  With the
        resource plane on, dispatch goes through the AOT compile cache
        so the compile sentinel sees every (re)compilation; the
        executable is the same lowering jit would have produced, so the
        math is identical either way."""
        if self._sentinel is not None:
            fn = self._compiled_scan(state, batches)
        else:
            fn = self._scan_health_jit
        if self._with_scores:
            state, self._health, self._last_scores = fn(
                state, self._health, batches
            )
        else:
            state, self._health = fn(state, self._health, batches)
        return state

    def _compiled_scan(self, state: TrainState, batches: Batch):
        """AOT compile cache for the fused-scan step.

        Keyed on the super-batch's pytree structure + per-leaf
        shape/dtype (structure matters: a sort_meta that flips between
        present and None retraces, and that flip is exactly a silent
        recompile worth flagging).  A miss compiles explicitly
        (``.lower().compile()``), timed and cost-analyzed for the
        sentinel.  Expected compiles: the first ever (startup), and an
        epoch-tail K' < steps_per_dispatch whose non-leading shapes
        match the first compile's — whitelisted provisionally, then
        confirmed by the dispatch loop (an epoch boundary must follow;
        see _resolve_tail_probation).  A compile error (HBM, VMEM,
        tiling) raises: re-dispatching through plain jit would only
        hit the same compiler with the cause hidden."""
        leaves, treedef = jax.tree_util.tree_flatten(batches)
        key = (treedef, tuple((x.shape, str(x.dtype)) for x in leaves))
        fn = self._compile_cache.get(key)
        if fn is not None:
            return fn
        k = int(batches.labels.shape[0])
        rest = tuple(x.shape[1:] for x in leaves)
        t0 = time.perf_counter()
        # The sentinel reads the scope table from this executable's own
        # text: a persistent-cache entry of a step with other scopes
        # must not stand in for it.
        with self.tracer.span("train.compile", args={"k": k}), \
                obs.trace_span("tffm:compile"), \
                platform.metadata_in_cache_key():
            fn = self._scan_health_jit.lower(
                state, self._health, batches
            ).compile()
        wall = time.perf_counter() - t0
        if self._primary_rest is None:
            expected = True  # startup compile (whatever its K)
            self._primary_rest = rest
        else:
            expected = (
                rest == self._primary_rest
                and k <= self._sentinel.expected_k
            )
            if expected and k < self._sentinel.expected_k:
                # Provisional: only a real epoch tail earns the
                # whitelist.  _resolve_tail_probation (dispatch loop)
                # checks that an epoch boundary actually follows this
                # super-batch and reclassifies if not.
                self._tail_probation = (k, self._run_steps)
        self._sentinel.record(
            wall, k, expected, cost=self._cost_of(fn),
            step=self._run_steps, compiled=fn,
        )
        self._compile_cache[key] = fn
        return fn

    def _resolve_tail_probation(self, item) -> None:
        """Confirm or refute a provisionally-whitelisted short-k
        compile with what the pipeline delivered NEXT: an EpochEnd
        marker or end of stream (``None``) confirms the epoch tail;
        another super-batch means the stream is emitting short groups
        mid-epoch — the drift class the sentinel exists to flag."""
        if self._tail_probation is None:
            return
        k, step = self._tail_probation
        self._tail_probation = None
        if item is not None and not isinstance(item, EpochEnd):
            self._sentinel.reclassify_unexpected(k, step)

    @staticmethod
    def _cost_of(compiled) -> dict:
        """FLOPs / bytes from the compiled executable's XLA analyses.
        Best-effort: backends disagree on what they report (and older
        jax returns cost_analysis as a one-element list), so absent
        numbers are simply omitted."""
        out: dict = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                if ca.get("flops"):
                    out["flops"] = float(ca["flops"])
                if ca.get("bytes accessed"):
                    out["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:  # noqa: BLE001 - analysis is optional
            pass
        try:
            ma = compiled.memory_analysis()
            for attr, name in (
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("argument_size_in_bytes", "argument_bytes"),
            ):
                v = int(getattr(ma, attr, 0) or 0)
                if v:
                    out[name] = v
        except Exception:  # noqa: BLE001 - analysis is optional
            pass
        return out

    def _device_mem(self) -> dict:
        """Device-memory figures for the resource block: the backend's
        allocator stats where supported (an allocator query, not a
        device sync — safe at heartbeat cadence), else only the
        shape-derived estimate computed at construction."""
        out: dict = {}
        try:
            stats = jax.local_devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 - backend drift
            stats = None
        if stats:
            if stats.get("bytes_in_use") is not None:
                out["device_bytes_in_use"] = int(stats["bytes_in_use"])
            if stats.get("peak_bytes_in_use") is not None:
                out["device_peak_bytes"] = int(
                    stats["peak_bytes_in_use"]
                )
        out["device_bytes_est"] = self._state_bytes_est
        return out

    def _resource_block(self, stages: dict, wall: float) -> dict:
        """The ``resource`` record block (flat, numeric): process RSS,
        the component byte ledger (read from the same telemetry gauges
        their owners maintain), device memory, and the compile
        sentinel's counters + throughput attribution.  Host-side only —
        callable from heartbeat/status threads."""
        rss, peak = obs.read_rss()
        gauges = (stages or {}).get("gauges") or {}

        def comp(name: str) -> int:
            try:
                return max(0, int(gauges.get(name, 0) or 0))
            except (TypeError, ValueError):
                return 0

        out = {
            "rss_mb": round(rss / (1 << 20), 1),
            "peak_rss_mb": round(peak / (1 << 20), 1),
            # Process vitals the incident/alert plane watches: run
            # uptime (alert alias `uptime_s`) and the open-descriptor
            # count from /proc/self/fd (alias `open_fds`) — a leaking
            # fd ledger is the classic slow-burn incident.  The fd key
            # is omitted where /proc is unavailable.
            "uptime_s": round(wall, 3),
        }
        fds = obs.read_open_fds()
        if fds >= 0:
            out["open_fds"] = fds
        if self.telemetry.enabled:
            # The owner-maintained gauges are no-op instruments when
            # telemetry is off — a hard 0 next to a real RSS would be
            # a lying ledger, so the keys are OMITTED (report.py
            # prints n/a, /metrics emits no series).
            out["ring_bytes"] = comp("ingest.ring_bytes")
            out["staging_bytes"] = comp("prefetch.staging_bytes")
            out["cache_bytes"] = comp("ingest.cache_bytes")
        # Trainer-owned components read directly (no extra gauge —
        # a registered sample would duplicate the same number in
        # every /metrics scrape): cold-store nbytes are plain int
        # attributes, the tracer property takes its own lock.
        out["cold_store_bytes"] = (
            int(sum(s.nbytes for s in self.tiered.stores))
            if self.tiered is not None else 0
        )
        out["trace_buffer_bytes"] = int(self.tracer.buffer_bytes)
        out.update(self._device_mem())
        snap = self._sentinel.snapshot()
        out.update(snap)
        flops = snap.get("flops_per_dispatch", 0.0)
        if flops and wall > 0 and self._dispatches:
            # Model FLOP/s from the steady-state dispatch's compile-time
            # cost analysis (epoch tails run fewer flops, so this is a
            # mild overestimate on short epochs — attribution, not
            # billing).
            out["model_flops_per_s"] = round(
                flops * self._dispatches / wall, 1
            )
        return out

    def _ondemand_profile(self, secs: float) -> str:
        """/profile route backend: one jax.profiler window into the run
        dir.  The StatusServer's lock is the one-at-a-time guard; a
        clash with the config-driven profiler (profile_dir) raises and
        surfaces as the route's 500."""
        out = self._profile_capture_dir
        jax.profiler.start_trace(out)
        try:
            time.sleep(secs)
        finally:
            jax.profiler.stop_trace()
        log.info("on-demand profiler capture (%.1fs) written to %s",
                 secs, out)
        writer = getattr(self, "_metrics_writer", None)
        if writer is not None:
            # The stream records that (and when) a capture perturbed
            # the run — a profiler window shows up as a step-time blip
            # that would otherwise read as a real regression.
            writer.write({
                "record": "profile",
                "time": time.time(),
                "secs": float(secs),
                "profile_dir": out,
            })
        return out

    def _reset_health(self) -> None:
        """Fresh per-run health carry (mirrors telemetry.reset).

        ``state.step`` is instance-cumulative (a second train() on a
        warm Trainer keeps counting), so the run's starting step is
        pinned here: health reporting divides by PER-RUN steps and
        rebases ``first_nonfinite_step`` to match the per-run ``step``
        every other record carries."""
        self._health = jax.device_put(
            HealthState.zeros(self._dcfg.vocabulary_size),
            health_sharding(self._dcfg, self.mesh),
        )
        self._health_step0 = int(self.state.step)

    def _health_summary(self, exact: bool = False) -> dict:
        """Host-side view of the health carry for records/results.

        ``exact=False`` (heartbeat path) reports only the cached scalars
        the dispatch loop already read back — never a device readback
        from the heartbeat thread.  ``exact=True`` (log cadence / final)
        syncs the scalars and computes the row-occupancy sums on device.
        """
        out = dict(self._health_host)
        if exact:
            try:
                h = self._health
                step0 = getattr(self, "_health_step0", 0)
                steps = max(1, int(self.state.step) - step0)
                rows = int(jnp.sum(h.rows_touched))
                vocab = self._dcfg.vocabulary_size
                first_nf = int(h.first_nonfinite_step)
                out.update({
                    "grad_norm": round(
                        float(jnp.sqrt(h.grad_sq_last)), 6
                    ),
                    "grad_norm_rms": round(
                        float(jnp.sqrt(h.grad_sq_sum / steps)), 6
                    ),
                    "nonfinite_steps": int(h.nonfinite_steps),
                    # Rebased to the per-run step every record carries.
                    "first_nonfinite_step": (
                        first_nf - step0 if first_nf >= 0 else -1
                    ),
                    "emb_rows_touched": rows,
                    "emb_row_occupancy": round(rows / vocab, 6),
                    "emb_touch_events": float(h.touch_events),
                })
                if self.cfg.optimizer == "ftrl" and self.tiered is None:
                    # what a user of an L1-sparse model watches (gauge
                    # train.ftrl_zero_frac); Adagrad runs no such pass,
                    # nor a tiered table, whose device table is the hot
                    # slots and not the logical one
                    zero = float(_zero_share(self.state.params.table))
                    out["ftrl_zero_frac"] = zero
                    self.telemetry.gauge("train.ftrl_zero_frac").set(zero)
                if self.tiered is not None:
                    # The scan-carry mask counts HOT SLOTS under
                    # tiering; the manager sees every logical id
                    # host-side and overrides with logical occupancy.
                    out.update(self.tiered.health_view())
                self._health_host = dict(out)
            except Exception:  # pragma: no cover - wedged device
                pass  # crash path: serve whatever was cached
        return out

    def _put(self, batch: Batch, want_meta: bool = True) -> Batch:
        spec = self._sort_meta_spec() if want_meta else None
        if spec is not None and batch.sort_meta is None:
            from fast_tffm_tpu.data import native as native_mod

            try:
                batch = batch._replace(
                    sort_meta=native_mod.sort_meta(batch.ids, *spec)
                )
            except native_mod.OutOfRangeIdsError as e:
                # Data/vocabulary_size integrity bug — same policy as the
                # pipeline workers: warn EVERY bad batch and keep the
                # spec (the device-sort path silently drops updates for
                # out-of-range ids, so this must not go quiet).
                log.warning(
                    "host sort_meta rejected a batch (%s); the input "
                    "data or vocabulary_size is wrong", e,
                )
            except Exception as e:
                # Lib unavailable (no g++?) or a real sort_meta bug: the
                # device-sort path is always correct, so train on — but
                # say so, or a ~11 ms/step regression has no trail.
                log.warning(
                    "host_sort disabled: native sort_meta failed (%s)", e
                )
                self._meta_spec = None
        return mesh_lib.shard_batch(batch, self.mesh)

    def _put_super(self, batch: Batch):
        """Ship a stacked [K, ...] super-batch — DevicePrefetcher's put_fn,
        called from the transfer thread so the H2D copies overlap the
        previous super-batch's training.  Host sort_meta is attached by
        the pipeline workers (sort_meta_spec); no fallback computation
        here — a meta-less stack trains through the device-sort path.

        With tiering on, this is where migration happens: the batch's
        logical ids are remapped to hot-slot indices (allocating slots
        for misses, fetching their cold rows) and the migration plan's
        device halves ship on the same async H2D path as the batch —
        the dispatch loop receives a :class:`tiered_lib.Shipment`.
        """
        if self.tiered is None:
            return mesh_lib.shard_super_batch(batch, self.mesh)
        if self._tiering_sharded:
            # Fleet tiering: every rank remaps the SAME global batch
            # through its lockstep shard mirrors, then materializes the
            # P(MODEL)-sharded plan arrays from PROCESS-LOCAL blocks —
            # each rank stages only its own columns' cold rows, so
            # migration H2D is ~1/R per rank by construction.
            new_ids, fplan = self.tiered.plan(batch.ids)
            batch = batch._replace(ids=new_ids, sort_meta=None)
            dev = mesh_lib.shard_super_batch(batch, self.mesh)
            slots_h, rows_h = self.tiered.local_load_blocks(fplan)
            evict_h = self.tiered.local_evict_slots(fplan)
            S = self.tiered.num_shards
            dim = self.tiered.dim
            slot_sh = NamedSharding(self.mesh, P(mesh_lib.MODEL_AXIS))
            row_sh = NamedSharding(
                self.mesh, P(mesh_lib.MODEL_AXIS, None)
            )
            return tiered_fleet.FleetShipment(
                batch=dev,
                load_slots=jax.make_array_from_process_local_data(
                    slot_sh, slots_h, (S * fplan.cap_load,)
                ),
                load_rows=tuple(
                    jax.make_array_from_process_local_data(
                        row_sh, r, (S * fplan.cap_load, dim)
                    )
                    for r in rows_h
                ),
                evict_slots=jax.make_array_from_process_local_data(
                    slot_sh, evict_h, (S * fplan.cap_evict,)
                ),
                plan=fplan,
            )
        new_ids, plan = self.tiered.plan(batch.ids)
        batch = batch._replace(ids=new_ids, sort_meta=None)
        dev = mesh_lib.shard_super_batch(batch, self.mesh)
        rep = NamedSharding(self.mesh, P())
        return tiered_lib.Shipment(
            batch=dev,
            load_slots=jax.device_put(plan.load_slots, rep),
            load_rows=tuple(
                jax.device_put(r, rep) for r in plan.load_rows
            ),
            evict_slots=jax.device_put(plan.evict_slots, rep),
            load_slots_h=plan.load_slots,
            load_ids=plan.load_ids,
            plan_id=plan.plan_id,
            n_load=plan.n_load,
            n_evict=plan.n_evict,
        )

    def _apply_migration(self, shipment: tiered_lib.Shipment) -> Batch:
        """Apply one super-batch's migration plan to the hot tables.

        Runs in the dispatch loop BETWEEN dispatches, so device-stream
        order guarantees correctness: the eviction gather reads the
        post-previous-dispatch row values (async D2H; consumed one-plus
        dispatches later by the cold store), and the load overwrite
        lands before the dispatch that needs the new rows.  Returns the
        device super-batch to dispatch.
        """
        if self._tiering_sharded:
            return self._apply_migration_fleet(shipment)
        man = self.tiered
        state = self.state
        tables = (state.params.table,) + tiered_lib.get_opt_tables(
            self.cfg.optimizer, state.opt_state
        )
        if shipment.n_evict:
            rows = self._tier_gather_jit(tables, shipment.evict_slots)
            for r in rows:
                try:
                    r.copy_to_host_async()
                except Exception:  # pragma: no cover - backend drift
                    pass
            man.push_writeback(shipment.plan_id, rows)
        if shipment.n_load:
            new_tables = self._tier_load_jit(
                tables, shipment.load_slots, shipment.load_rows
            )
            self.state = state._replace(
                params=state.params._replace(table=new_tables[0]),
                opt_state=tiered_lib.set_opt_tables(
                    self.cfg.optimizer, state.opt_state, new_tables[1:]
                ),
            )
            man.note_applied(shipment)
        return shipment.batch

    def _apply_migration_fleet(
        self, shipment: "tiered_fleet.FleetShipment"
    ) -> Batch:
        """Fleet half of :meth:`_apply_migration`: the gathered evict
        rows come back P(MODEL)-sharded, and each OWNED column's block
        is handed to its shard's write-back ledger directly from the
        device shard — no rank ever holds another rank's rows."""
        man = self.tiered
        state = self.state
        fplan = shipment.plan
        tables = (state.params.table,) + tiered_lib.get_opt_tables(
            self.cfg.optimizer, state.opt_state
        )
        if fplan.n_evict_max:
            rows = self._tier_gather_jit(tables, shipment.evict_slots)
            cap_e = fplan.cap_evict
            # shard index -> per-table device blocks, deduped across
            # data-axis replicas (same column, same values).
            blocks: dict = {}
            for r in rows:
                got: dict = {}
                for sh in r.addressable_shards:
                    s = (sh.index[0].start or 0) // cap_e
                    if s in got:
                        continue
                    got[s] = sh.data
                    try:
                        sh.data.copy_to_host_async()
                    except Exception:  # pragma: no cover - drift
                        pass
                for s, d in got.items():
                    blocks.setdefault(s, []).append(d)
            for s in sorted(man.owned):
                if fplan.shard_plans[s].n_evict and s in blocks:
                    man.push_writeback(
                        s, fplan.plan_id, tuple(blocks[s])
                    )
        if fplan.n_load_max:
            new_tables = self._tier_load_jit(
                tables, shipment.load_slots, shipment.load_rows
            )
            self.state = state._replace(
                params=state.params._replace(table=new_tables[0]),
                opt_state=tiered_lib.set_opt_tables(
                    self.cfg.optimizer, state.opt_state, new_tables[1:]
                ),
            )
            man.note_applied(fplan)
        return shipment.batch

    def _sort_meta_spec(self):
        """(vocab, CHUNK, TILE) when host-side sort prep applies, else None.

        Host prep rides the single-process tile path only: sharded and
        scatter applies derive their own metadata, and multi-process
        batches hold per-host slices the global-sort metadata would not
        match.  Cached; flips off permanently if the native lib fails.
        """
        if hasattr(self, "_meta_spec"):
            return self._meta_spec
        spec = None
        cfg = self.cfg
        if (
            cfg.host_sort
            and self.tiered is None  # sort prep keys on pre-remap ids
            and jax.process_count() == 1
            and self.mesh.size == 1
        ):
            try:
                if sparse_lib.apply_mode(cfg, self.mesh) == "tile":
                    spec = (
                        cfg.vocabulary_size,
                        sparse_lib.sparse_apply.CHUNK,
                        sparse_lib.sparse_apply.TILE,
                    )
            except ValueError:
                spec = None
        self._meta_spec = spec
        return spec

    def _input_plan(self):
        """(pipeline_cfg, shard, ordered) for host-sharded input.

        Multi-process: each host parses only its strided share of the
        global stream at LOCAL batch size (global / num_blocks); the global
        batch is assembled shard-by-shard in mesh_lib.shard_batch.  Hosts
        that share a data block (model-axis-spanning processes) must
        produce bit-identical batches in identical order, so their
        pipelines run ordered (parallel parse, sequence-ordered
        delivery)."""
        import dataclasses

        n_procs = jax.process_count()
        if n_procs == 1:
            return self.cfg, (0, 1), False
        shard = mesh_lib.data_partition(self.mesh)
        num_blocks = shard[1]
        if self.cfg.batch_size % num_blocks:
            raise ValueError(
                f"batch_size {self.cfg.batch_size} not divisible by "
                f"{num_blocks} host data blocks"
            )
        pipe_cfg = dataclasses.replace(
            self.cfg, batch_size=self.cfg.batch_size // num_blocks
        )
        return pipe_cfg, shard, n_procs > num_blocks

    def reset_metrics(self):
        rep = NamedSharding(self.mesh, P())
        self.state = self.state._replace(
            metrics=jax.device_put(MetricState.zeros(), rep)
        )

    def train(self) -> dict:
        cfg = self.cfg
        if not cfg.train_files:
            raise ValueError("no train_files configured")
        # Mid-epoch resume: a checkpoint carries the input-pipeline position
        # (epoch, batches consumed).  With the same seed/files, the stream
        # continues where the interrupted run stopped instead of replaying
        # the epoch from scratch.  A completed run's position (epoch ==
        # epoch_num) means a warm start trains epoch_num fresh epochs.
        resume_epoch, resume_skip = 0, 0
        # Only resume the data position when params actually warm-started —
        # a stale data_state.json next to cleared params must not make a
        # fresh model skip training data.
        ds = (
            checkpoint.restore_data_state(cfg.model_file)
            if self._restored_step else None
        )
        if ds is not None:
            # The position only means "continue where we stopped" under
            # the SAME stream definition: seed, batch size, file list.
            # A changed config would make the skip land on the wrong data
            # — warn and start the epoch from scratch instead.
            fp = ds.get("fingerprint")
            if fp is not None and fp != self._data_fingerprint():
                log.warning(
                    "checkpoint data position was saved under a different "
                    "input config (seed/batch_size/files changed); "
                    "ignoring it and reading the epoch from the start"
                )
                ds = None
        if ds is not None and 0 <= ds.get("epoch", -1) < cfg.epoch_num:
            resume_epoch = int(ds["epoch"])
            resume_skip = int(ds.get("batches_done", 0))
            if resume_epoch or resume_skip:
                log.info(
                    "resuming data stream at epoch %d, skipping %d batches",
                    resume_epoch, resume_skip,
                )
        # One metrics stream per process, like the trace files: rank 0
        # owns the configured path, ranks > 0 suffix .rankN
        # (obs.rank_suffix_path — the shared spelling).  Before this
        # guard every rank of a shared-filesystem fleet APPENDED into
        # one file and a merged report double-counted the run.
        rank = jax.process_index()
        metrics_out = (
            obs.JsonlWriter(obs.rank_suffix_path(cfg.metrics_file, rank))
            if cfg.metrics_file else None
        )
        pipe_cfg, shard, _ = self._input_plan()
        profiling = False
        profile_started = False
        profile_stop_at = 0
        k = cfg.steps_per_dispatch
        t0 = time.time()
        # A self-describing stream starts with its run identity: one
        # header record carries the config fingerprint, dispatch/ingest
        # mode, and platform versions, so any metrics file can be read
        # without the .cfg that produced it.
        if metrics_out is not None:
            metrics_out.write({
                "record": "run_header",
                "time": t0,
                # Which process of a multi-host fleet wrote this stream:
                # every process writes its own metrics_file, and the
                # rank tag is what lets tools/report.py merge them.
                "rank": rank,
                "config_fingerprint": _config_fingerprint(cfg),
                "steps_per_dispatch": k,
                "ingest_mode": (
                    "procs" if cfg.parse_processes > 0 else "threads"
                ),
                "fast_ingest": cfg.fast_ingest,
                "cache_epochs": cfg.cache_epochs,
                "cache_prestacked": cfg.cache_prestacked,
                "ring_slots": cfg.ring_slots,
                "table_tiering": cfg.table_tiering,
                "hot_rows": (
                    cfg.hot_rows if cfg.table_tiering == "on" else 0
                ),
                "tiered_partition": cfg.tiered_partition,
                "tiered_shards": (
                    self._tier_shards if self._tiering_sharded else 0
                ),
                "sparse_exchange_overlap": cfg.sparse_exchange_overlap,
                "exchange_overlap_active": self._overlap_active,
                "cold_dtype": cfg.cold_dtype,
                "batch_size": cfg.batch_size,
                "epoch_num": cfg.epoch_num,
                "optimizer": cfg.optimizer,
                "telemetry": cfg.telemetry,
                "heartbeat_secs": cfg.heartbeat_secs,
                "trace_file": cfg.trace_file,
                "trace_rotate_events": cfg.trace_rotate_events,
                "nan_policy": cfg.nan_policy,
                "status_port": cfg.status_port,
                "alert_rules": cfg.alert_rules,
                "resource_metrics": cfg.resource_metrics,
                "quality": cfg.quality,
                "quality_window": cfg.quality_window,
                "jax_version": jax.__version__,
                "backend": jax.default_backend(),
                "mesh": {str(a): int(n) for a, n in self.mesh.shape.items()},
                "n_processes": jax.process_count(),
                "resume_step": self._restored_step,
                "resume_epoch": resume_epoch,
                "resume_skip": resume_skip,
                "kernel_impl": self.kernel_impl,
                "interaction_impl": cfg.interaction_impl,
                "compile_cache_dir": cfg.compile_cache_dir,
            })
            if self._autotune is not None:
                autotune_lib.write_record(metrics_out, self._autotune)
        # Seed the step-rate interval from the CURRENT metric state, not
        # 0: a warm-started Trainer (or a second train() on the same
        # instance) carries pre-resume examples in metrics.count, and the
        # first ex/s interval used to be inflated by all of them.
        last_log_t = t0
        last_log_ex = float(self.state.metrics.count)
        stepno = 0
        # Per-run accounting: instruments persisted across runs would
        # report run-1+run-2 totals against run 2's wall clock
        # (ingest_wait_frac > 1 on a second train() of a warm Trainer).
        # Reset IN PLACE so external references to trainer.telemetry
        # stay live.
        self.telemetry.reset()
        # floats a table row holds: 1 + k, or 1 + field_num * k
        self.telemetry.gauge("train.row_floats").set(cfg.embedding_dim)
        # which writer the compiled step's one-device apply holds:
        # 1 = the transposed tile stream, 0 = the scatter loop (or
        # another apply mode altogether)
        self.telemetry.gauge("train.apply_stream").set(int(
            self.sparse and sparse_lib.apply_stream(self._dcfg, self.mesh)))
        # where the health carry's rows_touched comes from: 1 = the
        # stream writer's row-touch mask, OR-ed in; 0 = a scatter of
        # the batch's ids
        self.telemetry.gauge("train.touch_mask").set(int(
            self.sparse and sparse_lib.touch_mask(self._dcfg, self.mesh)))
        # the exchange over the data axis the compiled step holds:
        # 1 = entries (touched rows all-gathered), 0 = dense (a whole
        # shard's delta psum'd); absent where the step exchanges nothing
        exchange = (sparse_lib.exchange_mode(self._dcfg, self.mesh)
                    if self.sparse else None)
        if exchange is not None:
            self.telemetry.gauge("train.exchange_mode").set(
                int(exchange == "entries"))
        # what the health carry's apply_rows pair is the ratio of: the
        # hand-sharded entries step reports its merged stream's fill
        exchange_fills = exchange == "entries" and cfg.lookup == "shardmap"
        # the slots a step of that exchange merges over the whole mesh
        # on its compact rung (None: the step holds none) and at capacity
        compact_slots, full_slots = (
            sparse_lib.exchange_slots(self._dcfg, self.mesh)
            if exchange_fills else (None, None))
        self.tracer.reset()
        # Fresh health carry + host cache per run; the nan_policy check
        # below reads the PREVIOUS dispatch's scalars (async-copied right
        # after each dispatch) so detection costs no pipeline bubble:
        # by the time dispatch n+1 is enqueued, n has long finished on
        # device and its scalars are already on the host.
        self._reset_health()
        self._health_host = {}
        # Resource plane, per-run: fresh sentinel accounting (the AOT
        # cache itself is instance-lived — a second train() on a warm
        # Trainer truthfully reports zero compiles) and the run's
        # writer for `record: compile` entries.
        self._dispatches = 0
        self._run_steps = 0
        self._tail_probation = None
        if self._sentinel is not None:
            self._sentinel.reset()
            self._sentinel.set_writer(metrics_out)
        # /profile captures land beside the metrics stream (or cwd);
        # the writer is stashed so the route can log each capture as a
        # `record: profile` entry in the same stream.
        self._profile_capture_dir = os.path.join(
            os.path.dirname(cfg.metrics_file) or ".",
            "tffm_profile_ondemand",
        )
        self._metrics_writer = metrics_out
        # /metrics self-identification: one info-style gauge whose
        # labels name the run (tffm_build_info) so scrapes from
        # different runs/configs are distinguishable in Prometheus.
        self._build_info = {
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "mesh": "x".join(
                f"{a}{n}" for a, n in self.mesh.shape.items()
            ),
            "steps_per_dispatch": str(k),
            "rank": str(jax.process_index()),
            "config_fingerprint": _config_fingerprint(cfg),
        }
        if self.tiered is not None:
            self.tiered.reopen()  # re-arm after a cancelled prior run
        # Model-quality plane, per-run (same reset discipline as
        # telemetry/tracer/health): the drift-sketch accumulator the
        # parse workers feed, and the windowed online-eval monitor the
        # dispatch loop feeds one dispatch delayed.
        self._quality_sketch = (
            obs.StreamSketch(cfg.quality_window, telemetry=self.telemetry)
            if cfg.quality else None
        )
        self._quality = (
            obs.QualityMonitor(
                loss_type=cfg.loss_type, window=cfg.quality_window,
                sketch=self._quality_sketch,
            )
            if cfg.quality else None
        )
        self._last_scores = None
        # (nonfinite_arr, grad_sq_arr, grad_sq_sum_arr, apply_rows_arr, stepno)
        pending_health = None
        apply_rows_seen = np.zeros(2, np.uint32)  # at the last readback
        steps_seen = 0  # likewise
        pending_quality = None  # (scores_arr, labels_arr, weights_arr)
        nonfinite_warned = False

        def check_health(pending) -> None:
            """Consume one delayed health readback; apply nan_policy."""
            nonlocal nonfinite_warned, apply_rows_seen, steps_seen
            nf_arr, gs_arr, ss_arr, ap_arr, at_step = pending
            nf = int(nf_arr)
            gs = float(gs_arr)
            ss = float(ss_arr)
            ap = np.asarray(ap_arr)
            # this dispatch's own share (uint32 differences wrap back)
            written, merged = (int(x) for x in ap - apply_rows_seen)
            apply_rows_seen = ap
            steps, steps_seen = at_step - steps_seen, at_step
            if merged:  # the deduped scatter apply ran (train.sparse),
                # or the entries exchange: real merged entries over the
                # slots merged for them (train.shardmap_step)
                frac = round(written / merged, 6)
                if exchange_fills:
                    self.telemetry.gauge("train.exchange_fill").set(frac)
                    if compact_slots is not None:
                        # a step merged one or the other: the share
                        # of this dispatch's steps on the compact rung
                        self.telemetry.gauge("train.exchange_compact").set(
                            round((steps * full_slots - merged)
                                  / (steps * (full_slots - compact_slots)),
                                  6))
                else:
                    self.telemetry.gauge("train.apply_unique_frac").set(frac)
            self._health_host["grad_norm"] = round(
                float(np.sqrt(gs)) if np.isfinite(gs) else gs, 6
            )
            # RMS from the same readback: heartbeat-path rules on the
            # documented grad_norm_rms signal (and /status scrapes)
            # must see it live, not only at log cadence — a halt rule
            # on a signal that never materializes is silently inert.
            rms = ss / max(1, at_step)
            self._health_host["grad_norm_rms"] = round(
                float(np.sqrt(rms)) if np.isfinite(rms) else rms, 6
            )
            self._health_host["nonfinite_steps"] = nf
            if nf <= 0:
                return
            if not nonfinite_warned:
                nonfinite_warned = True
                log.warning(
                    "non-finite (NaN/inf) gradient detected by step %d "
                    "(%d bad step(s) so far; nan_policy=%s)",
                    at_step, nf, cfg.nan_policy,
                )
            if cfg.nan_policy == "halt":
                raise NonFiniteGradError(
                    f"non-finite gradient within the first {at_step} "
                    f"step(s) ({nf} bad step(s)); halting per "
                    "nan_policy=halt — the checkpoint was NOT "
                    "overwritten with poisoned params"
                )
        # Starvation-vs-dispatch split: wait_input times next() on the
        # prefetcher (the loop is input-starved), dispatch times the
        # fused-scan call (includes any device backpressure block); wall
        # minus the two is "other" (logging/validation/save).  The
        # heartbeat derives ingest_wait_frac = wait / wall from these.
        t_wait = self.telemetry.timer("train.wait_input")
        t_disp = self.telemetry.timer("train.dispatch")
        t_log = self.telemetry.timer("train.log_readback")
        # Tiered-table migration time (eviction gather enqueue + load
        # apply): part of "other" in the wall split — the H2D of the
        # cold rows themselves already overlapped in the prefetcher.
        t_migr = self.telemetry.timer("train.migrate")
        # Cadences move to super-batch (K-step) granularity: a trigger
        # fires at the first dispatch boundary where at least its period
        # of NEW steps has elapsed since it last fired.  At K == 1 this
        # reduces exactly to the old per-step ``stepno % period == 0``.
        last_log_step = last_val_step = last_save_step = 0
        trunc_logged = 0
        # ONE pipeline spans every remaining epoch of the run (the
        # epoch-persistent ingest): the reader reseeds per epoch
        # (seed + e, identical streams to the old one-pipeline-per-epoch
        # construction), the resume position (start_epoch, skip_batches)
        # lives inside the pipeline, and in-band EpochEnd markers carry
        # the epoch boundaries out — so parser workers, the native
        # parser, and (with cache_epochs) the parsed-batch cache all
        # survive across epochs instead of being torn down per epoch.
        #
        # ordered=True always for training: delivery follows the
        # (seeded, deterministic) reader order, so the saved
        # batches_done position identifies EXACTLY the prefix that
        # trained — with free-running workers a mid-epoch resume could
        # double- or never-train boundary batches.  Parsing still fans
        # out to thread_num workers (sequence-numbered delivery), so
        # this costs no throughput.
        self._epoch = resume_epoch
        self._batches_done = resume_skip
        pipeline = BatchPipeline(
            cfg.train_files,
            pipe_cfg,
            weight_files=cfg.weight_files or None,
            epochs=cfg.epoch_num,
            shuffle=True,
            seed=cfg.seed,
            start_epoch=resume_epoch,
            skip_batches=resume_skip,
            shard=shard,
            ordered=True,
            sort_meta_spec=self._sort_meta_spec(),
            cache_epochs=cfg.cache_epochs,
            cache_max_bytes=cfg.cache_max_bytes,
            # Pre-stacked cache storage: groups stack once at epoch-0
            # dispatch boundaries (K = steps_per_dispatch) and replay
            # epochs hand whole super-batches to the prefetcher.
            prestack_k=(k if cfg.cache_prestacked else 0),
            epoch_marks=True,
            telemetry=self.telemetry,
            tracer=self.tracer,
            quality=self._quality_sketch,
        )
        # Transfer stage: a background thread stacks K parsed batches
        # and ships super-batch n+1 (shard + device_put) while n trains;
        # an epoch's tail arrives as one short super-batch (K' =
        # leftover, the EpochEnd marker flushes the group), so every
        # batch trains exactly once and ``batches_done`` only ever
        # advances by whole dispatches — a saved position always lands
        # on a super-batch boundary.
        # Fused stack+H2D (mesh.FusedShipper): where the mesh/backend
        # allow it, the transfer thread copies the K batches into ONE
        # contiguous staging buffer and ships it with a single
        # device_put instead of stack-then-put-per-leaf.  Tiering needs
        # the host-side id remap between stack and put, so it keeps the
        # classic path.
        ship_fn = None
        if self.tiered is None and mesh_lib.fused_h2d_enabled(self.mesh):
            ship_fn = mesh_lib.FusedShipper(
                self.mesh, depth=cfg.prefetch_super_batches
            )
            log.info("fused stack+H2D transfer path enabled")
        prefetcher = DevicePrefetcher(
            pipeline, k, self._put_super,
            depth=cfg.prefetch_super_batches,
            telemetry=self.telemetry,
            # _put_super copies host->device, so stacking can recycle
            # pre-allocated staging buffers instead of allocating a
            # super-batch of host memory per dispatch.
            staging=True,
            tracer=self.tracer,
            ship_fn=ship_fn,
        )
        cache_logged = not cfg.cache_epochs

        # Live training-fleet plane (obs/fleet.py): rank 0 scrapes
        # every rank's /status on the heartbeat cadence and publishes
        # the merged `fleet` block + per-rank tffm_train_rank_* series.
        # Ranks > 0 only SERVE their /status — aggregation is rank 0's.
        fleet = None
        if cfg.train_fleet_scrape and rank == 0:
            fleet = obs.TrainFleet(
                cfg.train_fleet_scrape.split(","),
                interval_s=cfg.heartbeat_secs,
                telemetry=self.telemetry,
            )
        elif jax.process_count() > 1 and rank == 0 and cfg.status_port:
            # A real fleet with live endpoints but no aggregation
            # plane: nudge, don't act — peer addresses are not
            # discoverable from here.
            log.info(
                "multi-process run with status endpoints but no "
                "train_fleet_scrape targets; set it to each rank's "
                "host:port for live fleet aggregation and straggler "
                "alerts"
            )

        def telemetry_record(kind: str):
            """One structured self-report (heartbeat/final), host-side
            only: counters/gauges/timers — never a device readback, which
            would force a sync from the heartbeat thread mid-dispatch.

            Heartbeats return None (skip the beat) until the FIRST
            dispatch completes: before that, the wait timer has been
            running since before any dispatch could exist (jit compile,
            a resume's cached-epoch rebuild parse), and a wait-only
            window would report ingest_wait_frac ≈ 1 — an over-count
            that used to finger ingest for what is really startup.
            The guard reads ``stepno`` (not the dispatch timer's count,
            which is a permanent 0 with telemetry disabled — that would
            silence every liveness beat of a --no_telemetry run).  The
            final record always emits.
            """
            if kind == "heartbeat" and stepno == 0:
                return None
            now = time.time()
            wall = max(now - t0, 1e-9)
            wait_s, disp_s = t_wait.total_s, t_disp.total_s
            rec = {
                "record": kind,
                "time": now,
                # Self-identifying for the fleet scrape (and report
                # merges): which rank produced this record.
                "rank": rank,
                "step": stepno,
                "epoch": self._epoch,
                "elapsed": round(wall, 3),
                "examples_in": self.telemetry.counter(
                    "ingest.examples"
                ).value,
                "wait_input_s": round(wait_s, 3),
                "dispatch_s": round(disp_s, 3),
                "other_s": round(max(0.0, wall - wait_s - disp_s), 3),
                "ingest_wait_frac": round(wait_s / wall, 4),
                # Data-integrity counters (pipeline.stats): truncation,
                # out-of-range batches, cache outcome.
                **pipeline.stats(),
                # Training-health monitors (scan-carry): host-cached
                # scalars only on the heartbeat path; exact values are
                # refreshed at log cadence and for the final record.
                "health": self._health_summary(exact=(kind == "final")),
                "stages": self.telemetry.snapshot(),
            }
            if self._sentinel is not None:
                # Memory & compile self-report (obs/resource.py): RSS,
                # the component byte ledger, device memory, compile
                # sentinel counters, FLOP/s attribution.  Host-side
                # reads only — safe on the heartbeat/status threads.
                rec["resource"] = self._resource_block(
                    rec["stages"], wall
                )
            if kind == "status":
                # Scrapes are self-identifying: /metrics renders this
                # as the tffm_build_info info-style gauge.
                rec["build_info"] = dict(self._build_info)
            if kind == "status" and stepno == 0:
                # Same over-count the heartbeat path suppresses by
                # skipping the beat (see the docstring): before the
                # first dispatch the wait timer has only startup (jit
                # compile, cache rebuild) to attribute against, and a
                # scraped ingest_wait_frac ~= 1 would page someone for
                # a startup artifact.  /status must still ANSWER, so
                # the attribution keys are omitted (no Prometheus
                # series yet, rather than a lying one) and the record
                # says why.
                for key in ("wait_input_s", "dispatch_s", "other_s",
                            "ingest_wait_frac"):
                    del rec[key]
                rec["warming_up"] = True
            if self.tiered is not None:
                # Hot/cold cache behavior (host-side counters only —
                # safe from the heartbeat thread).
                rec["tiered"] = self.tiered.snapshot()
            if self._quality is not None:
                # Model-quality self-report: windowed online eval +
                # drift signals (host-side numpy over the consumed
                # score window; memoized inside the monitor so scrape
                # storms don't repeat the window statistics — the
                # final record forces a fresh compute, its values
                # must be end-of-run exact).
                rec["quality"] = self._quality.block(
                    force=(kind == "final")
                )
            if self.tracer.enabled:
                # Truncation truthfulness: a trace that hit the event
                # cap silently lies by omission; the count rides every
                # self-report (heartbeat / status / final) so the alert
                # watchdog and report tooling can flag it live.
                rec["trace_dropped_events"] = self.tracer.dropped_events
                if cfg.trace_rotate_events:
                    rec["trace_windows"] = self.tracer.windows_written
            if fleet is not None:
                # The merged fleet view (cached scrape state only —
                # nothing here blocks on the network, so heartbeat /
                # status threads stay host-fast).  Alert rules resolve
                # straggler_ratio / rank_step_skew / exchange_frac /
                # scrape_age_max_s from this block.
                rec["fleet"] = fleet.block(now)
            if alert_engine is not None:
                # Armed-rule states for /status and the per-rule
                # tffm_alert_active gauges (the engine is created just
                # below; every record is built after that).
                rec["alerts"] = alert_engine.active_snapshot()
            return rec

        # Incident flight recorder (obs/blackbox.py): fixed-memory
        # rings of recent heartbeats/alerts; rule breaches, crashes,
        # and POST /incident dump forensic bundles under
        # <model_file>/incidents (incident_dir overrides).  The rank
        # suffix keeps a fleet's bundles collision-free.
        # blackbox=false = None = rings never touched, training
        # bitwise-identical (pinned by test).
        blackbox = None
        if cfg.blackbox:
            blackbox = obs.Blackbox(
                cfg.incident_dir
                or os.path.join(cfg.model_file, "incidents"),
                suffix=f"rank{rank}",
                run_header=dict(self._build_info),
                metrics_render=lambda: obs.render_prometheus(
                    telemetry_record("status")
                ),
                trace_tail_fn=(
                    self.tracer.tail if self.tracer.enabled else None
                ),
                writer=metrics_out,
                telemetry=self.telemetry,
            )
        # Alert watchdog: declarative rules evaluated against every
        # heartbeat record ON the heartbeat thread (obs/alerts.py).
        # Breaches emit `record: alert` JSONL entries; an action=halt
        # rule arms engine.halted and the DISPATCH loop below raises
        # AlertHaltError at the next boundary (same no-poisoned-
        # checkpoint contract as nan_policy=halt).  Every emitted
        # alert also reaches the blackbox, which dumps a bundle.
        alert_engine = None
        if cfg.alert_rules:
            # FmConfig already guarantees heartbeat_secs > 0 whenever
            # rules are set (a watchdog with no heartbeat to ride
            # would be silently inert).
            alert_engine = obs.AlertEngine(
                obs.parse_rules(cfg.alert_rules), writer=metrics_out,
                on_alert=(
                    blackbox.on_alert if blackbox is not None else None
                ),
            )

        def heartbeat_build():
            rec = telemetry_record("heartbeat")
            if rec is not None:
                # Ring BEFORE the alert engine observes, so an alert-
                # triggered bundle contains the breaching record.
                if blackbox is not None:
                    blackbox.observe_record(rec)
                if alert_engine is not None:
                    alert_engine.observe(rec)
            return rec

        heartbeat = None
        if cfg.heartbeat_secs > 0:
            heartbeat = obs.Heartbeat(
                cfg.heartbeat_secs, heartbeat_build, writer=metrics_out,
            )
        # Live status endpoint: /metrics (Prometheus) + /status (the
        # heartbeat-shaped JSON record, on demand) from an in-process
        # stdlib HTTP server.  Requests read the same thread-safe
        # snapshots a heartbeat does; with status_port unset no server
        # exists and training is bit-identical.  A taken port degrades
        # to a warning — an observability convenience must never kill
        # the run it observes.
        status_server = None
        if cfg.status_port:
            try:
                status_server = obs.StatusServer(
                    cfg.status_port, partial(telemetry_record, "status"),
                    telemetry=self.telemetry, host=cfg.status_host,
                    profile=self._ondemand_profile,
                    incident=(
                        blackbox.incident if blackbox is not None
                        else None
                    ),
                    # Rank 0 of a fleet decorates /metrics with the
                    # per-rank tffm_train_rank_* labeled series.
                    metrics_extra=(
                        fleet.metrics_lines if fleet is not None
                        else None
                    ),
                )
                log.info(
                    "status endpoint listening on %s:%d "
                    "(/metrics, /status, /healthz, /debug/threadz, "
                    "/profile)", cfg.status_host,
                    status_server.port,
                )
            except OSError as e:
                log.warning(
                    "status endpoint failed to bind port %d: %s",
                    cfg.status_port, e,
                )
        # Cross-rank exchange probe (train.exchange): a tiny jitted
        # all-reduce enqueued after every dispatch and blocked on one
        # dispatch later — the HealthState discipline, so the timing
        # costs no pipeline bubble.  At parity the previous probe has
        # long finished and the wait is ~0; a straggling rank shows up
        # as exactly its lag.  Gated on the fleet plane being on AND a
        # real multi-device mesh; off-path training is untouched.
        exchange_probe = None
        pending_exchange = None
        t_exch = None
        if cfg.train_fleet_scrape and self.mesh.size > 1:
            try:
                if cfg.lookup == "shardmap":
                    from fast_tffm_tpu.train import (
                        shardmap_step as shardmap_lib,
                    )
                    exchange_probe = shardmap_lib.make_exchange_probe(
                        self.mesh
                    )
                else:
                    exchange_probe = sparse_lib.make_exchange_probe(
                        self.mesh
                    )
                t_exch = self.telemetry.timer("train.exchange")
            except Exception as e:  # noqa: BLE001 - obs must not kill
                log.warning("train.exchange probe unavailable: %s", e)
        run_exc: Optional[BaseException] = None
        total_trunc = 0
        try:
            try:
                self.tracer.name_thread("train-loop")
                source = iter(prefetcher)
                # Dispatch counter = super-batch id: the prefetcher
                # assigns sb in emission order and its bounded FIFO
                # output queue preserves it, so this counter names the
                # same super-batch its stack/h2d spans did — the trace
                # chain's final link.
                dispatch_idx = 0
                while True:
                    # Starvation accounting: time blocked waiting for the
                    # next staged super-batch.
                    with t_wait.time(), self.tracer.span(
                        "train.wait_input"
                    ):
                        item = next(source, None)
                    # A short-k compile from the PREVIOUS dispatch is
                    # only a legit epoch tail if a boundary follows it.
                    self._resolve_tail_probation(item)
                    if item is None:
                        break
                    if isinstance(item, EpochEnd):
                        self._epoch = item.epoch + 1
                        self._batches_done = 0
                        if not cache_logged:
                            # The cache outcome is known once epoch 0
                            # finishes parsing; surface it exactly once.
                            cache_logged = True
                            log.info(
                                "ingest cache after epoch %d: %s",
                                item.epoch, pipeline.cache_result,
                            )
                        continue
                    super_batch, kk = item
                    if self.tiered is not None:
                        # Migration first: eviction gather reads the
                        # previous dispatch's row values, the load lands
                        # before this dispatch gathers its rows.
                        plan = getattr(super_batch, "plan", None)
                        with t_migr.time(), self.tracer.span(
                            "train.migrate",
                            args={"sb": dispatch_idx,
                                  "loads": (
                                      plan.n_load_max if plan is not None
                                      else super_batch.n_load
                                  ),
                                  "evicts": (
                                      plan.n_evict_max if plan is not None
                                      else super_batch.n_evict
                                  )},
                        ):
                            super_batch = self._apply_migration(super_batch)
                    if (
                        cfg.profile_dir
                        and not profile_started
                        and stepno >= cfg.profile_start_step
                    ):
                        jax.profiler.start_trace(cfg.profile_dir)
                        profiling = profile_started = True
                        profile_stop_at = stepno + cfg.profile_steps
                    # ONE dispatch = kk fused train steps (lax.scan).
                    # The dispatch is async: this wall time is enqueue
                    # cost plus any device backpressure block — the
                    # compute-bound half of the wall-clock split.
                    with t_disp.time(), obs.trace_span("tffm:dispatch"), \
                            self.tracer.span(
                                "train.dispatch",
                                args={"sb": dispatch_idx, "k": kk,
                                      "step0": stepno},
                                flow=("f", f"sb{dispatch_idx}"),
                            ):
                        self.state = self._scan_train_step(
                            self.state, super_batch
                        )
                    dispatch_idx += 1
                    stepno += kk
                    self._batches_done += kk
                    # Resource-plane attribution state: dispatch count
                    # for model_flops_per_s, and the step the compile
                    # sentinel stamps on `record: compile` entries.
                    self._dispatches = dispatch_idx
                    self._run_steps = stepno
                    # Exchange timing: with the overlapped exchange
                    # active, one dispatch delayed — enqueue THIS
                    # dispatch's barrier probe (it runs behind the
                    # dispatch on every rank's stream), then block on
                    # the PREVIOUS one, already resolved at parity, so
                    # the wait measures only the residual cross-rank
                    # lag the overlap did not hide.  WITHOUT overlap
                    # the probe blocks immediately: the synchronous
                    # window (dispatch + exchange at the barrier) is
                    # exactly the cost the overlap exists to remove,
                    # so the off/on pair of exchange_frac readings is
                    # directly comparable.
                    if exchange_probe is not None:
                        probe_out = exchange_probe()
                        if self._overlap_active:
                            if pending_exchange is not None:
                                with t_exch.time():
                                    jax.block_until_ready(
                                        pending_exchange
                                    )
                            pending_exchange = probe_out
                        else:
                            with t_exch.time():
                                jax.block_until_ready(probe_out)
                    # Health readback, one dispatch delayed: start an
                    # async D2H copy of THIS dispatch's scalars, then
                    # consume the PREVIOUS dispatch's (already resident —
                    # that dispatch finished on device while this one's
                    # input staged, so the read never stalls the
                    # pipeline).  nan_policy=halt therefore fires within
                    # one dispatch of the poisoned one.
                    nf_arr = self._health.nonfinite_steps
                    gs_arr = self._health.grad_sq_last
                    ss_arr = self._health.grad_sq_sum
                    ap_arr = self._health.apply_rows
                    try:
                        nf_arr.copy_to_host_async()
                        gs_arr.copy_to_host_async()
                        ss_arr.copy_to_host_async()
                        ap_arr.copy_to_host_async()
                    except Exception:  # pragma: no cover - backend drift
                        pass
                    if pending_health is not None:
                        check_health(pending_health)
                    pending_health = (
                        nf_arr, gs_arr, ss_arr, ap_arr, stepno
                    )
                    # Quality eval feed, same one-dispatch-delayed
                    # discipline: start an async D2H of THIS dispatch's
                    # stacked scores (+ the labels/weights the batch
                    # already holds — the super-batch is not donated, so
                    # its buffers stay valid), then consume the PREVIOUS
                    # dispatch's arrays, which are already resident.
                    if self._quality is not None and self._with_scores:
                        q_arrs = (
                            self._last_scores, super_batch.labels,
                            super_batch.weights,
                        )
                        for a in q_arrs:
                            try:
                                a.copy_to_host_async()
                            except Exception:  # pragma: no cover - drift
                                pass
                        if pending_quality is not None:
                            self._quality.observe(
                                np.asarray(pending_quality[0]),
                                np.asarray(pending_quality[1]),
                                np.asarray(pending_quality[2]),
                            )
                        pending_quality = q_arrs
                    # Alert halt: the watchdog armed the flag on the
                    # heartbeat thread; raising HERE (between
                    # dispatches) keeps the halt on the main thread —
                    # no checkpoint overwrite, crash-truthful final
                    # record, same path as nan_policy=halt.
                    if (
                        alert_engine is not None
                        and alert_engine.halted is not None
                    ):
                        raise obs.halt_error(alert_engine.halted)
                    if profiling and stepno >= profile_stop_at:
                        jax.block_until_ready(self.state)
                        jax.profiler.stop_trace()
                        profiling = False
                        log.info(
                            "profiler trace written to %s",
                            cfg.profile_dir,
                        )
                    if (
                        cfg.log_steps
                        and stepno - last_log_step >= cfg.log_steps
                    ):
                        last_log_step = stepno
                        # The read-back of the metrics and the exact
                        # health waits for the device: one phase, so
                        # that a trace names the gap it leaves.
                        with obs.Phase(t_log,
                                       "tffm:train.log_readback"):
                            # Examples come from the on-device weight sum —
                            # the GLOBAL count in multi-host runs (each host
                            # only sees its local shard).
                            m = _finalize_metrics(
                                self.state.metrics, cfg.loss_type
                            )
                            now = time.time()
                            rate = (m["examples"] - last_log_ex) / max(
                                now - last_log_t, 1e-9
                            )
                            last_log_t, last_log_ex = now, m["examples"]
                            # The log readback already synced the host;
                            # piggyback the exact health refresh (row
                            # occupancy included) so heartbeats between
                            # logs serve fresh cached values.
                            self._health_summary(exact=True)
                            log.info(
                                "step %d examples %d loss %.6f auc %.4f "
                                "ex/s %.0f",
                                stepno, int(m["examples"]), m["loss"],
                                m["auc"], rate,
                            )
                            # Surface parser truncation (reference FmParser
                            # warned; silently vanishing features hide data
                            # bugs like a too-small max_features).  The
                            # counter spans the whole run now — it folds in
                            # process-worker drops and cached-epoch replays.
                            cur_trunc = pipeline.truncated_features
                            if cur_trunc > trunc_logged:
                                log.warning(
                                    "%d feature occurrences dropped by "
                                    "max_features=%d since last report "
                                    "(total %d)",
                                    cur_trunc - trunc_logged,
                                    cfg.max_features, cur_trunc,
                                )
                                trunc_logged = cur_trunc
                            if metrics_out is not None:
                                metrics_out.write({
                                    "record": "train",
                                    "step": stepno,
                                    "examples": m["examples"],
                                    "loss": m["loss"],
                                    "auc": m["auc"],
                                    "examples_per_sec": rate,
                                    "elapsed": now - t0,
                                })
                    if (
                        cfg.validation_steps
                        and cfg.validation_files
                        and stepno - last_val_step >= cfg.validation_steps
                    ):
                        last_val_step = stepno
                        vm = self.evaluate(cfg.validation_files)
                        log.info(
                            "step %d validation loss %.6f auc %.4f",
                            stepno, vm["loss"], vm["auc"],
                        )
                        if metrics_out is not None:
                            # Same shape as train records (elapsed /
                            # examples alongside the losses) so one file
                            # plots both streams on one time axis.
                            metrics_out.write({
                                "record": "validation",
                                "step": stepno,
                                "examples": vm["examples"],
                                "loss": vm["loss"],
                                "auc": vm["auc"],
                                "validation_loss": vm["loss"],
                                "validation_auc": vm["auc"],
                                "elapsed": time.time() - t0,
                            })
                    if (
                        cfg.save_steps
                        and stepno - last_save_step >= cfg.save_steps
                    ):
                        # Consume THIS dispatch's health scalars before
                        # writing the checkpoint: the delayed check
                        # alone would let a save in the same iteration
                        # persist NaN-poisoned params, breaking halt's
                        # "checkpoint not overwritten" guarantee.  The
                        # blocking read costs one device sync at save
                        # cadence only.
                        if pending_health is not None:
                            check_health(pending_health)
                            pending_health = None
                        last_save_step = stepno
                        self.save(stepno)
                # Stream exhausted: consume the last delayed health
                # readback so a NaN in the final dispatch still trips
                # nan_policy before the end-of-run save.
                if pending_health is not None:
                    check_health(pending_health)
                    pending_health = None
                # ... and the last delayed quality feed, so the final
                # record's windowed eval covers every dispatched step.
                if pending_quality is not None and self._quality is not None:
                    self._quality.observe(
                        np.asarray(pending_quality[0]),
                        np.asarray(pending_quality[1]),
                        np.asarray(pending_quality[2]),
                    )
                    pending_quality = None
            finally:
                if heartbeat is not None:
                    heartbeat.close()
                if status_server is not None:
                    status_server.close()
                if fleet is not None:
                    # Stop scraping; the cached state stays readable —
                    # the final record (outer finally) still carries
                    # the last merged fleet view.
                    fleet.close()
                if self.tiered is not None:
                    # Wake a transfer thread blocked on a write-back
                    # fill that will never come — prefetcher.close()
                    # joins that thread, and an untimed cv wait would
                    # deadlock shutdown under nan_policy=halt /
                    # KeyboardInterrupt / validation errors.
                    self.tiered.cancel_waits()
                prefetcher.close()
            self._epoch = cfg.epoch_num
            self._batches_done = 0
            total_trunc = pipeline.truncated_features
            if total_trunc > trunc_logged:
                log.warning(
                    "%d feature occurrences dropped by max_features=%d "
                    "over the run", total_trunc, cfg.max_features,
                )
        except BaseException as e:
            run_exc = e
            raise
        finally:
            # An abandoned trace poisons any later start_trace in-process.
            if profiling:
                jax.profiler.stop_trace()
            # Crash-truthful stream: the final record is written from
            # this finally, so a run that died mid-flight (preemption,
            # worker crash, nan_policy=halt) still closes its JSONL with
            # exception type + partial counters — tools/report.py can
            # summarize exactly what happened instead of trailing off at
            # the last heartbeat.
            self._final_record = telemetry_record("final")
            if run_exc is not None:
                self._final_record["exception"] = type(run_exc).__name__
                self._final_record["exception_msg"] = str(run_exc)[:300]
            if blackbox is not None:
                blackbox.observe_record(self._final_record)
                if run_exc is not None and not isinstance(
                    run_exc, KeyboardInterrupt
                ):
                    # Crash-truthful bundle (NonFiniteGradError,
                    # AlertHaltError, anything unhandled): dumped
                    # before the writer closes so the incident
                    # manifest still reaches the metrics stream.
                    blackbox.incident(
                        "crash_" + type(run_exc).__name__
                    )
            if metrics_out is not None:
                try:
                    metrics_out.write(self._final_record)
                except Exception as e:
                    # A full metrics volume must not mask the run's own
                    # outcome (this block runs on the crash path too).
                    log.warning("final record write failed: %s", e)
                metrics_out.close()
            if self.tracer.enabled:
                # One trace path per process: rank 0 writes the
                # configured path, ranks > 0 suffix theirs (the
                # documented naming — computed once in __init__), and
                # tools/report.py --trace merges the fleet.  With
                # rotation on, this final dump closes the last window
                # of the trace.0.json .. trace.N.json family.
                try:
                    n_ev = self.tracer.dump(self._trace_path)
                    if cfg.trace_rotate_events:
                        n_win = self.tracer.windows_written
                        log.info(
                            "wrote %d trace window(s) (%d events in "
                            "the last) — %s .. %s; merge with "
                            "tools/report.py --trace",
                            n_win, n_ev,
                            self.tracer.window_path(0),
                            self.tracer.window_path(n_win - 1),
                        )
                    else:
                        log.info(
                            "wrote %d trace events to %s", n_ev,
                            self._trace_path,
                        )
                except OSError as e:  # pragma: no cover - full volume
                    log.warning("trace dump failed: %s", e)
                # Stop the rotation writer thread (idempotent; no-op
                # without rotation) — each run used to leak one.
                self.tracer.close()
        train_metrics = _finalize_metrics(self.state.metrics, cfg.loss_type)
        train_metrics["examples_per_sec"] = (
            train_metrics["examples"] / max(time.time() - t0, 1e-9)
        )
        train_metrics["steps"] = stepno
        # Cache observability rides the result too ("off" | "cached" |
        # "overflow") so sweeps can tell which runs actually replayed,
        # alongside the run's data-integrity counters (truncation and
        # out-of-range-id batches used to be log-only) and the
        # wall-clock split the telemetry layer measured.
        train_metrics["ingest_cache"] = pipeline.cache_result
        train_metrics["truncated_features"] = int(total_trunc)
        train_metrics["out_of_range_batches"] = int(pipeline.oor_batches)
        train_metrics["ingest_wait_frac"] = (
            self._final_record["ingest_wait_frac"]
        )
        train_metrics["wait_input_s"] = self._final_record["wait_input_s"]
        train_metrics["dispatch_s"] = self._final_record["dispatch_s"]
        # Training-health summary (exact end-of-run values from the scan
        # carry): grad norms, non-finite counts, embedding-row touch /
        # occupancy — the model-health companions to the data-integrity
        # counters above.
        train_metrics["health"] = dict(
            self._final_record.get("health", {})
        )
        if "resource" in self._final_record:
            train_metrics["resource"] = dict(
                self._final_record["resource"]
            )
        if self.tiered is not None:
            train_metrics["tiered"] = dict(
                self._final_record.get("tiered", {})
            )
        if "quality" in self._final_record:
            # End-of-run windowed eval + drift signals (the model-
            # quality companion of the health block above).
            train_metrics["quality"] = dict(
                self._final_record["quality"]
            )
        self.save(stepno)
        result = {"train": train_metrics}
        if cfg.validation_files:
            result["validation"] = self.evaluate(cfg.validation_files)
            log.info(
                "validation loss %.6f auc %.4f",
                result["validation"]["loss"],
                result["validation"]["auc"],
            )
        return result

    def evaluate(self, files) -> dict:
        rep = NamedSharding(self.mesh, P())
        ms = jax.device_put(MetricState.zeros(), rep)
        pipe_cfg, shard, ordered = self._input_plan()
        pipeline = BatchPipeline(
            files, pipe_cfg, epochs=1, shuffle=False, shard=shard,
            ordered=ordered,
        )
        if self.tiered is not None:
            # Evaluation scores against the MERGED logical table (cold
            # rows included — evaluation must not be blind to rows that
            # happen to be cold right now).  Small logical tables merge
            # densely; huge-V virtual stores score each batch against a
            # compact per-batch table instead (no dense table ever
            # materializes).
            if self._tiering_sharded and (
                len(self.tiered.owned) != self.tiered.num_shards
            ):
                raise RuntimeError(
                    "evaluate with fleet-sharded tiering needs every "
                    "shard's cold store; this rank owns "
                    f"{sorted(self.tiered.owned)} of "
                    f"{self.tiered.num_shards}.  Evaluate from the "
                    "saved checkpoint instead (it merges all shards)."
                )
            if self._tiered_eval_jit is None:
                self._tiered_eval_jit = jax.jit(
                    make_eval_step(self.cfg), donate_argnums=1
                )
            if not self.tiered.dense_save_ok:
                return self._evaluate_tiered_virtual(pipeline, ms)
            params = self._tiered_logical_params()
            for batch in pipeline:
                ms = self._tiered_eval_jit(
                    params, ms, self._put(batch, want_meta=False)
                )
            return _finalize_metrics(ms, self.cfg.loss_type)
        for batch in pipeline:
            ms = self._eval_step(
                self.state.params, ms, self._put(batch, want_meta=False)
            )
        return _finalize_metrics(ms, self.cfg.loss_type)

    def _data_fingerprint(self) -> dict:
        """Identity of the training input stream; the saved data position
        is only valid for an identical stream.  Everything that changes
        batch composition or order belongs here: files, batch size, seed,
        the shuffle window, and which ingest path (they shuffle with
        different RNG streams)."""
        fp = {
            "seed": self.cfg.seed,
            "batch_size": self.cfg.batch_size,
            "train_files": list(self.cfg.train_files),
            "shuffle_buffer": self.cfg.shuffle_buffer,
            "fast_ingest": self.cfg.fast_ingest,
            # Cached replays permute epoch-0 BATCHES per epoch while
            # streaming re-shuffles LINES — toggling the cache redefines
            # every epoch > 0, so a saved position must not survive it.
            "cache_epochs": self.cfg.cache_epochs,
        }
        # Prestacked replay permutes at SUPER-batch granularity, another
        # stream redefinition for epochs > 0.  Only stamped when on, so
        # fingerprints from pre-prestack checkpoints still match runs
        # that leave it off.
        if self.cfg.cache_prestacked:
            fp["cache_prestacked"] = True
            fp["steps_per_dispatch"] = self.cfg.steps_per_dispatch
        return fp

    def _evaluate_tiered_virtual(self, pipeline, ms) -> dict:
        """Huge-V tiered evaluation: sync the hot rows back once, then
        score every eval batch against a COMPACT per-batch table — the
        batch's unique rows gathered from the cold store, ids remapped
        to local indices.  Same math as a full-table gather (row values
        are identical), without ever materializing [V, D].  No new
        dispatches run during evaluation, so the synced cold store is a
        consistent snapshot."""
        self.tiered.sync_from_device(self._tier_host_tables())
        rep = NamedSharding(self.mesh, P())
        w0 = jax.device_put(self.state.params.w0, rep)
        vocab = self.cfg.vocabulary_size
        dim = self.cfg.embedding_dim
        for batch in pipeline:
            flat = batch.ids.reshape(-1)
            safe = np.where((flat >= 0) & (flat < vocab), flat, 0)
            u, inv = np.unique(safe, return_inverse=True)
            # Bucket-pad the compact table so the eval jit retraces
            # O(log) times, not once per distinct unique count.
            mp = tiered_lib._bucket(len(u))
            mini = np.zeros((mp, dim), np.float32)
            mini[:len(u)] = self.tiered.gather_logical(u)
            params = fm.FmParams(
                w0=w0, table=jax.device_put(mini, rep)
            )
            b = batch._replace(
                ids=inv.astype(np.int32).reshape(batch.ids.shape)
            )
            ms = self._tiered_eval_jit(
                params, ms, self._put(b, want_meta=False)
            )
        return _finalize_metrics(ms, self.cfg.loss_type)

    def _hot_host_tables(self) -> list:
        """np copies of the current device hot tables (params first),
        ordered like the manager's stores.  Blocks until the device is
        caught up — only called from checkpoint/eval paths."""
        tabs = (self.state.params.table,) + tiered_lib.get_opt_tables(
            self.cfg.optimizer, self.state.opt_state
        )
        return [np.asarray(t) for t in tabs]

    def _hot_host_tables_by_shard(self) -> dict:
        """Fleet view of :meth:`_hot_host_tables`: {shard -> np copies
        of that COLUMN's hot-table rows, params first} for this rank's
        owned shards, read straight from the addressable device shards
        (deduped across data-axis replicas) — a rank never materializes
        another rank's rows."""
        tabs = (self.state.params.table,) + tiered_lib.get_opt_tables(
            self.cfg.optimizer, self.state.opt_state
        )
        hs = self._dcfg.vocabulary_size // self.tiered.num_shards
        out = {s: [] for s in sorted(self.tiered.owned)}
        for t in tabs:
            got = {}
            for sh in t.addressable_shards:
                s = (sh.index[0].start or 0) // hs
                if s in got:
                    continue
                got[s] = np.asarray(sh.data)
            for s in out:
                out[s].append(got[s])
        return out

    def _tier_host_tables(self):
        """The host-table payload the active tier manager expects."""
        if self._tiering_sharded:
            return self._hot_host_tables_by_shard()
        return self._hot_host_tables()

    def _tiered_logical_params(self) -> fm.FmParams:
        """The merged logical params (hot written back over cold) as a
        replicated device FmParams — the eval/predict view of a tiered
        table.  Only feasible when the logical table materializes
        densely (small V); huge-V tiered runs score via the training
        path, not a merged table."""
        merged = self.tiered.merged_dense(self._tier_host_tables())
        rep = NamedSharding(self.mesh, P())
        return fm.FmParams(
            w0=jax.device_put(self.state.params.w0, rep),
            table=jax.device_put(merged[0], rep),
        )

    def _manifest_quality(self) -> Optional[dict]:
        """The training→serving skew reference: this run's cumulative
        feature/score sketches, published into ``serve_manifest.json``
        next to the checkpoint step so serving replicas can compare
        live request traffic against the distribution the model
        actually trained on.  None (no manifest key at all) before the
        first sketched batch or with quality off — a serving fleet
        reads absence as "no reference", never as an empty one."""
        sk = self._quality_sketch
        if sk is None:
            return None
        payload = sk.export()
        if payload is None:
            return None
        return {"quality": {
            "examples": sk.examples, "sketches": payload,
        }}

    def save(self, stepno: int):
        data_state = {
            "epoch": self._epoch,
            "batches_done": self._batches_done,
            "fingerprint": self._data_fingerprint(),
        }
        if self.tiered is None:
            checkpoint.save(
                self.cfg.model_file,
                self._restored_step + stepno,
                self.state.params,
                self.state.opt_state,
                data_state=data_state,
                manifest_extra=self._manifest_quality(),
            )
            return
        # Tiered: the checkpoint of record is the LOGICAL table.  Small
        # logical tables merge into the ordinary dense format (dense and
        # tiered runs interchange checkpoints freely, any hot_rows);
        # larger ones save the sparse overlay (tier-layout-independent,
        # tiered-restore only).
        cfg = self.cfg
        step = self._restored_step + stepno
        host_tables = self._tier_host_tables()
        w0 = np.asarray(self.state.params.w0)
        opt_scalars = tiered_lib.get_opt_scalars(
            cfg.optimizer, self.state.opt_state
        )
        if self.tiered.dense_save_ok:
            merged = self.tiered.merged_dense(host_tables)
            params = fm.FmParams(w0=w0, table=merged[0])
            if cfg.optimizer == "sgd":
                opt_state = ()
            else:
                # The device opt pytree with its table/w0 leaves swapped
                # for the merged logical numpy arrays.
                opt_state = tiered_lib.set_opt_tables(
                    cfg.optimizer,
                    tiered_lib.set_opt_scalars(
                        cfg.optimizer, self.state.opt_state, opt_scalars,
                        np.asarray,
                    ),
                    tuple(merged[1:]),
                )
            checkpoint.save(
                cfg.model_file, step, params, opt_state,
                data_state=data_state,
                manifest_extra=self._manifest_quality(),
            )  # checkpoint.save clears any stale overlay itself
            return
        scalars = {"w0": w0, **opt_scalars}
        if self._tiering_sharded:
            # Elastic per-shard files: every rank writes its OWNED
            # shards, a fleet barrier orders the writes before rank 0
            # cleans stale formats and publishes the manifest (torn
            # saves stay detectable: restore refuses a mixed/partial
            # shard set).
            barrier = None
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                def barrier():
                    multihost_utils.sync_global_devices(
                        "tffm_tiered_shard_save"
                    )
            checkpoint.save_tiered_shards(
                cfg.model_file, step, scalars,
                self.tiered.export_shard_overlays(host_tables),
                num_shards=self.tiered.num_shards,
                data_state=data_state,
                manifest_extra=self._manifest_quality(),
                primary=jax.process_index() == 0,
                barrier=barrier,
            )
            return
        checkpoint.save_tiered(
            cfg.model_file, step, scalars,
            self.tiered.export_overlay(host_tables),
            data_state=data_state,
            manifest_extra=self._manifest_quality(),
        )


def predict(cfg: FmConfig, mesh=None) -> int:
    """Score predict_files into score_path (reference predict mode, §3.3).

    Scores are written in input order, one per line — sigmoid probabilities
    for logistic loss, raw scores for mse.

    Scoring routes through the SAME fixed-shape scorer ladder the
    online serving path uses (fast_tffm_tpu/serve/scorer.py): batches
    pad into a small set of precompiled shapes (the file's batches plus
    ``serve_batch_sizes``), so ragged shapes never retrace — every
    compile is an explicit, accounted event (``record: compile`` when
    ``metrics_file`` is set; off-ladder shapes bump
    ``serve.recompiles_unexpected``), and served scores are
    bitwise-identical to this offline path by construction.  Tiered
    sparse-overlay checkpoints (``tiered.npz``) score through the
    compact per-batch remap (serve.OverlayScorer) instead of requiring
    a dense merge.
    """
    if not cfg.predict_files:
        raise ValueError("no predict_files configured")
    if jax.process_count() > 1:
        raise NotImplementedError(
            "predict runs single-process (the reference scored on one "
            "worker too); run it without jax.distributed — the sharded "
            "checkpoint restores fine on fewer devices"
        )
    from fast_tffm_tpu.serve import scorer as serve_scorer

    mesh = mesh if mesh is not None else mesh_lib.make_mesh(cfg)
    writer = (
        obs.JsonlWriter(cfg.metrics_file) if cfg.metrics_file else None
    )
    telemetry = obs.Telemetry(enabled=cfg.telemetry)
    n = 0
    try:
        scorer = serve_scorer.make_scorer(
            cfg, mesh=mesh, telemetry=telemetry, writer=writer,
            # The pipeline delivers [batch_size] batches; making that a
            # rung means the whole offline run compiles exactly once
            # per distinct shape it actually scores.
            extra_rungs=(cfg.batch_size,),
        )
        pipeline = BatchPipeline(
            cfg.predict_files, cfg, epochs=1, shuffle=False, ordered=True
        )
        with open(cfg.score_path, "w") as out:
            for batch in pipeline:
                scores = scorer.score(batch.ids, batch.vals, batch.fields)
                for s in scores[batch.weights > 0]:
                    out.write(f"{s:.6f}\n")
                    n += 1
    finally:
        if writer is not None:
            writer.close()
    log.info(
        "wrote %d scores to %s (%d scorer compile(s), checkpoint "
        "step %d)", n, cfg.score_path, scorer.compiles, scorer.step,
    )
    return n
