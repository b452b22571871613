"""Sparse row-update training step — the IndexedSlices path, TPU-style.

The reference's PS trainer never touches the whole table per step: workers
pull only the gathered rows and push ``IndexedSlices`` updates for exactly
those rows (SURVEY.md §3.2).  A naive jit step loses that: autodiff w.r.t.
the table materializes a dense [V, D] gradient and the optimizer rewrites
every row — hundreds of GB/step of HBM traffic at Criteo-1TB vocabularies.

This step restores sparsity, TPU-style:

1. gather rows once: ``rows = table[ids]``,
2. differentiate the loss w.r.t. ``(w0, rows)`` — the Pallas FmGrad kernel
   produces per-occurrence row grads, never a dense table grad,
3. apply the optimizer to exactly the touched rows, by ``apply_mode``:
   the tile kernels (``tile`` / ``sharded``, ops.sparse_apply), or the XLA
   row ``scatter``.  On one device the scatter path first sorts the
   occurrences and sums ``g`` and ``g^2`` per unique row, then writes
   each row ONCE (ops.sparse_apply.scatter_apply_unique): the scatter
   costs ~0.1 us a written row on v5e whatever the data, and 69% of the
   occurrences of a Criteo-like Zipf(1.1) batch repeat a row of the same
   step (797k unique rows in 2.56 M occurrences; the hottest id has
   ~240,000).  On a multi-device mesh GSPMD partitions the
   per-occurrence form: ``acc.at[ids].add(g^2)`` then
   ``table.at[ids].add(-lr*g/sqrt(acc'))``.

Duplicate ids in a batch follow per-occurrence accumulator semantics on
every path (each occurrence adds its own g^2 — sum of squares, never the
square of the sum — and the shared denominator includes all of them): the
behavior of TF's SparseApplyAdagrad that the reference relies on, vs. the
dense path which squares the summed gradient.  Both are tested.

Per-step HBM traffic scales with B*F*D instead of V*D: at B=16k, F=39,
D=9 that is ~50 MB/step regardless of vocabulary size.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import interaction, sparse_apply
from fast_tffm_tpu.parallel import mesh as mesh_lib

ADAGRAD_EPS = 1e-7  # matches optax.adagrad's default eps


def apply_mode(cfg: FmConfig, mesh=None) -> str:
    """How sparse updates hit the table: 'scatter' | 'tile' | 'sharded'.

    'tile' (single device): fused K2 streams table+state once per step.
    'sharded' (multi device): per-device dense deltas psum'd over the data
    axis, applied to the local model shard under shard_map.  Both need a
    TILE-aligned (per-shard) vocabulary and a row-local optimizer;
    otherwise the XLA row-'scatter' path handles it: per occurrence via
    GSPMD on a multi-device mesh, deduped first on one device
    (sparse_step runs it as 'unique').
    """
    if cfg.sparse_apply == "scatter":
        return "scatter"
    multi = mesh is not None and mesh.size > 1
    if multi:
        ok = sparse_apply.supports_tile_sharded(
            cfg.vocabulary_size, cfg.optimizer,
            mesh.shape[mesh_lib.MODEL_AXIS],
        )
    else:
        ok = sparse_apply.supports_tile(cfg.vocabulary_size, cfg.optimizer)
    tiled = "sharded" if multi else "tile"
    if cfg.sparse_apply == "tile":
        if not ok:
            raise ValueError(
                "sparse_apply=tile needs a vocabulary_size divisible by "
                f"model_shards*{sparse_apply.TILE} and optimizer in "
                "adagrad/ftrl/sgd"
            )
        return tiled  # explicit: run even off-TPU (interpret mode, tests)
    # auto: only where the Mosaic kernels actually run (TPU) — interpret
    # mode on CPU is a correctness tool, far slower than XLA scatter.
    from fast_tffm_tpu.platform import is_tpu_backend

    if ok and is_tpu_backend():
        return tiled
    return "scatter"


def resolve_exchange(cfg: FmConfig, mesh, n_local_occ=None) -> str:
    """``cfg.sparse_exchange`` resolved for ``mesh``: the one rule that
    the hand-sharded step, the GSPMD 'sharded' apply, the overlap and
    the gauges all read.

    "dense" psums a [vocab_local, 2D] delta over the data axis — bytes
    grow with vocab, independent of the batch.  "entries" all-gathers
    the deduped touched-row streams — bytes grow with the batch,
    independent of vocab (the reference PS design's IndexedSlices
    scaling, SURVEY.md §3.2).  "auto" picks whichever moves fewer ring
    words per device, weighing the dense all-reduce at 2x its buffer
    (reduce-scatter + all-gather phases — see
    sparse_apply.resolve_exchange).

    ``n_local_occ``: a data shard's occurrences a step; a step being
    traced passes its batch's own, everything else leaves it to the
    cfg's batch (the same number wherever the batch is the cfg's)."""
    data_shards = mesh.shape[mesh_lib.DATA_AXIS]
    if n_local_occ is None:
        n_local_occ = cfg.batch_size * cfg.max_features // data_shards
    return sparse_apply.resolve_exchange(
        cfg.sparse_exchange,
        n_local_occ=n_local_occ,
        vocab_local=cfg.vocabulary_size // mesh.shape[mesh_lib.MODEL_AXIS],
        d=cfg.embedding_dim,
        data_shards=data_shards,
    )


def exchange_mode(cfg: FmConfig, mesh=None):
    """The exchange over the data axis that the step compiled for
    ``cfg`` on ``mesh`` holds (gauge ``train.exchange_mode``):
    resolve_exchange's "entries" or "dense" under the hand-sharded step
    (``lookup=shardmap``) and the GSPMD 'sharded' apply, None where the
    step exchanges nothing (one device, the per-occurrence GSPMD
    scatter, a dense optimizer)."""
    if mesh is None or mesh.size == 1 or not supports_sparse(cfg):
        return None
    if cfg.lookup != "shardmap" and apply_mode(cfg, mesh) != "sharded":
        return None
    return resolve_exchange(cfg, mesh)


def apply_stream(cfg: FmConfig, mesh=None) -> bool:
    """Whether the step compiled for ``cfg`` on ``mesh`` writes its
    touched rows with the transposed tile stream (gauge
    ``train.apply_stream``): the one-device scatter apply, where
    ops.sparse_apply's rule takes the stream for the step's shapes; on
    a mesh, the entries exchange wherever its merged stream goes through
    the stream writer (sparse_apply.exchange_takes_stream)."""
    if not supports_sparse(cfg):
        return False
    if mesh is not None and mesh.size > 1:
        return exchange_mode(cfg, mesh) == "entries" and (
            sparse_apply.exchange_takes_stream(
                mesh.shape[mesh_lib.DATA_AXIS]))
    if apply_mode(cfg, mesh) != "scatter":
        return False
    return sparse_apply.takes_stream(
        cfg.batch_size * cfg.max_features, cfg.vocabulary_size,
        cfg.embedding_dim, {"adagrad": 2, "ftrl": 3, "sgd": 1}[cfg.optimizer],
    )


class SparseAdagradState(NamedTuple):
    acc: fm.FmParams  # per-weight squared-gradient accumulators


class SparseFtrlState(NamedTuple):
    z: fm.FmParams
    n: fm.FmParams


def supports_sparse(cfg: FmConfig) -> bool:
    """Sparse updates need a row-local optimizer and row-local (batch) L2
    (or no L2 at all — l2_mode is irrelevant when both lambdas are 0)."""
    if cfg.optimizer not in ("adagrad", "ftrl", "sgd"):
        return False
    return cfg.l2_mode == "batch" or not (cfg.factor_lambda or cfg.bias_lambda)


def init_sparse_opt_state(cfg: FmConfig, params: fm.FmParams):
    if cfg.optimizer == "adagrad":
        acc = jax.tree.map(
            lambda p: jnp.full_like(p, cfg.adagrad_initial_accumulator), params
        )
        return SparseAdagradState(acc=acc)
    if cfg.optimizer == "ftrl":
        # z initialized so the FTRL closed form reproduces the incoming
        # params (warm-start correctness; see optimizers.ftrl).
        denom0 = (
            cfg.ftrl_beta + jnp.sqrt(cfg.adagrad_initial_accumulator)
        ) / cfg.learning_rate + cfg.ftrl_l2
        z = jax.tree.map(
            lambda p: -p * denom0 - jnp.sign(p) * cfg.ftrl_l1, params
        )
        n = jax.tree.map(
            lambda p: jnp.full_like(p, cfg.adagrad_initial_accumulator), params
        )
        return SparseFtrlState(z=z, n=n)
    if cfg.optimizer == "sgd":
        return ()
    raise ValueError(f"no sparse path for optimizer {cfg.optimizer!r}")


def _rows_loss_fn(
    cfg: FmConfig, batch: Batch, mesh=None, data_axis: str = "data",
    compute_dtype=jnp.float32,
):
    """loss(w0, rows) over the gathered rows — autodiff target.

    ``compute_dtype=bfloat16`` rounds the interaction inputs (rows, vals)
    to bf16 — halving the [B,F,D] HBM streams, the sparse step's dominant
    traffic — while scores, loss, and gradients stay f32 (the cast is
    inside the autodiff region, so row cotangents come back f32 for the
    optimizer).
    """

    def loss_fn(w0, rows):
        if cfg.field_num:
            # Closed-form FFM op (ops.interaction.ffm_interaction): same
            # forward math as fm.ffm_scores_from_rows, backward via the
            # shardmap inversion's closed form instead of autodiff
            # through the einsum chain — w0 enters linearly outside.
            scores = (
                w0.astype(jnp.float32) + interaction.ffm_interaction(
                    rows, batch.vals, batch.fields, cfg.factor_num,
                    cfg.field_num, compute_dtype,
                )
            )
        else:
            scores = w0 + interaction.fm_interaction_sharded(
                rows.astype(compute_dtype),
                batch.vals.astype(compute_dtype),
                cfg.interaction_resolved, mesh, data_axis,
            )
        per_ex = fm.example_losses(scores, batch.labels, cfg.loss_type)
        wsum = jnp.maximum(jnp.sum(batch.weights), 1e-12)
        data_loss = jnp.sum(per_ex * batch.weights) / wsum
        reg = jnp.zeros((), jnp.float32)
        if cfg.factor_lambda or cfg.bias_lambda:
            reg = fm.l2_penalty_batch(
                fm.FmParams(w0=w0, table=rows), rows, batch.vals,
                cfg.factor_lambda, cfg.bias_lambda,
            )
        return data_loss + reg, scores

    return loss_fn


def overlap_active(cfg: FmConfig, mesh=None) -> bool:
    """Resolve ``cfg.sparse_exchange_overlap`` against the path actually
    taken: compute-overlapped exchange needs the entries exchange's id
    plane (the deduped row streams are a pure function of batch ids, so
    they can be computed one dispatch ahead) — i.e. the GSPMD 'sharded'
    apply with resolved exchange 'entries' over >1 data shard.

    'auto' enables exactly when those hold; 'on' refuses loudly when they
    don't (a silently inert knob would fake the overlap win); 'off' never
    overlaps.  Callers pass the cfg the step actually runs with (the
    hot-table _dcfg under tiering, whose vocabulary is the hot size).
    """
    if cfg.sparse_exchange_overlap == "off":
        return False
    ok = mesh is not None and mesh.shape[mesh_lib.DATA_AXIS] > 1
    if ok:
        ok = supports_sparse(cfg) and apply_mode(cfg, mesh) == "sharded"
    if ok:
        ok = resolve_exchange(cfg, mesh) == "entries"
    if cfg.sparse_exchange_overlap == "on" and not ok:
        raise ValueError(
            "sparse_exchange_overlap=on requires the sharded sparse apply "
            "with resolved exchange 'entries' over >1 data shard (got "
            f"mesh={None if mesh is None else dict(mesh.shape)}, "
            f"sparse_exchange={cfg.sparse_exchange!r}); use 'auto' to "
            "overlap opportunistically"
        )
    return ok


def _apply_adagrad(cfg, params, opt, ids, g_rows, dw0, w_rows,
                   mode="scatter", mesh=None, meta=None, rows_all=None):
    del w_rows  # adagrad needs no pre-update weights
    # Same formula as optax.scale_by_rss: u = g * rsqrt(acc_new + eps),
    # so sparse and dense paths agree exactly on duplicate-free batches.
    lr = cfg.learning_rate
    unique = None
    if mode == "sharded":
        table, acc_table = sparse_apply.adagrad_apply_sharded(
            params.table, opt.acc.table, ids, g_rows,
            lr=lr, eps=ADAGRAD_EPS, mesh=mesh,
            data_axis=mesh_lib.DATA_AXIS, model_axis=mesh_lib.MODEL_AXIS,
            exchange=resolve_exchange(
                cfg, mesh, ids.shape[0] // mesh.shape[mesh_lib.DATA_AXIS]),
            rows_all=rows_all,
        )
    elif mode == "tile":
        table, acc_table = sparse_apply.adagrad_apply(
            params.table, opt.acc.table, ids, g_rows,
            lr=lr, eps=ADAGRAD_EPS, meta=meta,
        )
    elif mode == "unique":
        (table, acc_table), unique = sparse_apply.scatter_apply_unique(
            partial(sparse_apply.adagrad_update, lr=lr, eps=ADAGRAD_EPS),
            (params.table, opt.acc.table), ids, g_rows, additive=True,
        )
    else:
        acc_table = opt.acc.table.at[ids].add(g_rows * g_rows)
        acc_rows = acc_table[ids]  # post-update accumulators, touched rows
        table = params.table.at[ids].add(
            -lr * g_rows * jax.lax.rsqrt(acc_rows + ADAGRAD_EPS)
        )
    acc_w0 = opt.acc.w0 + dw0 * dw0
    w0 = params.w0 - lr * dw0 * jax.lax.rsqrt(acc_w0 + ADAGRAD_EPS)
    return (
        fm.FmParams(w0=w0, table=table),
        SparseAdagradState(acc=fm.FmParams(w0=acc_w0, table=acc_table)),
        unique,
    )


# One shared closed form across scatter / tile-kernel / sharded paths.
_ftrl_solve = sparse_apply.ftrl_solve


def _apply_ftrl(cfg, params, opt, ids, g_rows, dw0, w_rows,
                mode="scatter", mesh=None, meta=None, rows_all=None):
    lr, l1, l2, beta = (
        cfg.learning_rate, cfg.ftrl_l1, cfg.ftrl_l2, cfg.ftrl_beta,
    )
    unique = None
    if mode == "sharded":
        table, z_table, n_table = sparse_apply.ftrl_apply_sharded(
            params.table, opt.z.table, opt.n.table, ids, g_rows,
            lr=lr, l1=l1, l2=l2, beta=beta, mesh=mesh,
            data_axis=mesh_lib.DATA_AXIS, model_axis=mesh_lib.MODEL_AXIS,
            exchange=resolve_exchange(
                cfg, mesh, ids.shape[0] // mesh.shape[mesh_lib.DATA_AXIS]),
            rows_all=rows_all,
        )
    elif mode == "tile":
        table, z_table, n_table = sparse_apply.ftrl_apply(
            params.table, opt.z.table, opt.n.table, ids, g_rows,
            lr=lr, l1=l1, l2=l2, beta=beta, meta=meta,
        )
    elif mode == "unique":
        # Unique rows: ftrl_update's single -sigma*w per row is the whole
        # duplicate-id care (see the GSPMD branch below for what it
        # takes per occurrence).
        (table, z_table, n_table), unique = (
            sparse_apply.scatter_apply_unique(
                partial(sparse_apply.ftrl_update,
                        lr=lr, l1=l1, l2=l2, beta=beta),
                (params.table, opt.z.table, opt.n.table), ids, g_rows,
            )
        )
    else:
        # Rows: FTRL recursion on the touched rows (w_rows is the
        # pre-update gather from sparse_step, reused — no second gather).
        #
        # Duplicate-id care: z must receive each occurrence's gradient ONCE
        # but the -sigma*w correction only once PER ROW.  Scatter-adding
        # (g - sigma*w) per occurrence would apply -sigma*w k times for a
        # row appearing k times — a positive feedback on w that diverges (w
        # grows, |z| grows with it, the closed form returns a larger w,
        # ...).  So: per-occurrence scatter-add of g, then a
        # gather-modify-set for the sigma correction.  All quantities in
        # the set are identical across duplicates (n_old/n_new/w pre-update
        # are per-row), so the duplicate writes are well-defined.
        n_old_rows = opt.n.table[ids]
        n_table = opt.n.table.at[ids].add(g_rows * g_rows)
        n_new_rows = n_table[ids]  # for dups: includes all occurrences' g^2
        sigma = (jnp.sqrt(n_new_rows) - jnp.sqrt(n_old_rows)) / lr
        zg_table = opt.z.table.at[ids].add(g_rows)
        z_rows = zg_table[ids] - sigma * w_rows
        z_table = zg_table.at[ids].set(z_rows)
        new_w_rows = _ftrl_solve(z_rows, n_new_rows, lr, l1, l2, beta)
        table = params.table.at[ids].set(new_w_rows)
    # w0 (dense scalar path, shared by both table branches).
    n0_new = opt.n.w0 + dw0 * dw0
    sigma0 = (jnp.sqrt(n0_new) - jnp.sqrt(opt.n.w0)) / lr
    z0 = opt.z.w0 + dw0 - sigma0 * params.w0
    w0 = _ftrl_solve(z0, n0_new, lr, l1, l2, beta)
    return (
        fm.FmParams(w0=w0, table=table),
        SparseFtrlState(
            z=fm.FmParams(w0=z0, table=z_table),
            n=fm.FmParams(w0=n0_new, table=n_table),
        ),
        unique,
    )


def _apply_sgd(cfg, params, opt, ids, g_rows, dw0, w_rows,
               mode="scatter", mesh=None, meta=None, rows_all=None):
    del w_rows
    lr = cfg.learning_rate
    unique = None
    if mode == "sharded":
        table = sparse_apply.sgd_apply_sharded(
            params.table, ids, g_rows, lr=lr, mesh=mesh,
            data_axis=mesh_lib.DATA_AXIS, model_axis=mesh_lib.MODEL_AXIS,
            exchange=resolve_exchange(
                cfg, mesh, ids.shape[0] // mesh.shape[mesh_lib.DATA_AXIS]),
            rows_all=rows_all,
        )
    elif mode == "tile":
        table = sparse_apply.sgd_apply(
            params.table, ids, g_rows, lr=lr, meta=meta)
    elif mode == "unique":
        (table,), unique = sparse_apply.scatter_apply_unique(
            partial(sparse_apply.sgd_update, lr=lr),
            (params.table,), ids, g_rows, additive=True,
        )
    else:
        table = params.table.at[ids].add(-lr * g_rows)
    return fm.FmParams(w0=params.w0 - lr * dw0, table=table), opt, unique


_APPLY = {"adagrad": _apply_adagrad, "ftrl": _apply_ftrl, "sgd": _apply_sgd}


def make_exchange_probe(mesh):
    """Cross-rank barrier probe for the GSPMD sparse path: a tiny
    jitted all-reduce (one float per device, summed to a replicated
    scalar — GSPMD lowers it to the same all-reduce family the
    sharded apply's psum uses) that the dispatch loop enqueues right
    after each dispatch and blocks on ONE DISPATCH LATER (the
    HealthState discipline — no pipeline bubble).  Because the probe
    is enqueued behind the dispatch on every rank's stream, the
    delayed blocking wait measures exactly the straggler-induced
    collective wall: ~0 when the fleet is in step, the slowest rank's
    lag otherwise.  Feeds the ``train.exchange`` timer and the fleet
    block's ``exchange_frac``.

    Returns ``probe() -> jax.Array`` (async; callers block on the
    result to time the barrier)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(
        mesh, P((mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    )
    arr = jax.make_array_from_process_local_data(
        sharding,
        np.ones((mesh.local_mesh.size,), np.float32),
        (mesh.size,),
    )
    reduce = jax.jit(
        jnp.sum, out_shardings=NamedSharding(mesh, P())
    )

    def probe():
        return reduce(arr)

    return probe


def grad_health(g_rows, dw0):
    """(grad_sq, nonfinite_count) for a step's gradients — the on-device
    training-health aux the scan carry accumulates (train.loop).

    ``grad_sq`` is the squared global gradient norm at OCCURRENCE
    granularity: duplicate ids in a batch contribute per occurrence
    (matching the per-occurrence accumulator semantics of the sparse
    optimizers), where a dense table-gradient norm would first sum
    duplicates per row.  For a health monitor the distinction is noise;
    for NaN detection it is irrelevant (any non-finite occurrence grad
    poisons the row either way).
    """
    grad_sq = jnp.sum(jnp.square(g_rows)) + jnp.square(dw0)
    nonfinite = (
        jnp.sum((~jnp.isfinite(g_rows)).astype(jnp.int32))
        + (~jnp.isfinite(dw0)).astype(jnp.int32)
    )
    return grad_sq, nonfinite


def sparse_step(
    cfg: FmConfig, params: fm.FmParams, opt_state, batch: Batch,
    mesh=None, data_axis: str = "data", health: bool = False,
    rows_all=None,
):
    """One sparse train step. Returns (params, opt_state, scores), plus
    a ``(grad_sq, nonfinite_count)`` health aux when ``health=True``
    (computed from the per-occurrence row grads this step already
    materialized — no extra memory traffic); the single-device scatter
    apply appends its ``[unique rows written, occurrences]`` pair.

    ``rows_all`` is the prefetched entries-exchange id plane (see
    ops.sparse_apply.make_entries_prefetch) — only legal on the sharded
    entries path, where it lifts the deduped-stream all-gather off the
    critical path (compute-overlapped exchange)."""
    with jax.named_scope("tffm.row_gather"):
        rows = params.table[batch.ids]  # [B, F, D]
    loss_fn = _rows_loss_fn(
        cfg, batch, mesh, data_axis, compute_dtype=cfg.compute_jnp_dtype
    )
    (_, scores), (dw0, drows) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True
    )(params.w0, rows)
    b, f, d = drows.shape
    ids = batch.ids.reshape(b * f)
    g_rows = drows.reshape(b * f, d)
    mode = apply_mode(cfg, mesh)
    if rows_all is not None and mode != "sharded":
        raise ValueError(
            f"prefetched exchange streams need apply mode 'sharded', got "
            f"{mode!r}"
        )
    if mode == "scatter" and (mesh is None or mesh.size == 1):
        # One device: dedup first, write each touched row once.  The
        # GSPMD scatter of a multi-device mesh stays per occurrence.
        mode = "unique"
    params, opt_state, unique = _APPLY[cfg.optimizer](
        cfg, params, opt_state, ids, g_rows, dw0, rows.reshape(b * f, d),
        mode=mode, mesh=mesh,
        meta=batch.sort_meta if mode == "tile" else None,
        rows_all=rows_all,
    )
    if health:
        aux = grad_health(g_rows, dw0)
        if unique is not None:
            # (rows written, occurrences they were merged from): what
            # train.apply_unique_frac is the ratio of.
            aux += (jnp.stack([unique, b * f]).astype(jnp.uint32),)
        return params, opt_state, scores, aux
    return params, opt_state, scores
