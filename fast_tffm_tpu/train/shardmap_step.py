"""Explicit shard_map sparse train step — `lookup = shardmap`.

The GSPMD-auto path (train.sparse under jit with shardings) lets XLA pick
the collectives for ``table[ids]`` with a row-sharded table; depending on
shapes that can materialize gathered rows across shards.  This module is
the hand-laid-out alternative, exploiting FM's algebra (SURVEY.md §7 step
4, models.fm.interaction_terms docstring):

  * The per-example terms (linear, s1, s2) are SUMS of per-feature
    contributions, and each feature's contribution depends only on the row
    its id owns.  So each model shard computes partial terms from ITS rows
    and a psum over the model axis of [b, 2k+1] floats replaces the whole
    row exchange — per-step model-axis traffic is ~KB where a gathered-row
    exchange is ~MB-GB.  This is the PS architecture inverted: row owners
    compute, examples aggregate.
  * The backward is the closed-form FmGrad (SURVEY.md §3.4): dV = g*x*(s1
    - v*x) needs only the psum'd s1 plus the shard's own rows — each
    shard computes gradients for exactly the occurrences it owns, locally.
  * Updates: per-shard dense (sum g, sum g^2) deltas via ops.sparse_apply's
    K1+K-place kernels, psum'd over the data axis (the sync-DP gradient
    allreduce), then the optimizer formula applied elementwise in place.

Scope: FM and field-aware FM with the sparse row-local optimizers
(adagrad/ftrl/sgd) and batch-mode (or zero) L2.  Dense optimizers stay on
the GSPMD-auto path.

FFM uses the same inversion (BASELINE config 5): the field-grouped sums
``S[b,p,q,:] = sum_{i: f_i=p} v_i^q x_i`` are linear in per-feature
contributions, so each shard computes a partial S from ITS rows and one
psum completes it; the closed-form backward
``dv_i^q = g x_i (S[q, f_i] - [q=f_i] v_i^{f_i} x_i)`` needs only the
completed S plus the shard's own rows — no row exchange, exactly like
FM's s1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.libsvm import Batch
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from fast_tffm_tpu.train.sparse import (
    ADAGRAD_EPS,
    SparseAdagradState,
    SparseFtrlState,
    StepAux,
    exchange_slots,
    resolve_exchange,
)


def supports_shardmap(cfg: FmConfig, mesh) -> bool:
    if cfg.optimizer not in ("adagrad", "ftrl", "sgd"):
        return False
    if cfg.l2_mode != "batch" and (cfg.factor_lambda or cfg.bias_lambda):
        return False
    model_shards = mesh.shape[MODEL_AXIS]
    return sparse_apply.supports_tile_sharded(
        cfg.vocabulary_size, cfg.optimizer, model_shards
    )


def _dscore(scores, labels, loss_type):
    if loss_type == "logistic":
        return jax.nn.sigmoid(scores) - labels
    return 2.0 * (scores - labels)  # mse


def _opt_tables(cfg: FmConfig, opt_state):
    if cfg.optimizer == "adagrad":
        return (opt_state.acc.table,)
    if cfg.optimizer == "ftrl":
        return (opt_state.z.table, opt_state.n.table)
    return ()


def _rebuild_opt(cfg: FmConfig, opt_state, new_tables, dw0, w0_old):
    lr = cfg.learning_rate
    if cfg.optimizer == "adagrad":
        acc_w0 = opt_state.acc.w0 + dw0 * dw0
        w0 = w0_old - lr * dw0 * jax.lax.rsqrt(acc_w0 + ADAGRAD_EPS)
        return w0, SparseAdagradState(
            acc=fm.FmParams(w0=acc_w0, table=new_tables[0])
        )
    if cfg.optimizer == "ftrl":
        n0_new = opt_state.n.w0 + dw0 * dw0
        sigma0 = (jnp.sqrt(n0_new) - jnp.sqrt(opt_state.n.w0)) / lr
        z0 = opt_state.z.w0 + dw0 - sigma0 * w0_old
        w0 = sparse_apply.ftrl_solve(
            z0, n0_new, lr, cfg.ftrl_l1, cfg.ftrl_l2, cfg.ftrl_beta
        )
        return w0, SparseFtrlState(
            z=fm.FmParams(w0=z0, table=new_tables[0]),
            n=fm.FmParams(w0=n0_new, table=new_tables[1]),
        )
    return w0_old - lr * dw0, opt_state  # sgd


def sparse_step_shardmap(cfg: FmConfig, params, opt_state, batch: Batch,
                         mesh, health: bool = False):
    """One sparse train step, hand-sharded. Returns (params, opt, scores),
    plus a :class:`StepAux` when ``health=True``: ``(grad_sq,
    nonfinite_count)``, each reduced locally from the shard's own
    (masked) occurrence grads and psum'd over BOTH mesh axes, so the
    monitor is global at the cost of two extra scalar collectives per
    step.  Under the entries exchange it adds ``[merged real entries,
    slots merged for them]`` summed over the model shards (gauges
    ``train.exchange_fill``, ``train.exchange_compact``), and where the
    exchange ends in the stream writer the row-touch mask the writer
    made of every data shard's real occurrences: bool [vocab] out of
    the shard_map as ``P(model)``, a model shard's own rows (every data
    replica of it merged the same stream)."""
    model_shards = mesh.shape[MODEL_AXIS]
    data_shards = mesh.shape[DATA_AXIS]
    vocab_local = cfg.vocabulary_size // model_shards
    k = cfg.factor_num
    n_opt = len(_opt_tables(cfg, opt_state))
    b_local = batch.vals.shape[0] // data_shards
    exchange = resolve_exchange(cfg, mesh, b_local * batch.vals.shape[1])
    touch = (health and exchange == "entries"
             and sparse_apply.exchange_takes_stream(data_shards))

    cd = cfg.compute_jnp_dtype

    def _fm_fwd_bwd(w0, rows, vals, labels, weights):
        """Plain FM: partial (linear, s1, s2) -> psum -> closed-form grad."""
        with jax.named_scope("tffm.fm_interaction_fwd"):
            w = rows[..., 0].astype(cd)
            v = rows[..., 1:].astype(cd)
            vals_c = vals.astype(cd)
            xv = v * vals_c[..., None]
            # Partial terms from this shard's rows; psum over model
            # completes them — the entire "lookup" is this [b, 2k+1]
            # collective.
            terms = jnp.concatenate(
                [
                    jnp.sum(w * vals_c, axis=-1, keepdims=True,
                            dtype=jnp.float32),  # linear
                    jnp.sum(xv, axis=1, dtype=jnp.float32),  # s1 [b, k]
                    jnp.sum(xv * xv, axis=1, dtype=jnp.float32),  # s2
                ],
                axis=-1,
            )
            terms = jax.lax.psum(terms, MODEL_AXIS)
            linear, s1, s2 = (terms[:, 0], terms[:, 1:1 + k],
                              terms[:, 1 + k:])
            inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
        with jax.named_scope("tffm.loss"):
            scores = w0 + linear + inter
            g, gx = _g_gx(scores, labels, weights, vals)
        with jax.named_scope("tffm.fm_interaction_bwd"):
            # Closed-form FmGrad for the occurrences this shard owns.
            dv = gx[..., None] * (s1[:, None, :] - xv.astype(jnp.float32))
            drows = jnp.concatenate([gx[..., None], dv], axis=-1)
        return scores, g, drows

    def _ffm_fwd_bwd(w0, rows, vals, fields, labels, weights):
        """Field-aware FM, same inversion: the field-grouped sums
        S[b,p,q,:] are per-shard-linear, so partial S + ONE psum replaces
        the row exchange; backward needs only the complete S plus own
        rows: dv_i^q = g x_i (S[q, f_i] - [q=f_i] v_i^{f_i} x_i)."""
        from fast_tffm_tpu.platform import ffm_compute_dtype

        ffm_cd = ffm_compute_dtype(cd)  # f32 off-TPU: CPU can't bf16-dot
        p_num = cfg.field_num
        b, f = vals.shape
        with jax.named_scope("tffm.ffm_interaction_fwd"):
            w = rows[..., 0].astype(ffm_cd)
            v = rows[..., 1:].astype(ffm_cd).reshape(b, f, p_num, k)
            vals_c = vals.astype(ffm_cd)
            oh = (
                fields[..., None] == jnp.arange(p_num, dtype=fields.dtype)
            ).astype(ffm_cd)  # [b, F, P]
            linear_p = jnp.sum(w * vals_c, axis=-1, dtype=jnp.float32)
            s_p = jnp.einsum(
                "bfp,bfqk->bpqk", oh * vals_c[..., None], v,
                preferred_element_type=jnp.float32,
            )  # [b, P, P, k] partial field-grouped sums
            v_own = jnp.einsum(
                "bfq,bfqk->bfk", oh, v, preferred_element_type=jnp.float32
            )  # v_i^{f_i}, zero off-shard (v is masked)
            self_p = jnp.sum(
                jnp.sum(v_own * v_own, axis=-1) * (vals * vals), axis=-1
            )
            terms = jnp.concatenate(
                [linear_p[:, None], self_p[:, None],
                 s_p.reshape(b, p_num * p_num * k)],
                axis=-1,
            )
            terms = jax.lax.psum(terms, MODEL_AXIS)
            linear, self_t = terms[:, 0], terms[:, 1]
            s_full = terms[:, 2:].reshape(b, p_num, p_num, k)
            cross = jnp.einsum("bpqk,bqpk->b", s_full, s_full)
            inter = 0.5 * (cross - self_t)
        with jax.named_scope("tffm.loss"):
            scores = w0 + linear + inter
            g, gx = _g_gx(scores, labels, weights, vals)
        with jax.named_scope("tffm.ffm_interaction_bwd"):
            oh32 = oh.astype(jnp.float32)
            # T[b,f,q,:] = S[b, q, f_i, :] — gather S's second field axis
            # by each occurrence's own field, as a one-hot matmul.
            t = jnp.einsum("bqpk,bfp->bfqk", s_full, oh32)
            dv = gx[..., None, None] * (
                t - oh32[..., None] * v_own[:, :, None, :]
                * vals[..., None, None]
            )  # [b, F, P, k]
            drows = jnp.concatenate(
                [gx[..., None], dv.reshape(b, f, p_num * k)], axis=-1
            )
        return scores, g, drows

    def _g_gx(scores, labels, weights, vals):
        # Global weighted-mean loss: normalizer spans the data axis.
        wsum = jax.lax.psum(jnp.sum(weights), DATA_AXIS)
        g = weights * _dscore(scores, labels, cfg.loss_type) / jnp.maximum(
            wsum, 1e-12
        )  # [b] dL/dscore
        return g, g[:, None] * vals  # gx [b, F]; caller masks via rows

    def device_fn(w0, table_l, labels, ids, vals, fields, weights,
                  *opt_tables_l):
        with jax.named_scope("tffm.row_gather"):
            m = jax.lax.axis_index(MODEL_AXIS)
            row_lo = m * vocab_local
            local = (ids >= row_lo) & (ids < row_lo + vocab_local)  # [b, F]
            lids = jnp.where(local, ids - row_lo, 0)
            maskf = local.astype(jnp.float32)
            rows = table_l[lids] * maskf[..., None]  # [b, F, D], 0 off-shard
        # bf16 mode (cd) rounds the [b, F, D] interaction operands (the
        # step's dominant HBM streams); sums accumulate f32, and the
        # psum'd terms, backward, and optimizer stay f32.
        if cfg.field_num:
            scores, g, drows = _ffm_fwd_bwd(
                w0, rows, vals, fields, labels, weights
            )
        else:
            scores, g, drows = _fm_fwd_bwd(w0, rows, vals, labels, weights)
        # Only occurrences this shard owns update its rows.
        with jax.named_scope("tffm.fm_interaction_bwd"
                             if not cfg.field_num
                             else "tffm.ffm_interaction_bwd"):
            drows = drows * maskf[..., None]
        if cfg.factor_lambda or cfg.bias_lambda:
            with jax.named_scope("tffm.loss"):
                # d/drow of l2_penalty_batch: 2*lambda*row/B per
                # occurrence.
                bsz = jax.lax.psum(jnp.float32(vals.shape[0]), DATA_AXIS)
                lam = jnp.concatenate([
                    jnp.full((1,), cfg.bias_lambda, jnp.float32),
                    jnp.full(
                        (rows.shape[-1] - 1,), cfg.factor_lambda,
                        jnp.float32
                    ),
                ])
                occ = ((vals != 0).astype(jnp.float32)[..., None]
                       * maskf[..., None])
                drows = drows + (2.0 / bsz) * lam * rows * occ
        # Local-coordinate occurrence list; off-shard -> sentinel row.
        b, f = vals.shape
        d = rows.shape[-1]  # 1 + k (FM) or 1 + field_num*k (FFM)
        with jax.named_scope("tffm.exchange" if exchange == "entries"
                             else "tffm.apply_prep"):
            ids_flat = jnp.where(
                local, ids - row_lo, vocab_local).reshape(b * f)
            g_flat = drows.reshape(b * f, d)
        if exchange == "entries":
            # Batch-proportional update exchange: dedupe locally, move
            # only the touched entries over the data axis, merge the S
            # sorted streams, apply through the stream writer (or K2).
            # Comms are independent of vocab — the reference's
            # IndexedSlices scaling property.  (ids_flat is already
            # local-coordinate with off-shard -> sentinel, the helper's
            # contract; drows already masked.)
            w_new, new_tables, exchanged, touched = _apply_stream(
                cfg, ids_flat.astype(jnp.int32), g_flat, table_l,
                opt_tables_l, data_shards=data_shards,
                real=(vals != 0).reshape(b * f) if touch else None,
            )
        else:
            delta = sparse_apply.dense_delta(
                ids_flat.astype(jnp.int32), g_flat,
                vocab=vocab_local, vocab_local=vocab_local, row_lo=0,
            )
            delta = jax.lax.psum(delta, DATA_AXIS)
            w_new, new_tables = _apply_delta(
                cfg, delta[:, :d], delta[:, d:], table_l, opt_tables_l
            )
        with jax.named_scope("tffm.loss"):
            dw0 = jax.lax.psum(jnp.sum(g), DATA_AXIS)
            if cfg.bias_lambda:
                # l2_penalty_batch includes bias_lambda*w0^2/B — its w0
                # grad must land here too or w0 diverges from the
                # scatter path.
                bsz_g = jax.lax.psum(jnp.float32(vals.shape[0]), DATA_AXIS)
                dw0 = dw0 + 2.0 * cfg.bias_lambda * w0 / bsz_g
        outs = (w_new, scores, dw0) + tuple(new_tables)
        if health:
            # Each occurrence's grad lives on exactly ONE model shard
            # (off-shard rows are masked to zero), so summing local
            # squares over both axes is the global occurrence-grad norm
            # — no double counting.  dw0 is already global; folded in
            # by the caller.
            with jax.named_scope("tffm.health"):
                gsq = jax.lax.psum(
                    jnp.sum(jnp.square(g_flat)), (MODEL_AXIS, DATA_AXIS)
                )
                nonfin = jax.lax.psum(
                    jnp.sum((~jnp.isfinite(g_flat)).astype(jnp.int32)),
                    (MODEL_AXIS, DATA_AXIS),
                )
                outs = outs + (gsq, nonfin)
                if exchange == "entries":
                    # every data replica of a model shard holds the same
                    # merged stream: count a shard's once
                    outs = outs + (jax.lax.psum(exchanged, MODEL_AXIS),)
            if touch:
                outs = outs + (touched,)
        return outs

    out_specs = (
        (P(MODEL_AXIS, None), P(DATA_AXIS), P())
        + (P(MODEL_AXIS, None),) * n_opt
        + ((P(), P()) if health else ())
        + ((P(),) if health and exchange == "entries" else ())
        + ((P(MODEL_AXIS),) if touch else ())
    )
    from jax import shard_map

    outs = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(
            (P(), P(MODEL_AXIS, None), P(DATA_AXIS), P(DATA_AXIS, None),
             P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS))
            + (P(MODEL_AXIS, None),) * n_opt
        ),
        out_specs=out_specs,
        check_vma=False,  # pallas_call outputs carry no vma annotations
    )(
        params.w0, params.table, batch.labels, batch.ids, batch.vals,
        batch.fields, batch.weights, *_opt_tables(cfg, opt_state),
    )
    table_new, scores, dw0 = outs[0], outs[1], outs[2]
    new_opt_tables = outs[3:3 + n_opt]
    w0_new, opt_new = _rebuild_opt(
        cfg, opt_state, new_opt_tables, dw0, params.w0
    )
    new_params = fm.FmParams(w0=w0_new, table=table_new)
    if health:
        gsq, nonfin = outs[3 + n_opt], outs[4 + n_opt]
        with jax.named_scope("tffm.health"):
            grad_sq = gsq + jnp.square(dw0)
            nonfin = nonfin + (~jnp.isfinite(dw0)).astype(jnp.int32)
        aux = StepAux(grad_sq, nonfin, touched=outs[-1] if touch else None)
        if exchange == "entries":
            # the host solves train.exchange_compact from the two
            # figures exchange_slots derives from cfg; a step traced at
            # other shapes would count slots that are neither
            cap = sparse_apply.entries_cap(
                b_local * batch.vals.shape[1], vocab_local)
            rung = sparse_apply.entries_rung(cap, data_shards)
            assert exchange_slots(cfg, mesh) == (
                rung and mesh.size * rung, mesh.size * cap), (cap, rung)
            aux = aux._replace(
                apply_rows=outs[5 + n_opt].astype(jnp.uint32))
        return new_params, opt_new, scores, aux
    return new_params, opt_new, scores


def make_exchange_probe(mesh):
    """Cross-rank barrier probe for the shard_map path: the same
    contract as train.sparse.make_exchange_probe, but lowered through
    an explicit ``psum`` over both mesh axes — the collective family
    THIS step uses (partial-terms psum / delta psum), so the probe's
    barrier rides the same channel as the step's exchange.  The
    dispatch loop enqueues it after each dispatch and blocks one
    dispatch later (``train.exchange`` timer; no pipeline bubble)."""
    import numpy as np
    from jax.sharding import NamedSharding

    from jax import shard_map

    spec = P((DATA_AXIS, MODEL_AXIS))
    reduce = jax.jit(shard_map(
        lambda x: jax.lax.psum(
            jnp.sum(x), (DATA_AXIS, MODEL_AXIS)
        ),
        mesh=mesh, in_specs=spec, out_specs=P(),
        check_vma=False,
    ))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec),
        np.ones((mesh.local_mesh.size,), np.float32),
        (mesh.size,),
    )

    def probe():
        return reduce(arr)

    return probe


def _apply_stream(cfg, lids, g_flat, w_l, opt_tables_l, *, data_shards,
                  real=None):
    """The entries exchange and the optimizer update on top of it
    (sparse_apply.entries_exchange_apply): same formulas as
    _apply_delta, run inside the stream writer (or the K2 tile kernel)
    over the shard's own tables — rows no data shard touched keep their
    bits.  Returns ``(table, opt tables, [merged real entries, slots
    merged for them], touched)``: the shard's row-touch mask where
    ``real`` is given and the writer runs, else None."""
    lr = cfg.learning_rate
    if cfg.optimizer == "adagrad":
        upd = functools.partial(
            sparse_apply.adagrad_update, lr=lr, eps=ADAGRAD_EPS
        )
    elif cfg.optimizer == "ftrl":
        upd = functools.partial(
            sparse_apply.ftrl_update,
            lr=lr, l1=cfg.ftrl_l1, l2=cfg.ftrl_l2, beta=cfg.ftrl_beta,
        )
    else:
        upd = functools.partial(sparse_apply.sgd_update, lr=lr)
    (w_new, *opt_new), merged, slots, touched = (
        sparse_apply.entries_exchange_apply(
            upd, (w_l,) + tuple(opt_tables_l), lids, g_flat,
            vocab_local=w_l.shape[0], data_axis=DATA_AXIS,
            model_axis=MODEL_AXIS, data_shards=data_shards, real=real,
        )
    )
    return w_new, tuple(opt_new), jnp.stack([merged, slots]), touched


def _apply_delta(cfg, g1, g2, w_l, opt_tables_l):
    """Optimizer update on (table shard, opt-table shards) -> new tables.

    Delegates to ops.sparse_apply's shared elementwise update functions so
    all sharded paths stay bit-identical.
    """
    lr = cfg.learning_rate
    if cfg.optimizer == "adagrad":
        w_new, acc_new = sparse_apply.adagrad_update(
            g1, g2, w_l, opt_tables_l[0], lr=lr, eps=ADAGRAD_EPS
        )
        return w_new, (acc_new,)
    if cfg.optimizer == "ftrl":
        w_new, z_new, n_new = sparse_apply.ftrl_update(
            g1, g2, w_l, *opt_tables_l,
            lr=lr, l1=cfg.ftrl_l1, l2=cfg.ftrl_l2, beta=cfg.ftrl_beta,
        )
        return w_new, (z_new, n_new)
    (w_new,) = sparse_apply.sgd_update(g1, g2, w_l, lr=lr)
    return w_new, ()
