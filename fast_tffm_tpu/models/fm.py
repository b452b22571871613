"""FM / field-aware FM model core — the pure-jnp oracle.

Numeric spec (reference ``FmScorer``, SURVEY.md §3.4):

    score_e = w0 + sum_i w[i]*x_i
                 + 0.5 * sum_f [ (sum_i V[i,f]*x_i)^2 - sum_i V[i,f]^2*x_i^2 ]

The parameter store is ONE table ``[vocab, D]`` whose column 0 is the linear
weight and columns 1: the factor vector(s) — mirroring the reference's
combined bias+factor rows (SURVEY.md §2 #5) and giving a single gather per
batch.  For field-aware FM (BASELINE config 5) ``D = 1 + field_num*k`` and
the interaction uses per-field factors ``<v_{i,f_j}, v_{j,f_i}>``.

Everything here is jit-friendly: static shapes, no Python branching on traced
values.  Padded feature slots carry ``val == 0`` and thus contribute nothing
to the score or its gradient.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from fast_tffm_tpu.config import FmConfig


class FmParams(NamedTuple):
    w0: jax.Array  # [] global bias
    table: jax.Array  # [vocab, 1 + k] or [vocab, 1 + field_num*k]


def init_params(rng: jax.Array, cfg: FmConfig, dtype=jnp.float32) -> FmParams:
    """Uniform init in ±init_value_range (reference behavior, SURVEY.md §2 #5)."""
    table = jax.random.uniform(
        rng,
        (cfg.vocabulary_size, cfg.embedding_dim),
        dtype=dtype,
        minval=-cfg.init_value_range,
        maxval=cfg.init_value_range,
    )
    return FmParams(w0=jnp.zeros((), dtype), table=table)


def interaction_terms(
    rows: jax.Array,  # [B, F, 1+k] gathered table rows
    vals: jax.Array,  # [B, F]
    compute_dtype=jnp.float32,
):
    """Per-example (linear, s1, s2) partial sums for plain FM.

    These are linear in per-feature contributions, so a row-sharded backend
    can compute them per shard and psum (SURVEY.md §7 step 4); the final
    squaring happens in :func:`scores_from_terms` after the reduction.
    """
    rows = rows.astype(compute_dtype)
    vals = vals.astype(compute_dtype)
    w = rows[..., 0]  # [B, F]
    v = rows[..., 1:]  # [B, F, k]
    # bf16 mode rounds the products; sums still accumulate in f32 (the
    # s1^2 - s2 cancellation in scores_from_terms amplifies sum error).
    linear = jnp.sum(w * vals, axis=-1, dtype=jnp.float32)  # [B]
    xv = v * vals[..., None]  # [B, F, k]
    s1 = jnp.sum(xv, axis=1, dtype=jnp.float32)  # [B, k]
    s2 = jnp.sum(xv * xv, axis=1, dtype=jnp.float32)  # [B, k]
    return linear, s1, s2


def scores_from_terms(w0, linear, s1, s2) -> jax.Array:
    return w0 + linear + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)


def ffm_scores_from_rows(
    w0: jax.Array,
    rows: jax.Array,  # [B, F, 1 + field_num*k]
    vals: jax.Array,  # [B, F]
    fields: jax.Array,  # [B, F] int32
    factor_num: int,
    field_num: int,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Field-aware FM: score = w0 + sum w_i x_i + sum_{i<j} <v_{i,f_j}, v_{j,f_i}> x_i x_j.

    MXU-friendly field-grouped form (no per-example gathers): with
    S[b,p,q,:] = sum_{i: f_i = p} v_i^q * x_i (a batched one-hot matmul),

        sum_{i != j} <v_i^{f_j}, v_j^{f_i}> x_i x_j
            = sum_{p,q} <S[p,q], S[q,p]> - sum_i <v_i^{f_i}, v_i^{f_i}> x_i^2

    and the strict-upper-triangle sum is half of that.  This replaces the
    naive [B,F,F,k] pairwise tensor (a ~800MB intermediate at Criteo
    shapes, built by row gathers) with two einsum-matmuls over [B,P,P,k].
    """
    from fast_tffm_tpu.platform import (
        ffm_compute_dtype, ffm_matmul_precision,
    )

    # Off-TPU the einsum operands fall back to f32 (XLA:CPU cannot run
    # bf16 dots) — see platform.ffm_compute_dtype, the one copy of that
    # gate.
    compute_dtype = ffm_compute_dtype(compute_dtype)
    prec = ffm_matmul_precision(compute_dtype)
    rows = rows.astype(compute_dtype)
    vals = vals.astype(compute_dtype)
    b, f = vals.shape
    w = rows[..., 0]
    v = rows[..., 1:].reshape(b, f, field_num, factor_num)  # [B,F,P,k]
    # bf16 mode: bf16 operands, f32 accumulation/result throughout.
    linear = jnp.sum(w * vals, axis=-1, dtype=jnp.float32)
    oh = (
        fields[..., None] == jnp.arange(field_num, dtype=fields.dtype)
    ).astype(compute_dtype)  # [B, F, P] pure field one-hot
    s = jnp.einsum(
        "bfp,bfqk->bpqk", oh * vals[..., None], v, precision=prec,
        preferred_element_type=jnp.float32,
    )
    cross = jnp.einsum("bpqk,bqpk->b", s, s, precision=prec)  # s is f32
    v_own = jnp.einsum(
        "bfq,bfqk->bfk", oh, v, precision=prec,
        preferred_element_type=jnp.float32,
    )  # v_i^{f_i}
    self_term = jnp.sum(
        jnp.sum(v_own * v_own, axis=-1)
        * (vals * vals).astype(jnp.float32),
        axis=-1,
    )
    inter = 0.5 * (cross - self_term)
    return (w0 + linear + inter).astype(jnp.float32)


def scores_from_rows(
    w0: jax.Array,
    rows: jax.Array,  # [B, F, D] gathered (and, if needed, dequantized)
    vals: jax.Array,  # [B, F]
    fields: Optional[jax.Array],
    *,
    factor_num: int,
    field_num: int = 0,
    compute_dtype=jnp.float32,
    impl: Optional[str] = None,
) -> jax.Array:
    """Score from pre-gathered rows — the shared tail of the fp32 and
    quantized forwards (plain FM and FFM both).  ``rows`` may arrive
    in any storage dtype (f32, bf16, or int8 already widened by
    ops.quant.dequant_gathered): both score paths upcast operands to
    the compute dtype and accumulate in f32.

    ``impl`` routes the plain-FM interaction through an alternative
    ops.interaction formulation ("pallas" | "flat") — the autotuner's
    serving-side promotion hook (parity-gated against this reference
    path by ops.autotune).  None/"jnp" is the reference math; FFM
    always uses its closed-form path regardless.
    """
    if field_num:
        assert fields is not None
        return ffm_scores_from_rows(
            w0, rows, vals, fields, factor_num, field_num, compute_dtype
        )
    if impl not in (None, "", "jnp"):
        from fast_tffm_tpu.ops import interaction as interaction_ops

        scores, _ = interaction_ops._forward(
            rows.astype(compute_dtype), vals.astype(compute_dtype), impl
        )
        return w0.astype(jnp.float32) + scores
    linear, s1, s2 = interaction_terms(rows, vals, compute_dtype)
    return scores_from_terms(w0.astype(compute_dtype), linear, s1, s2)


def fm_scores(
    params: FmParams,
    ids: jax.Array,  # [B, F] int32
    vals: jax.Array,  # [B, F] float32
    fields: Optional[jax.Array] = None,
    *,
    factor_num: int,
    field_num: int = 0,
    compute_dtype=jnp.float32,
    impl: Optional[str] = None,
) -> jax.Array:
    """Oracle forward: gather + score. One `take` = one gather op for XLA.

    ``params.table`` may be stored bf16 (the compact serving format):
    the gather reads compact rows and :func:`scores_from_rows` widens
    them in-register — XLA fuses the cast into the gather.  ``impl``
    passes through to :func:`scores_from_rows` (the autotuner's
    serving-side routing; None = reference).
    """
    rows = params.table[ids]  # [B, F, D]
    return scores_from_rows(
        params.w0, rows, vals, fields,
        factor_num=factor_num, field_num=field_num,
        compute_dtype=compute_dtype, impl=impl,
    )


def fm_scores_dequant(
    w0: jax.Array,
    codes: jax.Array,  # [V, D] int8 table codes
    scales: jax.Array,  # [ceil(V/chunk)] f32 scale chunks
    chunk: int,
    ids: jax.Array,  # [B, F] int32
    vals: jax.Array,  # [B, F] float32
    fields: Optional[jax.Array] = None,
    *,
    factor_num: int,
    field_num: int = 0,
    compute_dtype=jnp.float32,
    impl: Optional[str] = None,
) -> jax.Array:
    """Forward over an int8-quantized table: gather compact codes (a
    quarter of the fp32 row bytes) plus each row's scale chunk, widen
    in-register (ops.quant.dequant_gathered), score.  Identical math
    to :func:`fm_scores` on the dequantized table, pinned by
    tests/test_quant.py.  ``impl`` passes through to
    :func:`scores_from_rows` (autotuner routing; None = reference)."""
    from fast_tffm_tpu.ops import quant

    code_rows = codes[ids]  # [B, F, D] int8
    scale_rows = scales[ids // chunk if chunk > 1 else ids]
    rows = quant.dequant_gathered(code_rows, scale_rows)
    return scores_from_rows(
        w0, rows, vals, fields,
        factor_num=factor_num, field_num=field_num,
        compute_dtype=compute_dtype, impl=impl,
    )


def example_losses(scores: jax.Array, labels: jax.Array, loss_type: str) -> jax.Array:
    if loss_type == "logistic":
        # Numerically stable BCE-with-logits (labels in {0,1}).
        return jax.nn.softplus(scores) - labels * scores
    elif loss_type == "mse":
        d = scores - labels
        return d * d
    raise ValueError(f"unknown loss_type {loss_type!r}")


def l2_penalty_batch(
    params: FmParams,
    rows: jax.Array,  # [B, F, D] the rows this batch touched
    vals: jax.Array,  # [B, F] (0 marks padding)
    factor_lambda: float,
    bias_lambda: float,
) -> jax.Array:
    """Sparse-friendly L2: regularize only rows touched by the batch.

    The reference's dense full-table ``tf.nn.l2_loss`` would make every update
    dense — unaffordable for a row-sharded 1e9-row table — so the default
    regularizes per occurrence, normalized by batch size.  ``l2_mode=full``
    in the config selects the exact dense penalty instead.
    """
    mask = (vals != 0).astype(rows.dtype)[..., None]  # [B, F, 1]
    b = vals.shape[0]
    w_sq = jnp.sum((rows[..., :1] * mask) ** 2)
    v_sq = jnp.sum((rows[..., 1:] * mask) ** 2)
    return (factor_lambda * v_sq + bias_lambda * (w_sq + params.w0**2)) / b


def l2_penalty_full(
    params: FmParams, factor_lambda: float, bias_lambda: float
) -> jax.Array:
    w_sq = jnp.sum(params.table[:, 0] ** 2)
    v_sq = jnp.sum(params.table[:, 1:] ** 2)
    return factor_lambda * v_sq + bias_lambda * (w_sq + params.w0**2)


def loss_and_metrics(
    params: FmParams,
    labels: jax.Array,
    ids: jax.Array,
    vals: jax.Array,
    fields: Optional[jax.Array],
    weights: jax.Array,
    cfg: FmConfig,
    compute_dtype=jnp.float32,
):
    """Weighted training loss (+L2) and unregularized metrics.

    Padded examples carry weight 0 and drop out of both loss and metrics.
    Returns ``(loss, aux)`` for ``jax.value_and_grad(..., has_aux=True)``.
    """
    rows = params.table[ids]
    scores = scores_from_rows(
        params.w0, rows, vals, fields,
        factor_num=cfg.factor_num, field_num=cfg.field_num,
        compute_dtype=compute_dtype,
    )
    # scores are f32 regardless of compute_dtype (both score paths
    # accumulate and return f32), so loss/metrics math stays f32.
    per_ex = example_losses(scores, labels, cfg.loss_type)
    wsum = jnp.maximum(jnp.sum(weights), 1e-12)
    data_loss = jnp.sum(per_ex * weights) / wsum
    if cfg.factor_lambda or cfg.bias_lambda:
        if cfg.l2_mode == "full":
            reg = l2_penalty_full(params, cfg.factor_lambda, cfg.bias_lambda)
        else:
            reg = l2_penalty_batch(
                params, rows, vals, cfg.factor_lambda, cfg.bias_lambda
            )
    else:
        reg = jnp.zeros((), jnp.float32)
    loss = data_loss + reg
    aux = {
        "data_loss": data_loss,
        "reg": reg,
        "scores": scores,
        "weight_sum": jnp.sum(weights),
    }
    return loss, aux
