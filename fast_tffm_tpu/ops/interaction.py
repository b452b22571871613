"""FM interaction op with custom VJP — dispatches jnp oracle or Pallas.

``fm_interaction(rows, vals)`` computes per-example FM scores (without w0)
from gathered table rows, differentiable w.r.t. ``rows`` only (feature
values are data, not parameters).  The backward pass uses the closed-form
FmGrad (SURVEY.md §3.4) instead of autodiff through the sum-square trick —
one fused kernel instead of XLA's unfused chain, and the basis for the
sparse row-update training path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fast_tffm_tpu.ops import fm_pallas


from fast_tffm_tpu.platform import (
    ffm_compute_dtype,
    ffm_matmul_precision,
    use_interpret as _use_interpret,
)


def _scores_jnp(rows, vals):
    # Upcast once: in bf16-input mode only the STORED rows/vals are
    # rounded — accumulation and the returned scores/s1 stay f32, matching
    # the Pallas kernels' contract.  The same astype is the in-register
    # widening of a bf16-STORED serving table (ops.quant): the gather
    # reads compact rows, this cast fuses into it, and everything
    # downstream is f32 either way.
    rows = rows.astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    w = rows[..., 0]
    v = rows[..., 1:]
    xv = v * vals[..., None]
    s1 = jnp.sum(xv, axis=1)
    s2 = jnp.sum(xv * xv, axis=1)
    linear = jnp.sum(w * vals, axis=-1)
    return linear + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1), s1


def _grads_jnp(rows, vals, s1, g):
    in_dtype = rows.dtype
    rows = rows.astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    v = rows[..., 1:]
    gx = (g[:, None] * vals)[..., None]  # [B, F, 1]
    dv = gx * (s1[:, None, :] - v * vals[..., None])
    # Cotangent dtype must match the primal's (bf16 in bf16 mode).
    return jnp.concatenate([gx, dv], axis=-1).astype(in_dtype)


# Flat-layout pure-XLA variant: the Pallas kernels' [B, F*D] one-hot-
# matmul math, but left to XLA to fuse (no pallas_call).  The [B, F, D]
# elementwise layout above runs the VPU at D/128 lane utilization; here
# the hot elementwise chain is [B, F*D] (~91% at F=39, D=9) and the
# per-feature reductions ride the MXU.  Broadcasts that the kernel
# builds with R/Mt selection matmuls become repeat/tile (XLA fuses them
# for free); only the feature-sum keeps a one-hot matmul, because the
# reshape back to [B, F, D] it would otherwise need is a real relayout
# on TPU.
def _m_matrix(fd, d, dtype):
    """M[c, c % d] = 1: sums row slot j across features on the MXU."""
    cm = jax.lax.broadcasted_iota(jnp.int32, (fd, d), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (fd, d), 1)
    return (cm % d == j).astype(dtype)


_HI = jax.lax.Precision.HIGHEST  # keep ~f32 exactness on the MXU


def _scores_flat(rows, vals):
    b, f, d = rows.shape
    rows2 = rows.reshape(b, f * d).astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    xe = jnp.repeat(vals, d, axis=1)  # xe[b, f*d+j] = x_f
    y = rows2 * xe
    m = _m_matrix(f * d, d, jnp.float32)
    s = jax.lax.dot(y, m, precision=_HI,
                    preferred_element_type=jnp.float32)
    s2 = jax.lax.dot(y * y, m, precision=_HI,
                     preferred_element_type=jnp.float32)
    s1 = s[:, 1:]
    inter = 0.5 * jnp.sum(s1 * s1 - s2[:, 1:], axis=-1)
    return s[:, 0] + inter, s1


def _grads_flat(rows, vals, s1, g):
    in_dtype = rows.dtype
    b, f, d = rows.shape
    rows2 = rows.reshape(b, f * d).astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    xe = jnp.repeat(vals, d, axis=1)
    y = rows2 * xe
    ones = jnp.ones((b, 1), jnp.float32)
    # s1e[b, f*d+j] = (1 if j == 0 else s1[b, j-1]): tile, not a matmul.
    s1e = jnp.tile(jnp.concatenate([ones, s1], axis=1), (1, f))
    c = jax.lax.broadcasted_iota(jnp.int32, (1, f * d), 1)
    maskv = (c % d != 0).astype(jnp.float32)  # kill the w column in y
    drows2 = (g[:, None] * xe) * (s1e - y * maskv)
    return drows2.reshape(b, f, d).astype(in_dtype)


def _impl_name(impl) -> str:
    """Normalize the static dispatch arg: bools are the legacy surface."""
    if impl is True:
        return "pallas"
    if impl is False:
        return "jnp"
    if impl in ("pallas", "jnp", "flat"):
        return impl
    raise ValueError(f"unknown interaction impl {impl!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fm_interaction(rows, vals, use_pallas=True):
    scores, _ = _forward(rows, vals, use_pallas)
    return scores


def fm_interaction_sharded(rows, vals, use_pallas, mesh, data_axis: str):
    """Mesh-aware wrapper: Mosaic kernels cannot be auto-partitioned by
    GSPMD, so on a multi-device mesh the pallas path must run under
    shard_map with the batch dimension sharded on the data axis (rows/vals
    are replicated across the model axis — the gather already happened)."""
    impl = _impl_name(use_pallas)
    if impl != "pallas":  # jnp/flat are plain XLA: GSPMD partitions them
        return fm_interaction(rows, vals, impl)
    if mesh is None or mesh.size == 1:
        return fm_interaction(rows, vals, impl)
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    # check_vma=False: pallas_call out_shapes don't carry vma annotations.
    return shard_map(
        lambda r, v: fm_interaction(r, v, "pallas"),
        mesh=mesh,
        in_specs=(P(data_axis, None, None), P(data_axis, None)),
        out_specs=P(data_axis),
        check_vma=False,
    )(rows, vals)


def _forward(rows, vals, impl):
    impl = _impl_name(impl)
    if impl == "pallas":
        return fm_pallas.fm_scores_pallas(rows, vals,
                                          interpret=_use_interpret())
    if impl == "flat":
        return _scores_flat(rows, vals)
    return _scores_jnp(rows, vals)


@jax.named_scope("tffm.fm_interaction_fwd")
def _fwd(rows, vals, impl):
    scores, s1 = _forward(rows, vals, impl)
    return scores, (rows, vals, s1)


@jax.named_scope("tffm.fm_interaction_bwd")
def _bwd(impl, res, g):
    rows, vals, s1 = res
    impl = _impl_name(impl)
    if impl == "pallas":
        drows = fm_pallas.fm_grad_pallas(rows, vals, s1, g,
                                         interpret=_use_interpret())
    elif impl == "flat":
        drows = _grads_flat(rows, vals, s1, g)
    else:
        drows = _grads_jnp(rows, vals, s1, g)
    return drows, None  # no gradient w.r.t. vals


fm_interaction.defvjp(_fwd, _bwd)


# ---------------------------------------------------- field-aware FM (FFM)
#
# Closed-form forward + backward for the field-grouped FFM interaction —
# the single-device analogue of train.shardmap_step's inversion algebra
# (reference FmScorer/FmGrad roles for BASELINE config 5).  Autodiff
# through the einsum chain in models.fm.ffm_scores_from_rows re-derives
# cotangents for every intermediate (oh*vals, S, v_own, ...); the closed
# form reuses the saved field-grouped sums S and computes
#
#     dv_i^q = g x_i (S[q, f_i] - [q = f_i] v_i^{f_i} x_i),  dw_i = g x_i
#
# with one gather-by-field einsum.  Parity with the autodiff oracle is
# test-enforced (tests/test_ffm_op.py).


@jax.named_scope("tffm.ffm_interaction_fwd")
def _ffm_forward(rows, vals, fields, factor_num, field_num, compute_dtype):
    """The forward math: (scores without w0, S).

    Mirrors models.fm.ffm_scores_from_rows operand-for-operand —
    including which products see the bf16-ROUNDED operands — so the two
    forwards agree to accumulation order in every compute_dtype.
    """
    cd = ffm_compute_dtype(compute_dtype)  # f32 off-TPU: CPU can't bf16-dot
    prec = ffm_matmul_precision(cd)
    rows = rows.astype(cd)
    vals_c = vals.astype(cd)
    b, f = vals.shape
    w = rows[..., 0]
    v = rows[..., 1:].reshape(b, f, field_num, factor_num)
    linear = jnp.sum(w * vals_c, axis=-1, dtype=jnp.float32)
    oh = (
        fields[..., None] == jnp.arange(field_num, dtype=fields.dtype)
    ).astype(cd)  # [B, F, P]
    s = jnp.einsum(
        "bfp,bfqk->bpqk", oh * vals_c[..., None], v, precision=prec,
        preferred_element_type=jnp.float32,
    )  # [B, P, P, k] field-grouped sums, f32
    v_own = jnp.einsum(
        "bfq,bfqk->bfk", oh, v, precision=prec,
        preferred_element_type=jnp.float32,
    )  # v_i^{f_i}
    # The rounded vals square here must match the rounded diagonal of
    # `cross` or the i = j cancellation leaves a bf16-eps residual.
    self_term = jnp.sum(
        jnp.sum(v_own * v_own, axis=-1) * (vals_c * vals_c),
        axis=-1, dtype=jnp.float32,
    )
    cross = jnp.einsum("bpqk,bqpk->b", s, s, precision=prec)
    return linear + 0.5 * (cross - self_term), s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ffm_interaction(rows, vals, fields, factor_num, field_num,
                    compute_dtype=jnp.float32):
    """Per-example FFM interaction scores (without w0), differentiable
    w.r.t. ``rows`` only.  Same numeric contract as
    models.fm.ffm_scores_from_rows minus the w0 term: bf16 mode rounds
    the operands, accumulation and scores stay f32; float32 mode is
    float32 on the MXU too (platform.ffm_matmul_precision)."""
    return _ffm_forward(
        rows, vals, fields, factor_num, field_num, compute_dtype
    )[0]


def _ffm_fwd(rows, vals, fields, factor_num, field_num, compute_dtype):
    scores, s = _ffm_forward(
        rows, vals, fields, factor_num, field_num, compute_dtype
    )
    # Residuals: save only the inputs + S (what autodiff would keep
    # anyway); oh/v_own are cheap one-hot recomputes in the backward.
    return scores, (rows, vals, fields, s)


def _ffm_bwd(factor_num, field_num, compute_dtype, res, g):
    rows, vals, fields, s = res
    b, f = vals.shape
    # Same operand rounding as the forward/autodiff: products see the
    # cd-rounded rows/vals, accumulation stays f32.
    cd = ffm_compute_dtype(compute_dtype)
    prec = ffm_matmul_precision(cd)
    with jax.named_scope("tffm.ffm_interaction_bwd"):
        v = rows[..., 1:].astype(cd).reshape(b, f, field_num, factor_num)
        vals32 = vals.astype(cd).astype(jnp.float32)
        oh = (
            fields[..., None] == jnp.arange(field_num, dtype=fields.dtype)
        ).astype(cd)
        v_own = jnp.einsum(
            "bfq,bfqk->bfk", oh, v, precision=prec,
            preferred_element_type=jnp.float32,
        )
        oh32 = oh.astype(jnp.float32)
        gx = g[:, None] * vals32  # [B, F]
        # T[b,f,q,:] = S[b, q, f_i, :]: gather S's second field axis by
        # each occurrence's own field, as a one-hot matmul.
        t = jnp.einsum("bqpk,bfp->bfqk", s, oh32, precision=prec)
        dv = gx[..., None, None] * (
            t - oh32[..., None] * v_own[:, :, None, :]
            * vals32[..., None, None]
        )  # [B, F, P, k]
        drows = jnp.concatenate(
            [gx[..., None], dv.reshape(b, f, field_num * factor_num)],
            axis=-1,
        ).astype(rows.dtype)
    return drows, None, None  # no gradients w.r.t. vals/fields


ffm_interaction.defvjp(_ffm_fwd, _ffm_bwd)
