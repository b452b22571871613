"""ops.interaction.ffm_interaction (closed-form VJP) vs the autodiff oracle.

The op's backward implements the shardmap inversion's closed form
``dv_i^q = g x_i (S[q, f_i] - [q = f_i] v_i^{f_i} x_i)``; it must match
jax.grad through models.fm.ffm_scores_from_rows to float tolerance, and
the forward must match exactly (same einsum sequence).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import interaction

B, F, P, K = 32, 8, 3, 4
D = 1 + P * K


def _data(seed):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.uniform(-0.5, 0.5, (B, F, D)), jnp.float32)
    vals = jnp.asarray(rng.uniform(0.1, 1.0, (B, F)), jnp.float32)
    vals = vals.at[:, -2:].set(0.0)  # padded feature slots
    fields = jnp.asarray(rng.integers(0, P, (B, F)), jnp.int32)
    g = jnp.asarray(rng.uniform(-1, 1, (B,)), jnp.float32)
    return rows, vals, fields, g


def test_ffm_forward_matches_oracle():
    rows, vals, fields, _ = _data(0)
    got = interaction.ffm_interaction(rows, vals, fields, K, P)
    want = fm.ffm_scores_from_rows(
        jnp.zeros(()), rows, vals, fields, K, P
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_ffm_closed_form_grad_matches_autodiff():
    rows, vals, fields, g = _data(1)

    def via_op(r):
        return jnp.sum(
            g * interaction.ffm_interaction(r, vals, fields, K, P)
        )

    def via_oracle(r):
        return jnp.sum(
            g * fm.ffm_scores_from_rows(jnp.zeros(()), r, vals, fields, K, P)
        )

    d_op = jax.grad(via_op)(rows)
    d_or = jax.grad(via_oracle)(rows)
    np.testing.assert_allclose(
        np.asarray(d_op), np.asarray(d_or), rtol=1e-5, atol=1e-6
    )


def test_ffm_grad_zero_on_padded_slots():
    """Padded features (val == 0) must receive zero row gradients."""
    rows, vals, fields, g = _data(2)
    d = jax.grad(
        lambda r: jnp.sum(
            g * interaction.ffm_interaction(r, vals, fields, K, P)
        )
    )(rows)
    np.testing.assert_array_equal(np.asarray(d[:, -2:, :]), 0.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ffm_op_bf16_mode_runs_and_tracks_f32(dtype):
    """bf16 compute rounds operands but accumulates f32; scores must stay
    within bf16 rounding of the f32 scores, and the cotangent dtype must
    match the primal's."""
    rows, vals, fields, g = _data(3)
    cd = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    rows_c = rows.astype(cd)
    got = interaction.ffm_interaction(rows_c, vals, fields, K, P, cd)
    assert got.dtype == jnp.float32
    ref = interaction.ffm_interaction(rows, vals, fields, K, P)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-2, atol=2e-2
    )
    d = jax.grad(
        lambda r: jnp.sum(
            g * interaction.ffm_interaction(r, vals, fields, K, P, cd)
        )
    )(rows_c)
    assert d.dtype == cd


def test_ffm_op_matches_oracle_same_compute_dtype():
    """At the SAME compute_dtype the op must track the oracle to
    accumulation order — including which products see the bf16-rounded
    operands (the self-term/cross diagonal cancellation is where an
    operand-rounding mismatch shows up).  Off-TPU both gates fall back
    to f32 via platform.ffm_compute_dtype, so this pins the shared
    operand plumbing; the bf16-vs-bf16 comparison has no chip run yet
    (no field-aware cell: PERF.md §7)."""
    rows, vals, fields, g = _data(4)
    cd = jnp.bfloat16
    rows_c = rows.astype(cd)
    got = interaction.ffm_interaction(rows_c, vals, fields, K, P, cd)
    want = fm.ffm_scores_from_rows(
        jnp.zeros(()), rows_c, vals, fields, K, P, cd
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    d_op = jax.grad(
        lambda r: jnp.sum(
            g * interaction.ffm_interaction(r, vals, fields, K, P, cd)
        )
    )(rows_c)
    d_or = jax.grad(
        lambda r: jnp.sum(g * fm.ffm_scores_from_rows(
            jnp.zeros(()), r, vals, fields, K, P, cd
        ))
    )(rows_c)
    assert d_op.dtype == d_or.dtype == cd
    np.testing.assert_allclose(
        np.asarray(d_op, dtype=np.float32), np.asarray(d_or, np.float32),
        rtol=1e-4, atol=1e-4,
    )
