"""Plain reference of the field-aware factorization machine this
repository trains (Juan, Zhuang, Chin, Lin 2016, "Field-aware
Factorization Machines for CTR Prediction"; LIBFFM's Criteo run: 39
fields, k = 4).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no one-hot matmul, no field-grouped sums, no scatter-add.  It
imports nothing of ``fast_tffm_tpu`` and is handed nothing the program
made.  The hash, the seeded table and Adagrad's epsilon are those of the
plain-FM reference beside this file (``fm.py``, loaded by path): one
copy of each.

    score_e = w0 + sum_i w[i] x_i
                 + sum_{i<j} <V[i, f_j], V[j, f_i]> x_i x_j

written as it stands: for every example the double sum over the pairs of
its features, feature i's factor vector FOR FEATURE j's FIELD against
feature j's FOR FEATURE i's.  (The program computes the same number from
field-grouped sums S[p, q] = sum_{i: f_i = p} V[i, q] x_i.)  One table
``[vocab, 1 + fields * k]``: column 0 is the linear weight, columns
``1 + q * k .. 1 + (q + 1) * k`` the factor vector for field ``q``.
Pairs are computed in blocks of ``BLOCK`` examples so that a batch of
16,384 fits beside the tables.

Training is the logistic loss (mean over the batch's weights) plus L2 on
the rows the batch touched, per occurrence, over the batch size;
per-coordinate Adagrad with per-occurrence accumulators
(``acc += sum_occ g^2``, ``w -= lr * sum_occ g / sqrt(acc_new + eps)``),
a row's occurrences summed in a sorted segment sum and each touched row
written once.

Departures from LIBFFM, all the program's and followed here:

* a global bias ``w0`` and a linear term ``w[i] x_i`` (LIBFFM has
  neither): a row is 1 + 39 * 4 = 157 floats, not 156;
* mini-batches with the mean loss and one update a batch, not one
  Hogwild update an instance; the L2 term is per occurrence over the
  batch size (LIBFFM adds ``lambda * w`` to every instance's gradient);
* no instance-wise normalisation of the feature values;
* weights start uniform in +-``init_value_range`` (LIBFFM: uniform in
  [0, 1/sqrt(k))), the accumulator at ``adagrad.initial_accumulator``
  (LIBFFM: 1, as the configuration states);
* ids are MurmurHash64A of the token modulo the vocabulary, one id space
  for all fields (LIBFFM's Criteo script hashes to 10^6 bins).
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_fm",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fm.py"))
_fm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fm)

ADAGRAD_EPS = _fm.ADAGRAD_EPS
hash_bucket_decimal = _fm.hash_bucket_decimal
murmur64a_decimal = _fm.murmur64a_decimal
uniform_table = _fm.uniform_table

# Examples a block: the [BLOCK, k, F, F] pair tensor is 25 MB of numbers
# at F = 39, k = 4 (84 MB as the chip lays it out).
BLOCK = 1024

# ---------------------------------------------------------------- forward


def pair_scores(w0, rows, vals, fields, *, field_num: int, factor_num: int,
                compute_dtype=None):
    """``[n]`` scores from gathered rows ``[n, F, 1 + field_num *
    factor_num]``, values ``[n, F]`` (0 = padding) and fields ``[n, F]``.
    ``compute_dtype`` rounds rows and values first (the lower-precision
    reading of PERF.md section 2); sums stay float32."""
    import jax.numpy as jnp

    cd = compute_dtype or jnp.float32
    rows = rows.astype(cd).astype(jnp.float32)
    vals = vals.astype(cd).astype(jnp.float32)
    n, f = vals.shape
    w = rows[..., 0]
    v = rows[..., 1:].reshape(n, f, field_num, factor_num)
    linear = jnp.sum(w * vals, axis=-1)
    # held[e, c, i, j] = V[i, f_j][c]: what feature i holds for the field
    # of feature j (the factor index c leads so that F x F is minor)
    held = jnp.take_along_axis(
        jnp.moveaxis(v, 3, 1), fields[:, None, None, :], axis=3)
    dots = jnp.sum(held * jnp.swapaxes(held, 2, 3), axis=1)  # [n, F, F]
    i_lt_j = jnp.triu(jnp.ones((f, f), jnp.float32), k=1)
    pairs = jnp.sum(dots * (vals[:, :, None] * vals[:, None, :]) * i_lt_j,
                    axis=(1, 2))
    return w0.astype(jnp.float32) + linear + pairs


# --------------------------------------------------------------- training


def _block_loss(w0, rows, vals, fields, labels, weights, *, wsum, batch,
                factor_lambda, bias_lambda, model):
    """A block's share of the step's loss: its examples' weighted
    logloss over the batch's weight sum, plus its occurrences' L2 over
    the batch size (``w0``'s own L2 is added once, by the caller)."""
    import jax
    import jax.numpy as jnp

    scores = pair_scores(w0, rows, vals, fields, **model)
    per_ex = jax.nn.softplus(scores) - labels * scores
    data = jnp.sum(per_ex * weights) / wsum
    mask = (vals != 0).astype(rows.dtype)[..., None]
    reg = (factor_lambda * jnp.sum((rows[..., 1:] * mask) ** 2)
           + bias_lambda * jnp.sum((rows[..., :1] * mask) ** 2)) / batch
    return data + reg, (data, scores)


def make_adagrad_step(*, vocab: int, lr: float, factor_lambda: float,
                      bias_lambda: float, field_num: int, factor_num: int,
                      compute_dtype=None):
    """``step(state, batch) -> (state, aux)``; state is ``(w0, table,
    acc_w0, acc_table)``, batch a dict of ``ids[B,F]``, ``vals[B,F]``,
    ``fields[B,F]``, ``labels[B]``, ``weights[B]``; aux carries the
    step's data loss, its scores and, under ``grad``, the summed gradient
    the optimizer got per parameter leaf (the table's: one row per unique
    id)."""
    import jax
    import jax.numpy as jnp

    model = dict(field_num=field_num, factor_num=factor_num,
                 compute_dtype=compute_dtype)

    def step(state, batch):
        w0, table, acc_w0, acc_table = state
        ids, vals = batch["ids"], batch["vals"]
        b, f = ids.shape
        blk = min(b, BLOCK)
        if b % blk:
            raise ValueError(f"batch {b} is not a multiple of {blk}")
        wsum = jnp.maximum(jnp.sum(batch["weights"]), 1e-12)
        grad_fn = jax.grad(_block_loss, argnums=(0, 1), has_aux=True)

        def one_block(args):
            rows_b, vals_b, fields_b, labels_b, weights_b = args
            (dw0_b, drows_b), (data_b, scores_b) = grad_fn(
                w0, rows_b, vals_b, fields_b, labels_b, weights_b,
                wsum=wsum, batch=b, factor_lambda=factor_lambda,
                bias_lambda=bias_lambda, model=model)
            return dw0_b, drows_b, data_b, scores_b

        def blocks(x):
            return x.reshape((b // blk, blk) + x.shape[1:])

        with jax.default_matmul_precision("highest"):
            rows = table[ids]
            dw0_b, drows, data_b, scores = jax.lax.map(one_block, tuple(
                blocks(x) for x in (rows, vals, batch["fields"],
                                    batch["labels"], batch["weights"])))
        dw0 = jnp.sum(dw0_b) + 2.0 * bias_lambda * w0 / b
        data, scores = jnp.sum(data_b), scores.reshape(b)
        n = b * f
        flat = ids.reshape(n)
        g = drows.reshape(n, -1)
        uniq, inv = jnp.unique(flat, return_inverse=True, size=n,
                               fill_value=vocab)
        inv = inv.reshape(n)
        g_sum = jax.ops.segment_sum(g, inv, num_segments=n)
        g2_sum = jax.ops.segment_sum(g * g, inv, num_segments=n)
        acc_u = acc_table[uniq] + g2_sum
        w_u = table[uniq] - lr * g_sum * jax.lax.rsqrt(acc_u + ADAGRAD_EPS)
        table = table.at[uniq].set(w_u, mode="drop")
        acc_table = acc_table.at[uniq].set(acc_u, mode="drop")
        acc_w0 = acc_w0 + dw0 * dw0
        w0 = w0 - lr * dw0 * jax.lax.rsqrt(acc_w0 + ADAGRAD_EPS)
        aux = {"loss": data, "scores": scores,
               "grad": {"params.w0": dw0, "params.table": g_sum}}
        return (w0, table, acc_w0, acc_table), aux

    return jax.jit(step, donate_argnums=0)


# ------------------------------------------- what the train driver asks
#
# The five functions ``reference/fm.py`` ends on, for this model: ``keys``
# are the configuration's cfg keys as run, leaves go by their path in the
# program's ``(params, opt_state)``.


def _adagrad_ffm(keys: dict) -> None:
    if (keys.get("optimizer") != "adagrad"
            or keys.get("loss_type") != "logistic"
            or not keys.get("field_num")):
        raise NotImplementedError(
            "reference/ffm.py follows a field-aware FM (field_num > 0) "
            "under optimizer=adagrad with logistic loss; the configuration "
            f"states field_num={keys.get('field_num')!r}, "
            f"{keys.get('optimizer')!r} / {keys.get('loss_type')!r}")


def program_leaves(keys: dict) -> dict:
    """Paths of the program's leaves the check reads: ``params`` are
    compared, ``optimizer`` is what ``first_gradient`` needs besides."""
    _adagrad_ffm(keys)
    return {"params": ["params.w0", "params.table"],
            "optimizer": ["opt_state.acc.w0", "opt_state.acc.table"]}


def init_state(keys: dict):
    """The state a job starts from under the cfg's ``seed``."""
    import jax.numpy as jnp

    _adagrad_ffm(keys)
    v = keys["vocabulary_size"]
    d = 1 + keys["field_num"] * keys["factor_num"]
    acc0 = keys["adagrad.initial_accumulator"]
    return (jnp.zeros((), jnp.float32),
            uniform_table(keys["seed"], v, d, keys["init_value_range"]),
            jnp.full((), acc0, jnp.float32),
            jnp.full((v, d), acc0, jnp.float32))


def param_leaves(state) -> dict:
    return {"params.w0": state[0], "params.table": state[1]}


def make_step(keys: dict, compute_dtype=None):
    _adagrad_ffm(keys)
    return make_adagrad_step(
        vocab=keys["vocabulary_size"], lr=keys["learning_rate"],
        factor_lambda=keys["factor_lambda"],
        bias_lambda=keys["bias_lambda"], field_num=keys["field_num"],
        factor_num=keys["factor_num"], compute_dtype=compute_dtype)


def first_gradient(keys: dict, pre: dict, post: dict) -> dict:
    """Norm, per parameter leaf, of the first gradient as the optimizer
    got it, from the program's state before and after one step: Adagrad
    moved ``w`` by ``-lr * g / sqrt(acc_new + eps)`` (``fm.py``'s, which
    knows no model)."""
    _adagrad_ffm(keys)
    return _fm.first_gradient(keys, pre, post)
