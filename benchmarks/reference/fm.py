"""Plain reference of the factorization machine this repository trains
and serves (Rendle 2010; ``examples/criteo_1tb_dist.cfg``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no batching ladder, no scatter-add.  It imports nothing of
``fast_tffm_tpu`` and is handed nothing the program made.

    score_e = w0 + sum_j w[i_j] x_j
                 + 0.5 sum_c [(sum_j V[i_j,c] x_j)^2 - sum_j (V[i_j,c] x_j)^2]

One table ``[vocab, 1 + k]``: column 0 is the linear weight, the rest the
factor vector.  Training is logistic loss (mean over the batch's weights)
plus L2 on the rows the batch touched, per occurrence, over the batch
size; sparse Adagrad with per-occurrence accumulators
(``acc += sum_occ g^2``, ``w -= lr * sum_occ g / sqrt(acc_new + eps)``),
the semantics of TF's SparseApplyAdagrad that the published
configuration states.  Departures from the program, on purpose: the
reference sums a row's occurrences in a sorted segment sum and writes
each touched row once, where the program scatter-adds occurrence by
occurrence.

Hashing (``hash_feature_id = true``): MurmurHash64A (Appleby) of the
feature token's bytes, modulo the vocabulary.
"""

from __future__ import annotations

import numpy as np

ADAGRAD_EPS = 1e-7

_M = np.uint64(0xC6A4A7935BD1E995)
_R = np.uint64(47)


def _le64(buf: np.ndarray) -> np.ndarray:
    """Little-endian u64 of ``[n, 8]`` uint8."""
    return np.ascontiguousarray(buf).view("<u8").reshape(-1)


def murmur64a_decimal(x: np.ndarray) -> np.ndarray:
    """MurmurHash64A (seed 0) of the decimal spelling of each
    non-negative integer below 10**15 (one 8-byte block and a tail),
    vectorized."""
    x = np.asarray(x, np.int64).reshape(-1)
    if len(x) and (x.min() < 0 or x.max() >= 10**15):
        raise ValueError("ids must be in [0, 10**15)")
    s = x.astype("S16")
    length = np.char.str_len(s).astype(np.uint64)
    buf = np.zeros((len(x), 16), np.uint8)
    raw = np.frombuffer(s.tobytes(), np.uint8).reshape(len(x), -1)
    buf[:, :raw.shape[1]] = raw
    with np.errstate(over="ignore"):
        h = length * _M  # seed 0
        has_block = length >= np.uint64(8)
        k = _le64(buf[:, :8]) * _M
        k ^= k >> _R
        k *= _M
        h = np.where(has_block, (h ^ k) * _M, h)
        # bytes past the spelling are zero, so the padded word IS the tail
        tail = np.where(has_block, _le64(buf[:, 8:]), _le64(buf[:, :8]))
        has_tail = (length % np.uint64(8)) != 0
        h = np.where(has_tail, (h ^ tail) * _M, h)
        h ^= h >> _R
        h *= _M
        h ^= h >> _R
    return h


def hash_bucket_decimal(raw_ids: np.ndarray, vocab: int) -> np.ndarray:
    raw_ids = np.asarray(raw_ids)
    h = murmur64a_decimal(raw_ids) % np.uint64(vocab)
    return h.astype(np.int32).reshape(raw_ids.shape)


# ----------------------------------------------------------------- params


def uniform_table(seed: int, vocab: int, dim: int, scale: float):
    """``[vocab, dim]`` float32, uniform in +-scale, made on the device in
    one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda key: jax.random.uniform(
            key, (vocab, dim), jnp.float32, -scale, scale),
    )(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------- forward


def _terms(rows, vals, compute_dtype):
    import jax.numpy as jnp

    rows = rows.astype(compute_dtype)
    vals = vals.astype(compute_dtype)
    w, v = rows[..., 0], rows[..., 1:]
    linear = jnp.sum(w * vals, axis=-1, dtype=jnp.float32)
    xv = v * vals[..., None]
    s1 = jnp.sum(xv, axis=1, dtype=jnp.float32)
    s2 = jnp.sum(xv * xv, axis=1, dtype=jnp.float32)
    return linear, s1, s2


def scores_from_rows(w0, rows, vals, compute_dtype=None):
    import jax.numpy as jnp

    linear, s1, s2 = _terms(rows, vals, compute_dtype or jnp.float32)
    return (w0.astype(jnp.float32) + linear
            + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1))


def probabilities(w0, table, ids, vals, compute_dtype=None):
    """Served answer per example: sigmoid(score).  ``ids``/``vals`` are
    ``[n, F]``; rows with ``vals == 0`` are inert padding."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(
            scores_from_rows(w0, table[ids], vals, compute_dtype))


# --------------------------------------------------------------- training


def _loss(w0, rows, vals, labels, weights, factor_lambda, bias_lambda,
          compute_dtype):
    import jax
    import jax.numpy as jnp

    scores = scores_from_rows(w0, rows, vals, compute_dtype)
    per_ex = jax.nn.softplus(scores) - labels * scores
    data = jnp.sum(per_ex * weights) / jnp.maximum(jnp.sum(weights), 1e-12)
    mask = (vals != 0).astype(rows.dtype)[..., None]
    reg = (factor_lambda * jnp.sum((rows[..., 1:] * mask) ** 2)
           + bias_lambda * (jnp.sum((rows[..., :1] * mask) ** 2)
                            + w0 ** 2)) / vals.shape[0]
    return data + reg, (data, scores)


def make_adagrad_step(*, vocab: int, lr: float, factor_lambda: float,
                      bias_lambda: float, compute_dtype=None):
    """``step(state, batch) -> (state, aux)``; state is ``(w0, table,
    acc_w0, acc_table)``, batch a dict of ``ids[B,F]``, ``vals[B,F]``,
    ``labels[B]``, ``weights[B]``; aux carries the step's data loss, its
    scores and, under ``grad``, the summed gradient the optimizer got
    per parameter leaf (the table's: one row per unique id)."""
    import jax
    import jax.numpy as jnp

    cd = compute_dtype or jnp.float32

    def step(state, batch):
        w0, table, acc_w0, acc_table = state
        ids, vals = batch["ids"], batch["vals"]
        b, f = ids.shape
        with jax.default_matmul_precision("highest"):
            rows = table[ids]
            grad_fn = jax.grad(_loss, argnums=(0, 1), has_aux=True)
            (dw0, drows), (data, scores) = grad_fn(
                w0, rows, vals, batch["labels"], batch["weights"],
                factor_lambda, bias_lambda, cd)
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
# The driver of the job kind ``train`` knows no optimizer and no leaf by
# name: it asks the module that the configuration's ``reference`` key
# names.  ``keys`` are the configuration's cfg keys as run.  Leaves go by
# their path in the program's ``(params, opt_state)``, which is all this
# module knows of the program.  A configuration with another optimizer or
# other tables brings a reference module of its own with these five
# functions.


def _adagrad(keys: dict) -> None:
    if keys.get("optimizer") != "adagrad" or keys.get("loss_type") != "logistic":
        raise NotImplementedError(
            "reference/fm.py follows optimizer=adagrad with logistic loss; "
            f"the configuration states {keys.get('optimizer')!r} / "
            f"{keys.get('loss_type')!r}")


def program_leaves(keys: dict) -> dict:
    """Paths of the program's leaves the check reads: ``params`` are
    compared, ``optimizer`` is what ``first_gradient`` needs besides."""
    _adagrad(keys)
    return {"params": ["params.w0", "params.table"],
            "optimizer": ["opt_state.acc.w0", "opt_state.acc.table"]}


def init_state(keys: dict):
    """The state a job starts from under the cfg's ``seed``."""
    import jax.numpy as jnp

    _adagrad(keys)
    v, d = keys["vocabulary_size"], 1 + keys["factor_num"]
    acc0 = keys["adagrad.initial_accumulator"]
    return (jnp.zeros((), jnp.float32),
            uniform_table(keys["seed"], v, d, keys["init_value_range"]),
            jnp.full((), acc0, jnp.float32),
            jnp.full((v, d), acc0, jnp.float32))


def param_leaves(state) -> dict:
    return {"params.w0": state[0], "params.table": state[1]}


def make_step(keys: dict, compute_dtype=None):
    _adagrad(keys)
    return make_adagrad_step(
        vocab=keys["vocabulary_size"], lr=keys["learning_rate"],
        factor_lambda=keys["factor_lambda"],
        bias_lambda=keys["bias_lambda"], compute_dtype=compute_dtype)


def first_gradient(keys: dict, pre: dict, post: dict) -> dict:
    """Norm, per parameter leaf, of the first gradient as the optimizer
    got it, worked out from the program's state before and after one
    step (float64 arrays by leaf path; a table's rows once per unique
    id): Adagrad moved ``w`` by ``-lr * g / sqrt(acc_new + eps)``."""
    _adagrad(keys)
    out = {}
    for leaf in ("w0", "table"):
        moved = post["params." + leaf] - pre["params." + leaf]
        g = -moved * np.sqrt(post["opt_state.acc." + leaf]
                             + ADAGRAD_EPS) / keys["learning_rate"]
        out["params." + leaf] = float(np.sqrt(np.sum(np.square(g))))
    return out
