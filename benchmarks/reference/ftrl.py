"""Plain reference of the factorization machine this repository trains
(Rendle 2010; ``examples/criteo_1tb_dist.cfg``) under FTRL-Proximal
(McMahan et al. 2013, "Ad Click Prediction: a View from the Trenches",
Algorithm 1).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no one-hot matmul, no stream writer, no scatter-add.  It
imports nothing of ``fast_tffm_tpu`` and is handed nothing the program
made.  The hash, the seeded table and the loss (logistic, mean over the
batch's weights, plus the batch L2) are those of the plain-FM reference
beside this file (``fm.py``, loaded by path): one copy of each.

Per coordinate ``i``, with gradient ``g_i`` (Algorithm 1):

    n_i'    = n_i + g_i^2
    sigma_i = (sqrt(n_i') - sqrt(n_i)) / alpha
    z_i'    = z_i + g_i - sigma_i * w_i
    w_i'    = 0                                        if |z_i'| <= lambda1
            = -(z_i' - sgn(z_i') lambda1)
              / ((beta + sqrt(n_i')) / alpha + lambda2)  otherwise

``alpha`` is the configuration's ``learning_rate``, ``beta``,
``lambda1``, ``lambda2`` its ``ftrl.beta`` / ``ftrl.l1`` / ``ftrl.l2``.

Departures from the paper, all the program's and followed here:

* the model is the FM, not logistic regression: FTRL runs per
  coordinate on ``w0``, on every row's linear weight and on its ``k``
  factors, with the FM's gradients;
* mini-batches, one update a batch: a row's ``g`` is the sum over its
  occurrences in the batch (a sorted segment sum) of the mean loss's
  gradient, ``n`` takes the sum of the occurrences' squares, and
  ``sigma`` uses the row's ``n`` before and after the whole batch; each
  touched row is written once and every other row keeps its bits;
* ``n`` starts at ``n0`` = the configuration's
  ``adagrad.initial_accumulator`` (the paper: 0), and ``z`` from the
  seeded weights so that the closed form gives them back,
  ``z = -w (beta + sqrt(n0)) / alpha - lambda2 w - sgn(w) lambda1``
  (the paper starts from ``w = z = 0``);
* the loss carries the batch L2 of ``factor_lambda`` / ``bias_lambda``
  on the rows the batch touched, beside FTRL's own ``lambda2``.
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

hash_bucket_decimal = _fm.hash_bucket_decimal
murmur64a_decimal = _fm.murmur64a_decimal
uniform_table = _fm.uniform_table
scores_from_rows = _fm.scores_from_rows

# The program's leaves by their path: the weights and both of FTRL's
# per-coordinate accumulators, each a scalar for w0 and a table.
LEAVES = ("params.w0", "params.table", "opt_state.z.w0",
          "opt_state.z.table", "opt_state.n.w0", "opt_state.n.table")


def ftrl_solve(z, n, *, lr, l1, l2, beta):
    """The closed form of Algorithm 1 for ``w`` from ``z`` and ``n``."""
    import jax.numpy as jnp

    denom = (beta + jnp.sqrt(n)) / lr + l2
    return jnp.where(jnp.abs(z) <= l1, 0.0, -(z - jnp.sign(z) * l1) / denom)


def make_ftrl_step(*, vocab: int, lr: float, l1: float, l2: float,
                   beta: float, factor_lambda: float, bias_lambda: float,
                   compute_dtype=None):
    """``step(state, batch) -> (state, aux)``; state is ``(w0, table,
    z_w0, z_table, n_w0, n_table)``, batch a dict of ``ids[B,F]``,
    ``vals[B,F]``, ``labels[B]``, ``weights[B]``; aux carries the step's
    data loss, its scores and, under ``grad``, the summed gradient FTRL
    got per parameter leaf (the table's: one row per unique id)."""
    import jax
    import jax.numpy as jnp

    cd = compute_dtype or jnp.float32
    hyper = dict(lr=lr, l1=l1, l2=l2, beta=beta)

    def coordinate(w, z, n, g, g2):
        n_new = n + g2
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / lr
        z_new = z + g - sigma * w
        return ftrl_solve(z_new, n_new, **hyper), z_new, n_new

    def step(state, batch):
        w0, table, z_w0, z_table, n_w0, n_table = state
        ids, vals = batch["ids"], batch["vals"]
        b, f = ids.shape
        with jax.default_matmul_precision("highest"):
            rows = table[ids]
            grad_fn = jax.grad(_fm._loss, argnums=(0, 1), has_aux=True)
            (dw0, drows), (data, scores) = grad_fn(
                w0, rows, vals, batch["labels"], batch["weights"],
                factor_lambda, bias_lambda, cd)
        n = b * f
        flat = ids.reshape(n)
        g = drows.reshape(n, -1)
        uniq, inv = jnp.unique(flat, return_inverse=True, size=n,
                               fill_value=vocab)
        inv = inv.reshape(n)
        # The sums are made whole before they meet the state: without the
        # barrier XLA rewrites ``n[uniq] + segment_sum(g^2)`` into a
        # scatter-add into ``n`` itself, one occurrence at a time, and at
        # B = 65,536 every g^2 (~1e-11) is under half an ulp of n = 0.1:
        # n never moved (on the CPU and on the chip alike).
        g_sum, g2_sum = jax.lax.optimization_barrier((
            jax.ops.segment_sum(g, inv, num_segments=n),
            jax.ops.segment_sum(g * g, inv, num_segments=n)))
        w_u, z_u, n_u = coordinate(table[uniq], z_table[uniq],
                                   n_table[uniq], g_sum, g2_sum)
        table = table.at[uniq].set(w_u, mode="drop")
        z_table = z_table.at[uniq].set(z_u, mode="drop")
        n_table = n_table.at[uniq].set(n_u, mode="drop")
        w0, z_w0, n_w0 = coordinate(w0, z_w0, n_w0, dw0, dw0 * dw0)
        aux = {"loss": data, "scores": scores,
               "grad": {"params.w0": dw0, "params.table": g_sum}}
        return (w0, table, z_w0, z_table, n_w0, n_table), aux

    return jax.jit(step, donate_argnums=0)


# ------------------------------------------- what the train driver asks
#
# The five functions ``fm.py`` ends on, for optimizer = ftrl.  Every
# leaf of FTRL's state is compared: ``params`` names the weights AND
# both accumulators (their change over the three steps is checked as
# the weights' is), so the state before the first step -- what
# ``first_gradient`` needs besides the state after it -- is read of all
# six.


def _ftrl(keys: dict) -> None:
    if keys.get("optimizer") != "ftrl" or keys.get("loss_type") != "logistic":
        raise NotImplementedError(
            "reference/ftrl.py follows optimizer=ftrl with logistic loss; "
            f"the configuration states {keys.get('optimizer')!r} / "
            f"{keys.get('loss_type')!r}")


def _hyper(keys: dict) -> dict:
    return dict(lr=keys["learning_rate"], l1=keys["ftrl.l1"],
                l2=keys["ftrl.l2"], beta=keys["ftrl.beta"])


def program_leaves(keys: dict) -> dict:
    """Paths of the program's leaves the check reads: all of them are
    compared, so ``optimizer`` names nothing further."""
    _ftrl(keys)
    return {"params": list(LEAVES), "optimizer": []}


def init_state(keys: dict):
    """The state a job starts from under the cfg's ``seed``: the seeded
    weights, ``n = n0`` and ``z`` such that the closed form gives the
    weights back."""
    import jax
    import jax.numpy as jnp

    _ftrl(keys)
    h = _hyper(keys)
    v, d = keys["vocabulary_size"], 1 + keys["factor_num"]
    n0 = keys["adagrad.initial_accumulator"]
    denom0 = (h["beta"] + jnp.sqrt(jnp.float32(n0))) / h["lr"] + h["l2"]
    z_of = jax.jit(lambda w: -w * denom0 - jnp.sign(w) * h["l1"])
    w0 = jnp.zeros((), jnp.float32)
    table = uniform_table(keys["seed"], v, d, keys["init_value_range"])
    z_table = z_of(table)
    return (w0, table, z_of(w0), z_table, jnp.full((), n0, jnp.float32),
            jnp.full((v, d), n0, jnp.float32))


def param_leaves(state) -> dict:
    return dict(zip(LEAVES, state))


def make_step(keys: dict, compute_dtype=None):
    _ftrl(keys)
    return make_ftrl_step(
        vocab=keys["vocabulary_size"], factor_lambda=keys["factor_lambda"],
        bias_lambda=keys["bias_lambda"], compute_dtype=compute_dtype,
        **_hyper(keys))


def first_gradient(keys: dict, pre: dict, post: dict) -> dict:
    """Norm, per weight leaf, of the first gradient as FTRL got it,
    worked out from the program's state before and after one step
    (float64 arrays by leaf path; a table's rows once per unique id):
    ``z`` moved by ``g - sigma * w`` with ``sigma`` from ``n`` before
    and after, so ``g = (z' - z) + sigma * w``."""
    _ftrl(keys)
    lr = keys["learning_rate"]
    out = {}
    for leaf in ("w0", "table"):
        z, n = ("opt_state.%s." % s + leaf for s in "zn")
        sigma = (np.sqrt(post[n]) - np.sqrt(pre[n])) / lr
        g = post[z] - pre[z] + sigma * pre["params." + leaf]
        out["params." + leaf] = float(np.sqrt(np.sum(np.square(g))))
    return out
