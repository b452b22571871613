"""Compile the main path's kernels for a DESCRIBED v5e, at real widths.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  That
surfaces what interpret-mode tests and ``jax.export`` lowering tests
(tests/test_tpu_lowering.py stops at jaxpr->MLIR) structurally cannot:
Mosaic tiling/alignment refusals, VMEM budgets, and a program that does
not fit the chip's HBM — at Criteo-Kaggle width (F=39, D=1+8), the
shapes ``chip_smoke.py`` runs on the chip.  A compile that passes is
not a chip run; nothing here measures anything.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), and every compile happens in the
test's own process.  Keep all such tests in THIS file.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from fast_tffm_tpu import platform as pf
from fast_tffm_tpu.ops import fm_pallas, interaction, sparse_apply

F, K = 39, 8
D = 1 + K
# Sparse-apply cases: the real occurrence count of one criteo_kaggle.cfg
# batch (B=4096), at a vocabulary that keeps each compile near 2 s.
N_OCC = 4096 * F
V_APPLY = 1 << 18


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out of the way."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def compile_for(sharding, fn, *args, **jit_kw):
    """Compile ``fn`` for the described chip; returns the executable.
    Raises whatever the chip's compiler would raise."""
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args,
    )
    with pf.force_compiled():
        compiled = jax.jit(fn, **jit_kw).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel inside"
    return compiled


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("batch", [4096, 16384])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
class TestInteractionKernels:
    def test_forward(self, one_chip, no_persistent_cache, batch, dtype):
        compile_for(
            one_chip,
            functools.partial(fm_pallas.fm_scores_pallas, interpret=False),
            _s((batch, F, D), dtype), _s((batch, F), dtype),
        )

    def test_backward(self, one_chip, no_persistent_cache, batch, dtype):
        """jax.grad through the dispatch the trainer uses
        (``fm_interaction(..., "pallas")`` and its custom VJP)."""
        def loss(rows, vals):
            return jnp.sum(
                interaction.fm_interaction(rows, vals, "pallas")
            )

        compile_for(
            one_chip, jax.grad(loss),
            _s((batch, F, D), dtype), _s((batch, F), dtype),
        )


def _host_meta(vocab, lead=()):
    """Shapes of the host sort metadata the pipeline ships with every
    batch (the trainer's default: the device sort leaves the step)."""
    from fast_tffm_tpu.data import native

    meta = native.sort_meta(
        np.zeros((N_OCC,), np.int32), vocab,
        sparse_apply.CHUNK, sparse_apply.TILE,
    )
    return type(meta)(*(_s(lead + x.shape, x.dtype) for x in meta))


@pytest.mark.parametrize("case", ["adagrad", "ftrl", "sgd",
                                  "adagrad_compact"])
def test_sparse_apply_kernels(one_chip, no_persistent_cache, case):
    """K1 dedup + K2 apply through the kernels' own entry points, with
    the real N = B*F entry stream of one Criteo-Kaggle batch."""
    tab, ids, g = _s((V_APPLY, D)), _s((N_OCC,), jnp.int32), _s((N_OCC, D))
    meta = _host_meta(V_APPLY)
    if case == "ftrl":
        fn = functools.partial(
            sparse_apply.ftrl_apply, lr=0.1, l1=0.01, l2=0.01, beta=1.0
        )
        args = (tab, tab, tab, ids, g)
    elif case == "sgd":
        fn = functools.partial(sparse_apply.sgd_apply, lr=0.1)
        args = (tab, ids, g)
    else:
        fn = functools.partial(
            sparse_apply.adagrad_apply, lr=0.1, eps=1e-7,
            compact=True if case == "adagrad_compact" else None,
        )
        args = (tab, tab, ids, g)
    compiled = compile_for(
        one_chip, lambda *a: fn(*a[:-1], meta=a[-1]), *args, meta
    )
    # K1 and K2 are separate Mosaic calls.
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl"])
def test_unique_scatter_apply(one_chip, no_persistent_cache, optimizer):
    """The single-device scatter apply through its scatter writer:
    sort, K1 with the three passes it asks for, and the counted loop of
    unique-row gathers and scatters (additive under Adagrad,
    gather-update-set under FTRL)."""
    tab, ids, g = _s((V_APPLY, D)), _s((N_OCC,), jnp.int32), _s((N_OCC, D))
    if optimizer == "ftrl":
        update = functools.partial(
            sparse_apply.ftrl_update, lr=0.1, l1=0.01, l2=0.01, beta=1.0)
        tables = (tab, tab, tab)
    else:
        update = functools.partial(
            sparse_apply.adagrad_update, lr=0.1, eps=1e-7)
        tables = (tab, tab)
    compiled = compile_for(
        one_chip,
        lambda i, gr, *t: sparse_apply.scatter_apply_unique(
            update, t, i, gr, additive=optimizer == "adagrad",
            stream=False),
        ids, g, *tables,
    )
    assert "while" in compiled.as_text()


D_FFM = 1 + 39 * 4  # LIBFFM's Criteo row: 39 fields, k = 4


def test_unique_scatter_apply_at_three_payload_tiles(
        one_chip, no_persistent_cache):
    """The same apply at field-aware FM's row: [g | g^2 | lrow | tidx]
    is 2 * 157 + 2 = 316 floats, three 128-lane tiles.  K1's output
    windows land at dynamic row offsets, which Mosaic takes only in a
    128-lane array ("Failed to prove that a tile index in dimension 0
    is divisible by the tiling (8)" at 384 lanes): one call a tile."""
    tab = _s((V_APPLY, D_FFM))
    n = 16 * sparse_apply.CHUNK  # the sort's compile grows with n
    compiled = compile_for(
        one_chip,
        lambda i, gr, *t: sparse_apply.scatter_apply_unique(
            functools.partial(sparse_apply.adagrad_update, lr=0.2, eps=1e-7),
            t, i, gr, additive=True, stream=False),
        _s((n,), jnp.int32), _s((n, D_FFM)), tab, tab,
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 and "while" in text


@pytest.mark.parametrize("d,vocab", [(D, 1 << 25), (D_FFM, 1 << 22)])
@pytest.mark.parametrize("optimizer", ["adagrad", "ftrl"])
def test_stream_apply_holds_no_table_copy_and_no_loop(
        one_chip, no_persistent_cache, optimizer, d, vocab):
    """The same apply through the stream writer, at the two train
    cells' rows and vocabularies (the tables are shapes: nothing is
    allocated).  [V, D] rests {0,1:T(8,128)}, which is [D, V] in
    Mosaic's default layout: ``table.T`` must reach the kernel as a
    bitcast — no copy of a whole table in either shape — the tables
    aliased from the step's arguments through the kernel to its
    results, and no loop anywhere (the scatter writer's ``while``; the
    tile-start search is unrolled)."""
    import re

    n = 16 * sparse_apply.CHUNK  # the sort's compile grows with n
    if optimizer == "ftrl":
        update = functools.partial(
            sparse_apply.ftrl_update, lr=0.1, l1=0.01, l2=0.01, beta=1.0)
    else:
        update = functools.partial(
            sparse_apply.adagrad_update, lr=0.1, eps=1e-7)
    n_tables = 3 if optimizer == "ftrl" else 2
    compiled = compile_for(
        one_chip,
        lambda i, gr, *t: sparse_apply.scatter_apply_unique(
            update, t, i, gr, additive=optimizer == "adagrad", stream=True),
        _s((n,), jnp.int32), _s((n, d)), *[_s((vocab, d))] * n_tables,
        donate_argnums=tuple(range(2, 2 + n_tables)),
    )
    text = compiled.as_text()
    table = rf"f32\[({vocab},{d}|{d},{vocab})\]"
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(rf"= {table}\S* (copy|copy-start|transpose)\(", ln)]
    assert not moved, moved
    assert not re.search(r"\bwhile\(", text)
    # K1 a payload lane tile, and the writer
    assert text.count("tpu_custom_call") == -(-(2 * d + 2) // 128) + 1
    header = text.splitlines()[0]
    for k in range(n_tables):  # argument 2 + k is result k, in place
        assert f"{{{k}}}: ({2 + k}, {{}}, may-alias)" in header, header
    writer = next(ln for ln in text.splitlines()
                  if "custom-call(" in ln and "tffm.apply_write" in ln)
    assert writer.count(f"{d},{vocab}") >= 2 * n_tables, writer
    for k in range(n_tables):
        assert f"{{{k}}}: ({1 + k}, {{}})" in writer, writer
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem  # a table is >= 2 GiB


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_field_aware_interaction_at_libffm_criteo_shape(
        one_chip, no_persistent_cache, dtype):
    """Forward and closed-form backward of the field-aware interaction
    at 39 fields, k = 4, B = 4,096: the [B, 39, 39, 4] field-grouped
    sums must not be laid out with k = 4 padded to 128 lanes (10 GB at
    the cell's B = 16,384)."""
    b = 4096

    def loss(rows, vals, fields):
        return jnp.sum(interaction.ffm_interaction(
            rows, vals, fields, 4, 39, dtype))

    structs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
               for s in (_s((b, F, D_FFM)), _s((b, F)),
                         _s((b, F), jnp.int32))]
    with pf.force_compiled():
        compiled = jax.jit(jax.grad(loss)).lower(*structs).compile()
    mem = compiled.memory_analysis()
    # rows in, gradient out: 0.1 GB each; everything between under 1.5 GB
    assert mem.temp_size_in_bytes < 1.5 * 2**30, mem


def test_whole_tile_step_at_criteo_kaggle_shape(topo, no_persistent_cache):
    """The program the trainer really dispatches for
    examples/criteo_kaggle.cfg on one chip: the scan-fused tile step
    with the health carry, the scores output and the pipeline's host
    sort metadata, V=2^22, B=4096 — and it must fit the chip's 16 GB
    of HBM."""
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.data.libsvm import Batch
    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.parallel import mesh as mesh_lib
    from fast_tffm_tpu.train import loop, sparse as sparse_lib

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = dataclasses.replace(
        load_config(os.path.join(repo, "examples", "criteo_kaggle.cfg")),
        sparse_apply="tile",  # what "auto" resolves to on a TPU backend
    )
    assert (cfg.vocabulary_size, cfg.batch_size, cfg.max_features,
            cfg.factor_num) == (1 << 22, 4096, F, K)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1),
        (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
    )
    rep = NamedSharding(mesh, P())
    table_sh = mesh_lib.param_sharding(mesh).table

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=table_sh if x.ndim == 2 else rep,
            ),
            tree,
        )

    params = jax.eval_shape(
        lambda: fm.init_params(jax.random.PRNGKey(0), cfg)
    )
    state = placed(loop.TrainState(
        params=params,
        opt_state=jax.eval_shape(
            lambda p: sparse_lib.init_sparse_opt_state(cfg, p), params
        ),
        metrics=jax.eval_shape(loop.MetricState.zeros),
        step=_s((), jnp.int32),
    ))
    health = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(
            lambda: loop.HealthState.zeros(cfg.vocabulary_size)
        ),
    )
    b, f = cfg.batch_size, cfg.max_features
    super_sh = Batch(**mesh_lib.super_batch_sharding(mesh))
    assert b * f == N_OCC
    meta = _host_meta(cfg.vocabulary_size, lead=(1,))
    batches = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        Batch(labels=_s((1, b)), ids=_s((1, b, f), jnp.int32),
              vals=_s((1, b, f)), fields=_s((1, b, f), jnp.int32),
              weights=_s((1, b)), sort_meta=meta),
        super_sh._replace(sort_meta=type(meta)(*(rep for _ in meta))),
    )
    step = loop.make_scan_train_step(
        loop.make_sparse_train_step(cfg, mesh, with_health=True),
        loop.make_health_update(cfg), with_scores=True,
    )
    with pf.force_compiled():
        compiled = jax.jit(step, donate_argnums=0).lower(
            state, health, batches
        ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 15.75 * 2**30, f"{used / 2**30:.2f} GiB of 15.75"
