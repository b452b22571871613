"""Cross-platform TPU *lowering* tests for every Pallas kernel path.

Interpret-mode tests check kernel semantics but structurally cannot catch
Mosaic lowering errors — "Unimplemented primitive in Pallas TPU lowering"
aborted the round-3 hardware run (scatter-add at the old
sparse_apply K1 carry add) while every interpret test passed.  Mosaic's
jaxpr->MLIR pass runs at jax LOWERING time, so ``jax.export`` with
``platforms=['tpu']`` under ``platform.force_compiled()`` surfaces that
entire failure class on this CPU-only machine.

Every Pallas entry point must have a case here; a new kernel without one
is unprotected against exactly the bug class that lost that run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from fast_tffm_tpu import platform as pf
from fast_tffm_tpu.ops import fm_pallas, sparse_apply


V, D, N = 4096, 9, 2048
B, F, K = 1024, 39, 8


def lower_tpu(fn, *args):
    """Export ``fn`` for the tpu platform; raises on Mosaic lowering errors."""
    with pf.force_compiled():
        return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestSparseApplyLowering:
    def test_adagrad_apply(self):
        lower_tpu(
            functools.partial(sparse_apply.adagrad_apply, lr=0.1, eps=1e-7),
            _s((V, D)), _s((V, D)), _s((N,), jnp.int32), _s((N, D)),
        )

    def test_sgd_apply(self):
        lower_tpu(
            functools.partial(sparse_apply.sgd_apply, lr=0.1),
            _s((V, D)), _s((N,), jnp.int32), _s((N, D)),
        )

    def test_ftrl_apply(self):
        lower_tpu(
            functools.partial(
                sparse_apply.ftrl_apply, lr=0.1, l1=0.01, l2=0.01, beta=1.0
            ),
            _s((V, D)), _s((V, D)), _s((V, D)), _s((N,), jnp.int32),
            _s((N, D)),
        )

    def test_adagrad_apply_compact(self):
        """Compact K2 (scalar-prefetch-driven index maps, touched-group
        grid) lowers for TPU.  Shapes chosen so the compact branch
        actually engages (entries << table groups)."""
        v_big, n_small = 1 << 21, 512
        lower_tpu(
            functools.partial(
                sparse_apply.adagrad_apply, lr=0.1, eps=1e-7, compact=True
            ),
            _s((v_big, D)), _s((v_big, D)), _s((n_small,), jnp.int32),
            _s((n_small, D)),
        )

    def test_unique_entries_merge_apply(self):
        """The full entries-exchange chain (unique_entries ->
        merge_entries -> k2_apply) lowers for TPU."""
        cap = sparse_apply.entries_cap(N, V)

        def chain(table, acc, ids, g):
            rows, pay, _ = sparse_apply.unique_entries(
                ids, g, vocab=V, cap=cap
            )
            # Simulate a 2-shard gather: the merged stream length is
            # what matters for lowering.
            u, ts = sparse_apply.merge_entries(
                jnp.concatenate([rows, rows]),
                jnp.concatenate([pay, pay], axis=0), vocab=V,
            )
            upd = functools.partial(
                sparse_apply.adagrad_update, lr=0.1, eps=1e-7
            )
            return sparse_apply.k2_apply(upd, ts, u, (table, acc))

        lower_tpu(
            chain, _s((V, D)), _s((V, D)), _s((N,), jnp.int32), _s((N, D)),
        )

    def test_dense_delta(self):
        lower_tpu(
            functools.partial(
                sparse_apply.dense_delta, vocab=V, vocab_local=V, row_lo=0
            ),
            _s((N,), jnp.int32), _s((N, D)),
        )

    @pytest.mark.parametrize("chunk,tile", [(256, 512), (1024, 512),
                                            (2048, 256)])
    def test_adagrad_apply_alternate_blocks(self, chunk, tile):
        """The tunable CHUNK/TILE values the hardware sweep tries must
        all pass Mosaic lowering, or the sweep would crash the chip run."""
        orig = sparse_apply.CHUNK, sparse_apply.TILE
        sparse_apply.CHUNK, sparse_apply.TILE = chunk, tile
        try:
            lower_tpu(
                functools.partial(
                    sparse_apply.adagrad_apply, lr=0.1, eps=1e-7
                ),
                _s((V, D)), _s((V, D)), _s((N,), jnp.int32), _s((N, D)),
            )
        finally:
            sparse_apply.CHUNK, sparse_apply.TILE = orig

    @pytest.mark.parametrize(
        "chunk,tile,k1_group,group",
        [
            (512, 256, 1, 1),
            (512, 256, 4, 16),
            # Small blocks so the big groups actually materialize:
            # N/CHUNK = 16 chunks and V/TILE = 32 tiles — _group_for
            # would silently clamp them at the default block sizes and
            # lower the same kernel as the case above.
            (128, 128, 16, 32),
        ],
    )
    def test_adagrad_apply_alternate_groups(self, chunk, tile, k1_group,
                                            group):
        """Every K1_GROUP/GROUP value the hardware sweep tries must pass
        Mosaic lowering — the unrolled window loops and their semaphore
        protocols change shape with the group counts."""
        orig = (sparse_apply.CHUNK, sparse_apply.TILE,
                sparse_apply.K1_GROUP, sparse_apply.GROUP)
        sparse_apply.CHUNK = chunk
        sparse_apply.TILE = tile
        sparse_apply.K1_GROUP = k1_group
        sparse_apply.GROUP = group
        try:
            assert sparse_apply._group_for(N // chunk, k1_group) == k1_group
            assert sparse_apply._group_for(V // tile) == group
            lower_tpu(
                functools.partial(
                    sparse_apply.adagrad_apply, lr=0.1, eps=1e-7
                ),
                _s((V, D)), _s((V, D)), _s((N,), jnp.int32), _s((N, D)),
            )
        finally:
            (sparse_apply.CHUNK, sparse_apply.TILE,
             sparse_apply.K1_GROUP, sparse_apply.GROUP) = orig

    def test_adagrad_apply_with_host_meta(self):
        """The host-sort fast path reshapes the kernel inputs (prefetched
        metadata instead of in-graph sort); it must lower for TPU too."""
        n_pad = -(-N // sparse_apply.CHUNK) * sparse_apply.CHUNK
        n_chunks = n_pad // sparse_apply.CHUNK
        n_tiles = V // sparse_apply.TILE
        from fast_tffm_tpu.data.libsvm import SortMeta

        meta = SortMeta(
            perm=_s((n_pad,), jnp.int32),
            upos=_s((n_pad,), jnp.int32),
            lrow_last=_s((n_pad,), jnp.float32),
            starts=_s((n_chunks,), jnp.int32),
            firsts=_s((n_chunks + 1,), jnp.int32),
            ends=_s((n_chunks,), jnp.int32),
            tile_start=_s((n_tiles + 1,), jnp.int32),
        )
        lower_tpu(
            lambda t, a, i, g, m: sparse_apply.adagrad_apply(
                t, a, i, g, lr=0.1, eps=1e-7, meta=m
            ),
            _s((V, D)), _s((V, D)), _s((N,), jnp.int32), _s((N, D)), meta,
        )


class TestFmKernelLowering:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward(self, dtype):
        lower_tpu(
            functools.partial(fm_pallas.fm_scores_pallas, interpret=False),
            _s((B, F, 1 + K), dtype), _s((B, F), dtype),
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_backward(self, dtype):
        lower_tpu(
            functools.partial(fm_pallas.fm_grad_pallas, interpret=False),
            _s((B, F, 1 + K), dtype), _s((B, F), dtype), _s((B, K)),
            _s((B,)),
        )


class TestGraftEntryLowering:
    def test_entry_lowers_with_compiled_pallas(self):
        """The driver's single-chip compile gate runs entry() — which
        uses the Pallas forward — so entry must Mosaic-lower for TPU."""
        import __graft_entry__ as ge

        fn, args = ge.entry()
        lower_tpu(fn, *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args])


class TestFullStepLowering:
    """The exact step functions the trainer jits, lowered for TPU."""

    def test_single_device_tile_step_bf16(self):
        """The bf16-compute variant of the full tile step lowers too."""
        self.test_single_device_tile_step("adagrad", "bfloat16")

    @pytest.mark.parametrize("optimizer", ["adagrad", "ftrl", "sgd"])
    def test_single_device_tile_step(self, optimizer, compute_dtype="float32"):
        from fast_tffm_tpu.config import FmConfig
        from fast_tffm_tpu.data.libsvm import Batch
        from fast_tffm_tpu.models import fm
        from fast_tffm_tpu.train import sparse

        cfg = FmConfig(
            vocabulary_size=V, factor_num=K, max_features=F,
            batch_size=B, optimizer=optimizer, sparse_apply="tile",
            use_pallas=True, compute_dtype=compute_dtype,
        )
        params = fm.FmParams(w0=_s(()), table=_s((V, 1 + K)))
        opt = sparse.init_sparse_opt_state(
            cfg, fm.FmParams(w0=jnp.zeros(()), table=jnp.zeros((V, 1 + K)))
        )
        opt = jax.tree.map(lambda a: _s(a.shape, a.dtype), opt)
        batch = Batch(
            labels=_s((B,)), ids=_s((B, F), jnp.int32), vals=_s((B, F)),
            fields=_s((B, F), jnp.int32), weights=_s((B,)),
        )

        def step(params, opt, batch):
            p, o, scores = sparse.sparse_step(cfg, params, opt, batch)
            return p, o, scores

        lower_tpu(step, params, opt, batch)

    def test_shardmap_step_ffm(self):
        """FFM variant of the hand-sharded step lowers for TPU too."""
        self.test_shardmap_step("adagrad", field_num=4)

    def test_shardmap_step_entries_exchange(self):
        """The batch-proportional entries exchange (all-gather + merge +
        K2-from-stream) lowers for TPU."""
        self.test_shardmap_step("adagrad", sparse_exchange="entries")

    @pytest.mark.parametrize("optimizer", ["adagrad", "ftrl"])
    def test_shardmap_step(self, optimizer, field_num=0,
                           sparse_exchange="auto"):
        """The hand-sharded multi-device step over the virtual 8-dev mesh."""
        import numpy as np
        from jax.sharding import Mesh

        from fast_tffm_tpu.config import FmConfig
        from fast_tffm_tpu.data.libsvm import Batch
        from fast_tffm_tpu.models import fm
        from fast_tffm_tpu.parallel import mesh as mesh_lib
        from fast_tffm_tpu.train import shardmap_step, sparse

        mesh = Mesh(
            np.array(jax.devices()[:8]).reshape(4, 2),
            (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
        )
        cfg = FmConfig(
            vocabulary_size=V, factor_num=K, max_features=F,
            batch_size=B, optimizer=optimizer, sparse_apply="tile",
            use_pallas=True, field_num=field_num,
            sparse_exchange=sparse_exchange,
        )
        d = cfg.embedding_dim
        assert shardmap_step.supports_shardmap(cfg, mesh)
        params = fm.FmParams(w0=_s(()), table=_s((V, d)))
        opt = sparse.init_sparse_opt_state(
            cfg, fm.FmParams(w0=jnp.zeros(()), table=jnp.zeros((V, d)))
        )
        opt = jax.tree.map(lambda a: _s(a.shape, a.dtype), opt)
        batch = Batch(
            labels=_s((B,)), ids=_s((B, F), jnp.int32), vals=_s((B, F)),
            fields=_s((B, F), jnp.int32), weights=_s((B,)),
        )

        def step(params, opt, batch):
            return shardmap_step.sparse_step_shardmap(
                cfg, params, opt, batch, mesh
            )

        lower_tpu(step, params, opt, batch)


def test_transposed_stream_writer_lowers():
    """The transposed tile-stream writer of the one-device apply (grown
    from the micro_probe's transposed-K2 prototype, which it replaced)
    must pass Mosaic lowering: its column-block specs (9, block) differ
    structurally from the row-major K2's (block, 9)."""
    update = functools.partial(sparse_apply.adagrad_update, lr=0.05, eps=1e-7)
    lower_tpu(
        lambda i, g, *t: sparse_apply.scatter_apply_unique(
            update, t, i, g, additive=True, stream=True),
        _s((N,), jnp.int32), _s((N, D)), _s((V, D)), _s((V, D)),
    )


def test_packed_k2_probe_lowers():
    """The packed [V/8, 128] super-row K2 prototype must pass Mosaic
    lowering (its lane-spread one-hot matmuls and packed block specs
    are structurally new)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import micro_probe

    lower_tpu(
        functools.partial(micro_probe.k2p_apply, lr=0.05, eps=1e-7),
        _s((V // 8, 128)), _s((V // 8, 128)), _s((N,), jnp.int32),
        _s((N, D)),
    )
