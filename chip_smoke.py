#!/usr/bin/env python
"""chip_smoke.py — does the system still start, and compute right, on the chip?

One process, one chip, the entry points a user calls, at the full width of
``examples/criteo_kaggle.cfg`` (V=4,194,304 hashed ids, k=8, F=39, B=4096,
adagrad, L2 on).  Inputs are made from ``--seed``; weights are the cfg's own
seeded init.  Phases (each prints one JSON line; any failure raises and the
script exits nonzero — nothing is caught and carried past):

1. device   what jax reports, before anything is allocated
2. train    ``cli.main(["train", cfg])`` — asserts from the run's own
            ``metrics_file`` records and the trainer's own gates that the
            compiled Pallas/tile path, the native parser and the fused
            stack+H2D ship did the work
3. kernels  the trainer's step with the default config vs the XLA oracle
            (``interaction=jnp``, ``sparse_apply=scatter``) on the same
            batches, ``compute_dtype=bfloat16`` vs f32, and one apply of
            the scatter path (sort, K1 sums, unique-row scatter; Adagrad
            and FTRL) and of the tile kernels vs the per-occurrence sums
            in float64 on the host
4. ffm      the width the field-aware cell runs (39 fields, k=4: 157
            floats a row, a payload of three 128-lane tiles), at a small
            V: the interaction's forward and closed-form backward at
            float32 vs the double sum over pairs in float64 on the host,
            and one scatter-path apply (Adagrad and FTRL) vs the host's
            per-occurrence sums
5. predict  ``cli.main(["predict", cfg])``
6. serve    ``serve(cfg, port=0)``; text and binary requests over the socket
            must match the predict scores; zero steady-state compiles

``--chips 4`` runs ONLY the 2 data x 2 model row-sharded step
(``lookup=shardmap``, both sparse exchanges) against the one-device step on
the same batches, and checks that placement is real; then the entries
step once more at a shard of 2^24 rows, where the row-major K2 does not
compile and the merged stream has to go through the stream writer.

Without a TPU the script fails: it never continues on the CPU.
``--rehearse`` is the sandbox rehearsal — the same phases at a toy size on
the CPU backend (Pallas in interpret mode), which finds wrong paths and
control flow at no chip time; its last line says ``"rehearsal": true`` and
names the cpu platform, so it cannot be mistaken for a chip result.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import configparser
import dataclasses
import gc
import itertools
import json
import os
import shutil
import sys
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
BASE_CFG = os.path.join(REPO, "examples", "criteo_kaggle.cfg")
# The only FmConfig fields the smoke's cfg may differ in from BASE_CFG.
PATH_FIELDS = frozenset({
    "train_files", "validation_files", "predict_files", "model_file",
    "score_path", "metrics_file",
})

# Kernel-vs-oracle contract.  Bitwise is the wrong contract across
# kernels: the Mosaic kernels run their one-hot matmuls as two-pass bf16
# hi/lo splits (~2^-16 relative) and sum duplicates in another order.
F32_TOL = {"rtol": 1e-4, "atol": 1e-5}
# Optimizer accumulators get their own relative tolerance.  At Zipf skew
# the hottest id has ~15k occurrences in one batch, each adding g^2 ~ 1e-8
# to an accumulator that starts at 0.1 (one f32 ulp there is 7.5e-9).  A
# per-occurrence scatter-add (the XLA oracle until PR 27) loses low bits
# every time (numpy: -6e-6 per step for 15k such adds); both paths now
# pre-sum the duplicates (K1) and add once, the oracle through
# scatter_apply_unique.  First chip run, against the per-occurrence
# oracle: max abs 3.2e-5 on values <= 0.15 after 4 steps, with scores and
# tables inside F32_TOL at a quarter of it.
ACC_TOL = {"rtol": 5e-4, "atol": 1e-5}
# One apply against the per-occurrence formula in float64 on the host,
# absolute, on weights <= 0.1, FTRL z <= 1 and accumulators <= ~2.  The
# single-device scatter apply (sort, three-pass K1 sums, unique-row
# scatter; Adagrad and FTRL) keeps float32: first chip readings 2e-7
# (accumulators), 5e-8 (weights).  The tile kernels' one-hot matmuls
# keep 16 bits of a value: 2^-17 of an accumulator of 2 is 1.5e-5, and
# the hottest row (15k occurrences) read 7.5e-6, the weights 1.1e-6.
SCATTER_ATOL = 3e-6
TILE_ATOL = 2e-5
# what _applies_vs_host may report (a case is left out where the
# vocabulary or the row does not allow it)
APPLY_CASES = ("unique_adagrad", "unique_ftrl", "stream_adagrad",
               "stream_ftrl", "tile_adagrad")
# bf16 compute rounds the interaction operands to 8 mantissa bits;
# the repo's own bf16 tests (tests/test_bf16.py) hold this tolerance.
BF16_TOL = {"rtol": 0.05, "atol": 0.02}
# The field-aware phase: LIBFFM's Criteo shape at a vocabulary that keeps
# three float64 tables on the host small.
FFM_FIELDS, FFM_K, FFM_VOCAB, FFM_EXAMPLES = 39, 4, 1 << 16, 512
# Float32-true interaction against float64: scores of O(1) and gradients
# of O(0.1) summed from 741 pairs; one bf16 pass on the MXU (what float32
# operands get without a stated precision) reads ~1e-3 on both.
FFM_ATOL = 2e-5
# --chips 4: rows a model shard of the big case holds -- the first size
# at which the compiler refuses the row-major K2's whole-shard copies.
BIG_SHARD_ROWS = 1 << 24
# Served vs predicted probabilities: both print %.6f, and a request is
# padded to another ladder rung than predict's batch — same math per row.
SERVE_ATOL = 2e-6


@dataclasses.dataclass(frozen=True)
class Size:
    """How much the smoke runs.  ``overrides`` are cfg keys beyond the
    paths — empty at full size, where the cfg IS criteo_kaggle.cfg."""

    overrides: dict
    train_batches: int  # = dispatches (steps_per_dispatch defaults to 1)
    valid_batches: int
    predict_batches: int
    kernel_steps: int


FULL = Size({}, train_batches=16, valid_batches=2, predict_batches=2,
            kernel_steps=4)
# Rehearsal: every width but V and B kept (F=39, k=8); sparse_apply=tile
# forces the kernels' (interpreted) path the chip takes by default.
TOY = Size(
    {"vocabulary_size": "8192", "batch_size": "128", "thread_num": "2",
     "queue_size": "4", "shuffle_buffer": "512", "sparse_apply": "tile",
     "serve_batch_sizes": "8,32"},
    train_batches=8, valid_batches=1, predict_batches=1, kernel_steps=2,
)


def require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class Out:
    """Phase lines: stdout, and a copy under chiprun_out/ for the tool."""

    def __init__(self, path: str | None):
        self._f = None
        if path:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._f = open(path, "w")

    def emit(self, obj: dict) -> None:
        line = json.dumps(obj)
        print(line, flush=True)
        if self._f is not None:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()


def _mem(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def _read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------------------ inputs


def make_inputs(work: str, size: Size, seed: int, extra: dict | None = None):
    """Generate the libsvm files and write the cfg.  Returns
    (cfg_path, cfg).  At full size asserts that the cfg differs from
    examples/criteo_kaggle.cfg in paths only."""
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.data import synth

    base = configparser.ConfigParser()
    require(base.read(BASE_CFG), f"missing {BASE_CFG}")
    for key, val in {**size.overrides, **(extra or {})}.items():
        base["Tpu"][key] = val
    probe = os.path.join(work, "probe.cfg")
    with open(probe, "w") as f:
        base.write(f)
    shape = load_config(probe)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    files = {}
    for name, n in (("train", size.train_batches),
                    ("valid", size.valid_batches),
                    ("predict", size.predict_batches)):
        files[name] = synth.gen_libsvm_files(
            work, rng, 1, n * shape.batch_size, shape.max_features,
            shape.vocabulary_size, prefix=name, planted=True,
        )[0]
    gen_s = time.perf_counter() - t0
    base["General"]["model_file"] = os.path.join(work, "model")
    base["Train"]["train_files"] = files["train"]
    base["Train"]["validation_files"] = files["valid"]
    base["Train"]["metrics_file"] = os.path.join(work, "metrics.jsonl")
    base["Predict"]["predict_files"] = files["predict"]
    base["Predict"]["score_path"] = os.path.join(work, "scores.txt")
    cfg_path = os.path.join(work, "smoke.cfg")
    with open(cfg_path, "w") as f:
        base.write(f)
    cfg = load_config(cfg_path)
    if not size.overrides and not extra:
        ref = dataclasses.asdict(load_config(BASE_CFG))
        got = dataclasses.asdict(cfg)
        diff = {k for k in ref if ref[k] != got[k]}
        require(diff <= PATH_FIELDS,
                f"smoke cfg differs from criteo_kaggle.cfg in {diff}")
    return cfg_path, cfg, round(gen_s, 2)


# ------------------------------------------------------------------ phases


def phase_device(out: Out, rehearse: bool, chips: int):
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if not rehearse and plat != "tpu":
        # Nothing on stdout: a run with no accelerator prints no result.
        print(f"chip_smoke: jax found platform {plat!r}, not a TPU; "
              "refusing to run on it (use --rehearse for the toy-size "
              "CPU rehearsal)", file=sys.stderr)
        raise SystemExit(2)
    if not rehearse:
        require(len(devs) == chips,
                f"--chips {chips} but jax reports {len(devs)} device(s)")
    require(len(devs) >= chips, f"need {chips} devices, have {len(devs)}")
    device = {"platform": plat, "kind": devs[0].device_kind,
              "count": len(devs) if not rehearse else chips}
    out.emit({"phase": "device", "jax": jax.__version__, **device,
              "memory": _mem(devs[0])})
    return device


def phase_train(out: Out, cfg_path: str, cfg, size: Size, rehearse: bool):
    import jax

    from fast_tffm_tpu import cli, platform
    from fast_tffm_tpu.data import native
    from fast_tffm_tpu.parallel import mesh as mesh_lib
    from fast_tffm_tpu.train import checkpoint, sparse as sparse_lib

    cache0 = platform.compile_cache_stats()
    t0 = time.perf_counter()
    require(cli.main(["train", cfg_path]) == 0, "train returned nonzero")
    wall = time.perf_counter() - t0
    gc.collect()  # the Trainer's device buffers go with it
    cache1 = platform.compile_cache_stats()
    recs = _read_jsonl(cfg.metrics_file)
    header = [r for r in recs if r["record"] == "run_header"][-1]
    final = [r for r in recs if r["record"] == "final"][-1]
    counters = final["stages"]["counters"]
    res = final["resource"]
    mesh = mesh_lib.make_mesh(cfg, devices=jax.devices()[:1])
    logloss = float(final["quality"]["logloss"])
    manifest = json.load(
        open(os.path.join(cfg.model_file, "serve_manifest.json"))
    )
    facts = {
        "steps": int(final["step"]),
        "dispatches": int(counters["prefetch.super_batches"]),
        "train_logloss": logloss,
        "backend": header["backend"],
        "mesh": header["mesh"],
        "kernel_impl": header["kernel_impl"],
        "apply_mode": sparse_lib.apply_mode(cfg, mesh),
        "interpret": platform.use_interpret(),
        "fast_ingest": header["fast_ingest"],
        "native_parser_loaded": native._lib is not None,
        "fused_h2d_enabled": mesh_lib.fused_h2d_enabled(mesh),
        "fused_ships": int(counters["prefetch.fused_ships"]),
        "recompiles_unexpected": int(res["recompiles_unexpected"]),
        "checkpoint_step": int(manifest["step"]),
    }
    out.emit({
        "phase": "train", **facts,
        "vocabulary_size": cfg.vocabulary_size,
        "factor_num": cfg.factor_num, "max_features": cfg.max_features,
        "batch_size": cfg.batch_size, "optimizer": cfg.optimizer,
        "wall_s": round(wall, 2),
        "train_compile_s": res["compile_s"],
        "train_compiles": res["compiles"],
        "compile_cache": {
            "dir": cache1["dir"],
            "hits": cache1["hits"] - cache0["hits"],
            "misses": cache1["misses"] - cache0["misses"],
        },
        "table_logical_bytes": 4 * cfg.vocabulary_size * cfg.embedding_dim,
        "device_bytes_est": res.get("device_bytes_est"),
        "memory": _mem(jax.devices()[0]),
    })
    want = {
        "steps": size.train_batches,
        "backend": "cpu" if rehearse else "tpu",
        "mesh": {"data": 1, "model": 1},
        "kernel_impl": "pallas",
        "apply_mode": "tile",
        "interpret": rehearse,
        "fast_ingest": True,
        "native_parser_loaded": True,
        "fused_h2d_enabled": True,
        "recompiles_unexpected": 0,
        "checkpoint_step": size.train_batches,
    }
    bad = {k: (facts[k], v) for k, v in want.items() if facts[k] != v}
    require(not bad, f"train facts (got, want): {bad}")
    require(facts["dispatches"] >= 8, f"{facts['dispatches']} dispatches")
    require(facts["fused_ships"] == facts["dispatches"],
            "not every dispatch took the fused stack+H2D ship")
    require(np.isfinite(logloss) and logloss < 0.693,
            f"train logloss {logloss} not below ln 2")
    require(checkpoint.exists(cfg.model_file), "no checkpoint written")


def _k_steps(cfg, files, k: int, mesh=None):
    """Run the TRAINER's step (its jitted scan dispatch, its own input
    shipping) for ``k`` batches of ``files`` from a fresh seeded init.
    Returns host copies: per-step scores, params, optimizer state — and
    the live trainer's placement facts."""
    import jax

    from fast_tffm_tpu.data.pipeline import BatchPipeline, stack_batches
    from fast_tffm_tpu.train.loop import Trainer

    shutil.rmtree(cfg.model_file, ignore_errors=True)  # no warm start
    t = Trainer(cfg, mesh=mesh)
    pipe = BatchPipeline(
        files, cfg, epochs=1, shuffle=False, ordered=True,
        sort_meta_spec=t._sort_meta_spec(),
    )
    batches = list(itertools.islice(iter(pipe), k))
    require(len(batches) == k, f"only {len(batches)} batches in {files}")
    t0 = time.perf_counter()
    scores, placement = [], None
    for b in batches:
        sb = t._put_super(stack_batches([b]))
        if placement is None:
            placement = _placement(t, sb)
        t.state = t._scan_train_step(t.state, sb)
        scores.append(np.asarray(t._last_scores)[0])
    host = jax.tree.map(np.asarray, (t.state.params, t.state.opt_state))
    wall = time.perf_counter() - t0
    compile_s = t._sentinel.compile_s
    del t, sb
    gc.collect()
    return {"scores": np.stack(scores), "params": host[0], "opt": host[1],
            "placement": placement, "wall_s": round(wall, 2),
            "compile_s": round(compile_s, 2)}


def _placement(trainer, super_batch) -> dict:
    """Where the table / optimizer leaves and a batch really live."""
    import jax

    v = trainer.cfg.vocabulary_size
    leaves = [trainer.state.params.table] + [
        x for x in jax.tree.leaves(trainer.state.opt_state)
        if x.ndim == 2 and x.shape[0] == v
    ]
    return {
        "table_leaves": len(leaves),
        "shard_rows": sorted({
            int(s.data.shape[0]) for x in leaves
            for s in x.addressable_shards
        }),
        "shard_devices": sorted({
            len({s.device.id for s in x.addressable_shards})
            for x in leaves
        }),
        "batch_shard_rows": sorted({
            int(s.data.shape[1])
            for s in super_batch.ids.addressable_shards
        }),
    }


def _max_err(a, b, rtol: float, atol: float) -> dict:
    """Largest |a-b| over all leaves (and a-b there, signed), and the
    worst ratio to the allowed ``atol + rtol*|b|`` (<= 1 passes)."""
    import jax

    worst_abs, signed, worst_ratio = 0.0, 0.0, 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        require(np.all(np.isfinite(x)), "non-finite values")
        d = x - y
        i = np.unravel_index(np.argmax(np.abs(d)), d.shape) if d.ndim else ()
        if abs(float(d[i])) > worst_abs:
            worst_abs, signed = abs(float(d[i])), float(d[i])
        worst_ratio = max(
            worst_ratio, float((np.abs(d) / (atol + rtol * np.abs(y))).max())
        )
    return {"max_abs": worst_abs, "signed_at_max": signed,
            "worst_over_allowed": worst_ratio}


def _compare(got: dict, ref: dict, tol: dict, opt_tol: dict) -> dict:
    return {name: _max_err(got[name], ref[name], **t)
            for name, t in (("scores", tol), ("params", tol),
                            ("opt", opt_tol))}


def _passed(cmp: dict) -> bool:
    return all(v["worst_over_allowed"] <= 1.0 for v in cmp.values())


def _applies_vs_host(cfg, seed: int) -> dict:
    """One optimizer apply at the cfg's own V, B x F and row (Zipf ids, so
    the hottest row has thousands of occurrences) against every
    occurrence added singly in float64 ON THE HOST — the one side of
    this phase that shares no sort, payload or K1 with the program:

    * ``unique_adagrad`` / ``unique_ftrl``: the single-device scatter
      apply (``scatter_apply_unique``) through its scatter writer, its
      additive and its gather-update-set form;
    * ``stream_adagrad`` / ``stream_ftrl``: the same apply through its
      transposed tile-stream writer (a vocabulary of whole subtiles);
    * ``tile_adagrad``: the tile kernels (K1 + K2), whose step-level
      oracle (``sparse_apply=scatter``) runs the same prep since PR 27;
      left out at a row they do not hold (their payload ``[g | g^2 |
      lrow]`` is one 128-lane tile: 63 floats a row at most).
    """
    from functools import partial

    import jax

    from fast_tffm_tpu.data import synth
    from fast_tffm_tpu.ops import sparse_apply
    from fast_tffm_tpu.train import sparse as sparse_lib

    v, d = cfg.vocabulary_size, cfg.embedding_dim
    n = cfg.batch_size * cfg.max_features
    lr, eps = cfg.learning_rate, sparse_lib.ADAGRAD_EPS
    l1, l2, beta = cfg.ftrl_l1, cfg.ftrl_l2, cfg.ftrl_beta
    rng = np.random.default_rng(seed)
    ids = synth.zipf_ids(rng, (n,), v).astype(np.int32)
    g = (rng.normal(size=(n, d)) * 1e-2).astype(np.float32)
    table = rng.uniform(-0.1, 0.1, size=(v, d)).astype(np.float32)
    acc = np.full((v, d), cfg.adagrad_initial_accumulator, np.float32)
    z = rng.uniform(-1.0, 1.0, size=(v, d)).astype(np.float32)
    uniq, inv = np.unique(ids, return_inverse=True)
    g1 = np.zeros((len(uniq), d))
    g2 = np.zeros((len(uniq), d))
    g64 = g.astype(np.float64)
    np.add.at(g1, inv, g64)
    np.add.at(g2, inv, g64 * g64)
    rest = np.ones(v, bool)
    rest[uniq] = False

    acc_ref = acc[uniq] + g2
    adagrad_ref = [table[uniq] - lr * g1 / np.sqrt(acc_ref + eps), acc_ref]
    z_ref = z[uniq] + g1 - (
        np.sqrt(acc_ref) - np.sqrt(acc[uniq])) / lr * table[uniq]
    ftrl_ref = [
        np.where(np.abs(z_ref) <= l1, 0.0,
                 -(z_ref - np.sign(z_ref) * l1)
                 / ((beta + np.sqrt(acc_ref)) / lr + l2)),
        z_ref, acc_ref,
    ]

    def unique(update, additive, stream=False):
        def apply(i, gr, *tabs):
            return sparse_apply.scatter_apply_unique(
                update, tabs, i, gr, additive=additive, stream=stream)
        return apply

    def tile(i, gr, t, a):
        return sparse_apply.adagrad_apply(t, a, i, gr, lr=lr, eps=eps), None

    adagrad = partial(sparse_apply.adagrad_update, lr=lr, eps=eps)
    ftrl = partial(sparse_apply.ftrl_update, lr=lr, l1=l1, l2=l2, beta=beta)
    cases = {
        "unique_adagrad": (unique(adagrad, True), [table, acc], adagrad_ref),
        "unique_ftrl": (unique(ftrl, False), [table, z, acc], ftrl_ref),
    }
    if v % sparse_apply.TILE == 0:
        cases["stream_adagrad"] = (
            unique(adagrad, True, True), [table, acc], adagrad_ref)
        cases["stream_ftrl"] = (
            unique(ftrl, False, True), [table, z, acc], ftrl_ref)
    if 2 * d + 1 <= 128 and sparse_apply.supports_tile(v, "adagrad"):
        cases["tile_adagrad"] = (tile, [table, acc], adagrad_ref)
    out = {
        "occurrences": n, "unique_rows": int(len(uniq)),
        "hottest_row_occurrences": int(np.bincount(inv).max()),
    }
    for name, (apply, before, want) in cases.items():
        after, count = jax.jit(apply)(ids, g, *before)
        after = [np.asarray(x) for x in after]
        out[name] = {
            "atol": TILE_ATOL if name == "tile_adagrad" else SCATTER_ATOL,
            "rows_written": None if count is None else int(count),
            "max_abs": [float(np.abs(x[uniq] - w).max())
                        for x, w in zip(after, want)],
            "untouched_rows_changed": int(sum(
                np.any(x[rest] != b[rest], axis=1).sum()
                for x, b in zip(after, before))),
        }
    return out


def phase_kernels(out: Out, cfg, size: Size, work: str, seed: int):
    import jax

    from fast_tffm_tpu import platform

    base = dataclasses.replace(
        cfg, metrics_file="", validation_files=(),
        model_file=os.path.join(work, "kernels_model"),
    )
    k = size.kernel_steps
    cache0 = platform.compile_cache_stats()
    default = _k_steps(base, cfg.train_files, k)
    cache1 = platform.compile_cache_stats()
    oracle = _k_steps(
        dataclasses.replace(base, interaction="jnp",
                            sparse_apply="scatter"),
        cfg.train_files, k,
    )
    bf16 = _k_steps(
        dataclasses.replace(base, compute_dtype="bfloat16"),
        cfg.train_files, k,
    )
    f32 = _compare(default, oracle, F32_TOL, ACC_TOL)
    b16 = _compare(bf16, default, BF16_TOL, BF16_TOL)
    applies = _applies_vs_host(base, seed)
    acc_max = max(float(x.max()) for x in jax.tree.leaves(default["opt"]))
    out.emit({
        "phase": "kernels", "steps": k,
        "f32_tol": F32_TOL, "acc_tol": ACC_TOL, "default_vs_oracle": f32,
        "bf16_tol": BF16_TOL, "bf16_vs_f32": b16,
        "applies_vs_host": applies,
        "compile_s": {"default": default["compile_s"],
                      "oracle": oracle["compile_s"],
                      "bf16": bf16["compile_s"]},
        # Persistent-cache events while the default variant was built
        # and run (its inputs are shipped per leaf here, not fused, so
        # its step program is not byte-identical to the train phase's).
        "default_compile_cache": {
            "hits": cache1["hits"] - cache0["hits"],
            "misses": cache1["misses"] - cache0["misses"],
        },
        "accumulator_max": acc_max,
        "memory": _mem(jax.devices()[0]),
    })
    require(_passed(f32), f"kernels differ from the XLA oracle: {f32}")
    require(_passed(b16), f"bf16 compute differs from f32: {b16}")
    for name in APPLY_CASES:
        got = applies.get(name)  # no tile case at a vocabulary it refuses
        require(
            got is None or (
                got["rows_written"] in (None, applies["unique_rows"])
                and got["untouched_rows_changed"] == 0
                and max(got["max_abs"]) <= got["atol"]),
            f"the {name} apply differs from the host's per-occurrence "
            f"sums: {got} of {applies}",
        )
    # A comparison of two untrained tables would pass vacuously.
    require(acc_max > cfg.adagrad_initial_accumulator,
            "the optimizer state did not move over the steps")


def _ffm_vs_host(cfg, seed: int) -> dict:
    """The field-aware interaction (closed-form op, float32) on the
    device against the double sum over pairs in float64 on the host:
    scores and the gradient w.r.t. the gathered rows.  Slots draw their
    field at random, so fields repeat and are absent in an example."""
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.ops import interaction

    p, k, f, b = cfg.field_num, cfg.factor_num, cfg.max_features, FFM_EXAMPLES
    rng = np.random.default_rng(seed + 1)
    rows = rng.uniform(-0.3, 0.3, size=(b, f, 1 + p * k)).astype(np.float32)
    vals = rng.uniform(0.1, 1.0, size=(b, f)).astype(np.float32)
    fields = rng.integers(0, p, size=(b, f)).astype(np.int32)
    co = rng.normal(size=(b,)).astype(np.float32)  # the scores' cotangent

    def loss(r):
        s = interaction.ffm_interaction(
            r, jnp.asarray(vals), jnp.asarray(fields), k, p, jnp.float32)
        return jnp.sum(s * co), s

    grad, scores = jax.jit(jax.grad(loss, has_aux=True))(jnp.asarray(rows))
    r64, x = rows.astype(np.float64), vals.astype(np.float64)
    v = r64[..., 1:].reshape(b, f, p, k)
    e = np.arange(b)[:, None, None]
    # held[e, i, j] = V[i, f_j]: what feature i holds for feature j's field
    held = v[e, np.arange(f)[None, :, None], fields[:, None, :]]
    dots = np.einsum("eijc,ejic->eij", held, held)
    xx = x[:, :, None] * x[:, None, :]
    upper = np.triu(np.ones((f, f)), 1)
    want = (r64[..., 0] * x).sum(1) + (dots * xx * upper).sum((1, 2))
    # d/dV[i, q] = co x_i sum_{j != i, f_j = q} V[j, f_i] x_j
    off = 1.0 - np.eye(f)
    dv = np.zeros((b, f, p, k))
    contrib = np.swapaxes(held, 1, 2) * (xx * off)[..., None]  # [e,i,j,:]
    np.add.at(dv, (e, np.arange(f)[None, :, None], fields[:, None, :]),
              contrib)
    want_g = np.concatenate(
        [x[..., None], dv.reshape(b, f, p * k)], -1) * co[:, None, None]
    return {
        "examples": b, "atol": FFM_ATOL,
        "scores_max_abs": float(np.abs(np.asarray(scores) - want).max()),
        "grad_max_abs": float(np.abs(np.asarray(grad) - want_g).max()),
        "scores_abs_mean": float(np.abs(want).mean()),
        "grad_abs_max": float(np.abs(want_g).max()),
    }


def phase_ffm(out: Out, cfg, seed: int):
    """The row the field-aware cell runs, D = 1 + 39 * 4 = 157: a
    payload of 2 D + 2 = 316 floats, three lane tiles through K1."""
    import jax

    ffm = dataclasses.replace(
        cfg, field_num=FFM_FIELDS, factor_num=FFM_K,
        max_features=FFM_FIELDS,
        vocabulary_size=min(cfg.vocabulary_size, FFM_VOCAB),
    )
    inter = _ffm_vs_host(ffm, seed)
    applies = _applies_vs_host(ffm, seed)
    from fast_tffm_tpu.train import sparse as sparse_lib

    # What gauge train.apply_stream reads on this backend at the
    # benchmark's criteo-ffm-train shape (a fact of shapes: no table).
    cell = dataclasses.replace(
        ffm, vocabulary_size=1 << 22, batch_size=16384,
        sparse_apply="scatter")
    out.emit({"phase": "ffm", "row_floats": ffm.embedding_dim,
              "apply_stream_at_cell_shape": sparse_lib.apply_stream(cell),
              "payload_lanes": -(-(2 * ffm.embedding_dim + 2) // 128) * 128,
              "interaction_vs_host": inter, "applies_vs_host": applies,
              "memory": _mem(jax.devices()[0])})
    require(max(inter["scores_max_abs"], inter["grad_max_abs"])
            <= inter["atol"],
            f"the float32 field-aware interaction differs from the "
            f"host's float64 pairs: {inter}")
    for name in APPLY_CASES:
        got = applies.get(name)  # no tile case at this row
        require(
            got is None or (
                got["rows_written"] == applies["unique_rows"]
                and got["untouched_rows_changed"] == 0
                and max(got["max_abs"]) <= got["atol"]),
            f"the {name} apply at {ffm.embedding_dim} floats a row "
            f"differs from the host's per-occurrence sums: {got}",
        )


def phase_predict(out: Out, cfg_path: str, cfg, size: Size):
    from fast_tffm_tpu import cli

    t0 = time.perf_counter()
    require(cli.main(["predict", cfg_path]) == 0, "predict returned nonzero")
    wall = time.perf_counter() - t0
    gc.collect()
    with open(cfg.score_path) as f:
        scores = np.array([float(x) for x in f.read().split()], np.float64)
    n_lines = size.predict_batches * cfg.batch_size
    out.emit({"phase": "predict", "scores": int(scores.size),
              "min": float(scores.min()), "max": float(scores.max()),
              "mean": float(scores.mean()), "wall_s": round(wall, 2)})
    require(scores.size == n_lines,
            f"{scores.size} scores for {n_lines} input lines")
    require(np.all(np.isfinite(scores)), "non-finite score")
    require(np.all((scores > 0) & (scores < 1)), "score outside (0,1)")
    return scores


def phase_serve(out: Out, cfg, predicted: np.ndarray):
    import jax

    from fast_tffm_tpu.serve import wire
    from fast_tffm_tpu.serve.server import serve
    from fast_tffm_tpu.serve.textparse import parse_request

    with open(cfg.predict_files[0]) as f:
        lines = f.read().splitlines(keepends=True)
    ladder = tuple(cfg.serve_ladder)
    top = max(ladder)
    # Below the smallest rung, on a rung, between rungs, beyond the top
    # rung (chunked) — each from another offset of the predict file.
    sizes = [1, min(ladder), min(ladder) + 3, top, top + top // 2]

    def post(path: str, body: bytes) -> bytes:
        req = urllib.request.Request(
            f"http://127.0.0.1:{handle.port}{path}", data=body,
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            require(r.status == 200, f"{path} -> {r.status}")
            return r.read()

    t0 = time.perf_counter()
    handle = serve(cfg, port=0)
    try:
        warm_s = time.perf_counter() - t0
        require(tuple(handle.scorer.ladder) == ladder,
                f"ladder {handle.scorer.ladder} != {ladder}")
        warm_compiles = handle.scorer.compiles
        require(warm_compiles == len(ladder),
                f"{warm_compiles} warm-up compiles for ladder {ladder}")
        worst = {"text": 0.0, "bin": 0.0}
        exact = {"text": 0, "bin": 0}
        for i, n in enumerate(sizes):
            lo = (i * 97) % (len(lines) - n)
            text = "".join(lines[lo:lo + n])
            want = predicted[lo:lo + n]
            got_t = np.array(
                post("/score", text.encode()).decode().split(), np.float64
            )
            ids, vals, _fields, n_parsed, _trunc = parse_request(text, cfg)
            require(n_parsed == n, f"parsed {n_parsed} of {n} lines")
            got_b = wire.decode_bin_response(post(
                "/score_bin", wire.encode_bin_request(ids[:n], vals[:n])
            )).astype(np.float64)
            for kind, got in (("text", got_t), ("bin", got_b)):
                require(got.shape == want.shape,
                        f"{kind}: {got.shape} scores for {n} lines")
                worst[kind] = max(worst[kind],
                                  float(np.abs(got - want).max()))
            exact["text"] += int(np.array_equal(got_t, want))
            exact["bin"] += int(np.array_equal(np.round(got_b, 6), want))
        with urllib.request.urlopen(
            f"http://127.0.0.1:{handle.port}/healthz", timeout=30
        ) as r:
            health = (r.status, r.read().decode().strip())
        steady = handle.scorer.steady_compiles
    finally:
        handle.close()
    gc.collect()
    out.emit({
        "phase": "serve", "ladder": list(ladder),
        "warmup_s": round(warm_s, 2), "warmup_compiles": warm_compiles,
        "request_sizes": sizes, "max_abs_vs_predict": worst,
        "requests_equal_at_6_decimals": exact,
        "serve_atol": SERVE_ATOL, "healthz": list(health),
        "steady_compiles": steady, "memory": _mem(jax.devices()[0]),
    })
    require(max(worst.values()) <= SERVE_ATOL,
            f"served scores differ from predict: {worst}")
    require(health == (200, "ok"), f"/healthz said {health}")
    require(steady == 0, f"{steady} steady-state compile(s)")


def phase_sharded(out: Out, cfg, size: Size, work: str):
    """--chips 4: the 2x2 row-sharded step vs the one-device step."""
    import jax

    from fast_tffm_tpu.parallel import mesh as mesh_lib
    from fast_tffm_tpu.train import sparse as sparse_lib

    require(cfg.mesh_data == 2 and cfg.mesh_model == 2
            and cfg.lookup == "shardmap", "cfg does not ask for 2x2 shardmap")
    base = dataclasses.replace(
        cfg, metrics_file="", validation_files=(),
        model_file=os.path.join(work, "sharded_model"),
    )
    k = size.kernel_steps
    one = _k_steps(
        dataclasses.replace(base, mesh_data=1, mesh_model=1, lookup="auto"),
        cfg.train_files, k,
        mesh=mesh_lib.make_mesh(
            dataclasses.replace(base, mesh_data=1, mesh_model=1),
            devices=jax.devices()[:1],
        ),
    )
    one_compile_s, one_placement = one["compile_s"], one["placement"]
    results, ok = {}, True
    on_chip = jax.default_backend() == "tpu"

    def case(name, cfg_case, ref):
        nonlocal ok
        got = _k_steps(cfg_case, cfg.train_files, k)
        cmp = _compare(got, ref, F32_TOL, ACC_TOL)
        place = got["placement"]
        want_place = {
            "shard_rows": [cfg_case.vocabulary_size // 2],
            "shard_devices": [4],
            "batch_shard_rows": [cfg.batch_size // 2],
        }
        results[name] = {
            "vs_one_device": cmp, "placement": place,
            "want_placement": want_place,
            # what gauge train.apply_stream reads for this step
            "apply_stream": sparse_lib.apply_stream(
                cfg_case, mesh_lib.make_mesh(cfg_case)),
            "compile_s": got["compile_s"], "wall_s": got["wall_s"],
        }
        ok = ok and _passed(cmp) and all(
            place[key] == val for key, val in want_place.items()
        )

    for exchange in ("entries", "dense"):
        case(exchange, dataclasses.replace(base, sparse_exchange=exchange),
             one)
    del one
    # A shard the row-major K2 cannot hold (the compiler refuses its
    # whole-shard copies from 2^24 rows, PERF.md section 4): the entries
    # step must take the merged stream through the stream writer there.
    # The one-device side holds the same vocabulary by the scatter apply.
    big = dataclasses.replace(
        base, sparse_exchange="entries", sparse_apply="scatter",
        vocabulary_size=(2 * BIG_SHARD_ROWS if on_chip
                         else 4 * cfg.vocabulary_size),
    )
    one_big = _k_steps(
        dataclasses.replace(big, mesh_data=1, mesh_model=1, lookup="auto"),
        cfg.train_files, k,
        mesh=mesh_lib.make_mesh(
            dataclasses.replace(big, mesh_data=1, mesh_model=1),
            devices=jax.devices()[:1],
        ),
    )
    case("entries_big_shard", big, one_big)
    del one_big
    if on_chip:
        ok = ok and all(results[name]["apply_stream"]
                        for name in ("entries", "entries_big_shard"))
    out.emit({
        "phase": "sharded", "mesh": {"data": 2, "model": 2},
        "vocabulary_size": cfg.vocabulary_size, "steps": k,
        "f32_tol": F32_TOL, "acc_tol": ACC_TOL,
        "one_device_placement": one_placement,
        "one_device_compile_s": one_compile_s, **results,
        "big_shard_rows": big.vocabulary_size // 2,
        "memory": [_mem(d) for d in jax.devices()[:4]],
    })
    require(ok, f"sharded step: mismatch or unreal placement: {results}")


# -------------------------------------------------------------------- main


def run(chips: int = 1, rehearse: bool = False, seed: int = 0,
        work: str | None = None, out_path: str | None = None) -> dict:
    """All phases; returns the final line's object.  Raises on any
    failure (SystemExit(2) when there is no TPU and no --rehearse)."""
    from fast_tffm_tpu import platform

    if rehearse:
        # The fused ship is TPU-gated (device_put is zero-copy on CPU);
        # the rehearsal forces it so its control flow is rehearsed too.
        os.environ.setdefault("FAST_TFFM_FUSED_H2D", "1")
    out = Out(out_path)
    work = work or os.path.join(REPO, ".chip_smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        device = phase_device(out, rehearse, chips)
        # JAX_COMPILATION_CACHE_DIR where set, else one fixed path in
        # the checkout: a second run of the same call then hits.
        platform.enable_compile_cache(platform.REPO_COMPILE_CACHE_DIR)
        size = TOY if rehearse else FULL
        extra = None
        if chips == 4:
            extra = {"mesh_data": "2", "mesh_model": "2",
                     "lookup": "shardmap", "sparse_exchange": "entries"}
        cfg_path, cfg, gen_s = make_inputs(work, size, seed, extra)
        out.emit({"phase": "inputs", "seed": seed, "gen_s": gen_s,
                  "train_lines": size.train_batches * cfg.batch_size,
                  "cfg": cfg_path})
        if chips == 4:
            phase_sharded(out, cfg, size, work)
        else:
            phase_train(out, cfg_path, cfg, size, rehearse)
            phase_kernels(out, cfg, size, work, seed)
            phase_ffm(out, cfg, seed)
            predicted = phase_predict(out, cfg_path, cfg, size)
            phase_serve(out, cfg, predicted)
        stats = platform.compile_cache_stats()
        out.emit({"phase": "compile_cache", **stats})
        final = {"ok": True, "device": device}
        if rehearse:
            final["rehearsal"] = True
        out.emit(final)
        return final
    finally:
        out.close()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the 2x2 row-sharded phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy-size CPU rehearsal (never a chip result)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        from fast_tffm_tpu.platform import pin_cpu

        pin_cpu(max(args.chips, 1))
    tag = "rehearsal" if args.rehearse else f"{args.chips}chip"
    run(chips=args.chips, rehearse=args.rehearse, seed=args.seed,
        out_path=os.path.join(REPO, "chiprun_out", f"chip_smoke_{tag}.jsonl"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
