#!/usr/bin/env python
"""One-shot TPU validation + timing sweep for the Pallas paths.

Run on the real chip (one TPU process at a time!):

    python tools/tpu_validate.py [--quick]

Sections:
  1. correctness: flat fwd/bwd kernels + tile sparse apply vs XLA oracle
  2. component timings: sort / perm / cumsum / K1 / K2 / fwd+bwd
  3. step timings: full train step under scatter vs tile apply

All timings force completion with scalar readbacks (tools/timing.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from timing import bench, drain  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny shapes for an off-TPU plumbing check (interpret-mode "
        "kernels at real shapes would take hours on CPU)",
    )
    ap.add_argument(
        "--out", default="",
        help="also write a markdown report (e.g. TPU_RESULTS.md)",
    )
    ap.add_argument(
        "--sweep-blocks", action="store_true",
        help="time K1/K2 across CHUNK/TILE/GROUP sizes (grid-overhead vs "
        "MXU tradeoff is hardware-dependent; sweep on the chip, then pin "
        "winners via FAST_TFFM_K1_CHUNK / FAST_TFFM_K2_TILE / "
        "FAST_TFFM_K2_GROUP)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.ops import fm_pallas, interaction, sparse_apply
    from fast_tffm_tpu.platform import is_tpu_backend, use_interpret

    report: list[str] = []

    def emit(line: str) -> None:
        print(line, flush=True)
        report.append(line)

    emit(f"devices: {jax.devices()}")
    on_tpu = is_tpu_backend()
    emit(f"backend: {jax.default_backend()} (tpu={on_tpu})")

    B, F, K = (4096, 39, 8) if args.quick else (16384, 39, 8)
    V = 1 << 22
    if args.smoke:
        B, F, K, V = 256, 8, 8, 1 << 12
    D = 1 + K
    rng = np.random.default_rng(0)

    # ---- 1. correctness ------------------------------------------------
    rows = jax.device_put(
        jnp.asarray(rng.uniform(-0.1, 0.1, (B, F, D)), jnp.float32))
    vals = jax.device_put(
        jnp.asarray(rng.uniform(0.1, 1.0, (B, F)), jnp.float32))
    g = jax.device_put(jnp.asarray(rng.uniform(-1, 1, (B,)), jnp.float32))

    sc_p, s1_p = fm_pallas.fm_scores_pallas(rows, vals, interpret=use_interpret())
    sc_o, s1_o = jax.jit(interaction._scores_jnp)(rows, vals)
    err_f = float(jnp.max(jnp.abs(sc_p - sc_o)))
    dr_p = fm_pallas.fm_grad_pallas(rows, vals, s1_p, g, interpret=use_interpret())
    dr_o = jax.jit(interaction._grads_jnp)(rows, vals, s1_o, g)
    err_b = float(jnp.max(jnp.abs(dr_p - dr_o)))
    emit(f"fwd kernel max err: {err_f:.3e}  bwd: {err_b:.3e}")
    assert err_f < 1e-4 and err_b < 1e-4, "KERNEL MISMATCH"

    N = B * F
    ids = jax.device_put(
        jnp.asarray(rng.integers(0, V, (N,)), jnp.int32))
    g_rows = jax.device_put(
        jnp.asarray(rng.uniform(-1e-2, 1e-2, (N, D)), jnp.float32))
    table = jax.device_put(
        jnp.asarray(rng.uniform(-0.1, 0.1, (V, D)), jnp.float32))
    acc = jnp.full((V, D), 0.1, jnp.float32)
    lr, eps = 0.05, 1e-7

    t_tile, a_tile = jax.jit(
        lambda t, a, i, gg: sparse_apply.adagrad_apply(
            t, a, i, gg, lr=lr, eps=eps)
    )(table, acc, ids, g_rows)
    a_ref = acc.at[ids].add(g_rows * g_rows)
    t_ref = table.at[ids].add(
        -lr * g_rows * jax.lax.rsqrt(a_ref[ids] + eps))
    terr = float(jnp.max(jnp.abs(t_tile - t_ref)))
    aerr = float(jnp.max(jnp.abs(a_tile - a_ref)))
    emit(f"tile adagrad max err: table {terr:.3e} acc {aerr:.3e}")
    assert terr < 1e-4, "TILE APPLY MISMATCH"

    # ---- 2. component timings -----------------------------------------
    iota = jnp.arange(N, dtype=jnp.int32)
    t = {}
    t["sort_key_val"] = bench(
        jax.jit(lambda i: jax.lax.sort_key_val(i, iota)), ids)
    perm = jax.device_put(jnp.asarray(rng.permutation(N), jnp.int32))
    t["perm_gather"] = bench(jax.jit(lambda gg, p: gg[p]), g_rows, perm)
    t["cumsum"] = bench(
        jax.jit(lambda i: jnp.cumsum((i != 0).astype(jnp.int32))), ids)
    t["fwd_pallas"] = bench(
        lambda r, v: fm_pallas.fm_scores_pallas(r, v, interpret=use_interpret()),
        rows, vals)
    t["fwd_jnp"] = bench(jax.jit(interaction._scores_jnp), rows, vals)
    t["bwd_pallas"] = bench(
        lambda r, v, s, gg: fm_pallas.fm_grad_pallas(
            r, v, s, gg, interpret=use_interpret()), rows, vals, s1_p, g)
    t["bwd_jnp"] = bench(jax.jit(interaction._grads_jnp), rows, vals, s1_o, g)
    t["tile_adagrad_apply"] = bench(
        jax.jit(lambda tb, a, i, gg: sparse_apply.adagrad_apply(
            tb, a, i, gg, lr=lr, eps=eps)), table, acc, ids, g_rows)
    t["scatter_adagrad_apply"] = bench(
        jax.jit(lambda tb, a, i, gg: (
            lambda an: (tb.at[i].add(-lr * gg * jax.lax.rsqrt(an[i] + eps)),
                        an))(a.at[i].add(gg * gg))),
        table, acc, ids, g_rows)
    t["gather_2d"] = bench(
        jax.jit(lambda tb, i: tb[i]), table,
        jax.device_put(jnp.asarray(
            rng.integers(0, V, (B, F)), jnp.int32)))
    for k_, v_ in t.items():
        emit(f"  {k_:24s} {v_:9.3f} ms")
    # K2 (tile apply) is bandwidth-bound by design: it streams table+acc
    # in AND out once per step (4 x V x D x 4 bytes) plus the sorted
    # unique-entry stream.  Derived utilization makes the claim testable
    # against the chip's HBM spec (v5e ~= 819 GB/s) — that comparison is
    # only meaningful on the chip, not in CPU interpret mode.
    k2_bytes = 4 * V * D * 4
    k2_gbs = k2_bytes / (t["tile_adagrad_apply"] * 1e-3) / 1e9
    spec = " (v5e HBM ~819 GB/s peak)" if on_tpu else " (CPU interpret)"
    emit(
        f"  tile apply moves {k2_bytes / 1e6:.0f} MB/step -> "
        f"{k2_gbs:.0f} GB/s achieved{spec}"
    )
    emit(
        f"  tile vs scatter speedup: "
        f"{t['scatter_adagrad_apply'] / t['tile_adagrad_apply']:.1f}x"
    )

    # Compact K2 A/B (small batch): with 900 ids (-> 1024 padded
    # entries) the touched-group grid covers at most half of V=2^22's
    # 2048 groups, so FAST_TFFM_K2_COMPACT's auto heuristic would
    # engage — this measures whether touched-only streaming wins on
    # real DMA behavior (PERF.md, "One v5e window, round 4") and
    # verifies both paths agree on chip.  Fail-soft like the sweep.
    try:
        ids_small = jax.device_put(
            jnp.asarray(rng.integers(0, V, (900,)), jnp.int32))
        g_small = jax.device_put(
            jnp.asarray(rng.uniform(-1, 1, (900, D)), jnp.float32))
        fns = {
            compact: jax.jit(
                lambda tb, a, i, gg, c=compact: sparse_apply.adagrad_apply(
                    tb, a, i, gg, lr=lr, eps=eps, compact=c))
            for compact in (False, True)
        }
        # Parity first, outputs freed BEFORE timing (the sweep's rule:
        # extra (V, D) arrays held across a bench can OOM / skew it).
        outs = {c: fn(table, acc, ids_small, g_small)
                for c, fn in fns.items()}
        err_c = max(
            float(jnp.max(jnp.abs(a_ - b_)))
            for a_, b_ in zip(outs[False], outs[True])
        )
        del outs
        flag = "" if err_c < 1e-4 else "  WRONG"
        emit(f"  compact parity err {err_c:.2e}{flag}")
        for compact, fn in fns.items():
            ms_c = bench(fn, table, acc, ids_small, g_small)
            emit(f"  small-batch apply compact={int(compact)}: "
                 f"{ms_c:9.3f} ms")
    except Exception as exc:  # noqa: BLE001 — must not kill the window
        emit(f"  compact A/B FAILED: {type(exc).__name__}: "
             f"{str(exc).splitlines()[0][:150]}")

    if args.sweep_blocks:
        # K1 runs N/CHUNK sequential grid steps (per-step overhead) with
        # one-hot matmul work growing ~CHUNK per occurrence; K2's TILE
        # fixes the window DMA size and placement-matmul shape.  The
        # optimum is a hardware property — measure, don't guess.
        emit("block-size sweep (ms):")
        orig_chunk, orig_tile = sparse_apply.CHUNK, sparse_apply.TILE

        def try_candidate(label):
            # Fail-soft: Mosaic VMEM allocation happens at COMPILE time
            # (the big candidates' one-hot intermediates approach the
            # ~16MB scoped-VMEM limit), which cross-platform lowering
            # tests cannot check — a losing candidate must not kill the
            # hardware window.  Each candidate is also verified against
            # the scatter reference: a fast-but-WRONG block size must
            # never win the sweep.
            try:
                fn = jax.jit(
                    lambda tb, a, i, gg: sparse_apply.adagrad_apply(
                        tb, a, i, gg, lr=lr, eps=eps)
                )
                t_c, a_c = fn(table, acc, ids, g_rows)
                err = max(
                    float(jnp.max(jnp.abs(t_c - t_ref))),
                    float(jnp.max(jnp.abs(a_c - a_ref))),
                )
                # Free the check outputs before timing: two extra (V, D)
                # arrays held across the bench could OOM a big candidate
                # that would fit in production.
                del t_c, a_c
                ms = bench(fn, table, acc, ids, g_rows)
                flag = "" if err < 1e-4 else f"  WRONG (err {err:.2e})"
                emit(f"  {label}: {ms:9.3f}{flag}")
            except Exception as exc:  # noqa: BLE001
                emit(f"  {label}: FAILED {type(exc).__name__}: "
                     f"{str(exc).splitlines()[0][:150]}")

        orig_group = sparse_apply.GROUP
        orig_k1_group = sparse_apply.K1_GROUP
        try:
            for chunk in (256, 512, 1024, 2048):
                sparse_apply.CHUNK = chunk
                try_candidate(f"K1 CHUNK={chunk:5d} (TILE={orig_tile})")
            sparse_apply.CHUNK = orig_chunk
            for tile in (256, 512):
                if V % tile:
                    continue
                sparse_apply.TILE = tile
                try_candidate(f"K2 TILE={tile:6d} (CHUNK={orig_chunk})")
            sparse_apply.TILE = orig_tile
            for group in (1, 4, 8, 16, 32):
                sparse_apply.GROUP = group
                try_candidate(
                    f"K2 GROUP={group:5d} (TILE={orig_tile})"
                )
            sparse_apply.GROUP = orig_group
            for group in (1, 4, 16):
                sparse_apply.K1_GROUP = group
                try_candidate(
                    f"K1 GROUP={group:5d} (CHUNK={orig_chunk})"
                )
        finally:
            sparse_apply.CHUNK = orig_chunk
            sparse_apply.TILE = orig_tile
            sparse_apply.GROUP = orig_group
            sparse_apply.K1_GROUP = orig_k1_group

    # ---- 3. full steps -------------------------------------------------
    import shutil

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.libsvm import Batch
    from fast_tffm_tpu.train.loop import Trainer

    combos = [
        # (sparse_apply, use_pallas, dtype, field_num, host_sort, env)
        ("scatter", False, "float32", 0, True, {}),
        ("scatter", True, "float32", 0, True, {}),
        ("tile", False, "float32", 0, True, {}),
        # host_sort on/off at the default config: isolates the win from
        # moving the id sort + prep metadata onto pipeline threads.
        ("tile", True, "float32", 0, False, {}),
        ("tile", True, "float32", 0, True, {}),
        ("tile", True, "bfloat16", 0, True, {}),  # the fast path's bf16
        ("tile", "flat", "float32", 0, True, {}),  # pure-XLA flat
        # Field-aware FM (BASELINE config 5): closed-form ffm_interaction
        # (pinned "0" so an externally exported variable can't silently
        # turn this into a second autodiff run) vs the autodiff einsum
        # oracle — one window settles which backward wins on chip.
        ("tile", True, "float32", 4, True, {"FAST_TFFM_FFM_AUTODIFF": "0"}),
        ("tile", True, "float32", 4, True, {"FAST_TFFM_FFM_AUTODIFF": "1"}),
    ]
    for mode, use_pallas, dtype, field_num, host_sort, env in combos:
        env_saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        cfg = FmConfig(
            vocabulary_size=V, factor_num=K, max_features=F,
            batch_size=B, learning_rate=0.05, log_steps=0,
            sparse_apply=mode,
            use_pallas=(use_pallas is True),
            interaction="flat" if use_pallas == "flat" else "",
            compute_dtype=dtype, field_num=field_num,
            host_sort=host_sort,
            model_file=(
                f"/tmp/tpuval_{mode}_{use_pallas}_{dtype}_{field_num}"
                f"_{int(host_sort)}"
            ),
        )
        shutil.rmtree(cfg.model_file, ignore_errors=True)
        trainer = Trainer(cfg)
        batches = []
        for _ in range(4):
            batches.append(trainer._put(Batch(
                labels=rng.integers(0, 2, (B,)).astype(np.float32),
                ids=rng.integers(0, V, (B, F)).astype(np.int32),
                vals=rng.uniform(0.1, 1.0, (B, F)).astype(np.float32),
                fields=(
                    rng.integers(0, field_num, (B, F)).astype(np.int32)
                    if field_num else np.zeros((B, F), np.int32)
                ),
                weights=np.ones((B,), np.float32),
            )))

        # rotate batches without host sync
        def run_n(n, trainer=trainer, batches=batches):
            for i in range(n):
                trainer.state = trainer._train_step(
                    trainer.state, batches[i % 4])
            return trainer.state

        drain(run_n(3))
        steps = 10 if args.quick else 30
        t0 = time.perf_counter()
        st = run_n(steps)
        drain((st.metrics.loss_sum, st.params.table[0, 0], st.step))
        dt = time.perf_counter() - t0
        ms = dt * 1e3 / steps
        emit(json.dumps({
            "step": (
                f"sparse_apply={mode} interaction={cfg.interaction_resolved} "
                f"compute_dtype={dtype}"
                + (f" field_num={field_num}" if field_num else "")
                + ("" if host_sort else " host_sort=off")
                + ("".join(f" {k}={v}" for k, v in env.items()))
            ),
            "ms_per_step": round(ms, 2),
            "examples_per_sec": round(B * steps / dt, 1),
        }))
        for k, old in env_saved.items():  # restore, don't just delete
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old

    # ---- 3b. north-star vocab single chip (fail-soft) ------------------
    # The flagship config (examples/criteo_1tb_dist.cfg) is V=2^26; the
    # standard combos run V=2^22, where the O(V) terms of the tile apply
    # are 16x cheaper.  One V=2^26 step answers (a) whether the [V, 9]
    # table's PHYSICAL footprint allows it at all (HBM tiling may pad the
    # minor dim to 128 lanes — the memory_stats question in
    # PERF.md's table-layout decision tree) and (b) what the tile path costs at
    # the vocab the project is judged on.  Fail-soft: an OOM here is
    # itself the measurement.
    if on_tpu and not args.quick and not args.smoke:
        v_ns = 1 << 26
        cfg = FmConfig(
            vocabulary_size=v_ns, factor_num=K, max_features=F,
            batch_size=B, learning_rate=0.05, log_steps=0,
            sparse_apply="tile", use_pallas=True,
            model_file="/tmp/tpuval_northstar",
        )
        shutil.rmtree(cfg.model_file, ignore_errors=True)
        try:
            trainer = Trainer(cfg)
            b_ns = trainer._put(Batch(
                labels=rng.integers(0, 2, (B,)).astype(np.float32),
                ids=rng.integers(0, v_ns, (B, F)).astype(np.int32),
                vals=rng.uniform(0.1, 1.0, (B, F)).astype(np.float32),
                fields=np.zeros((B, F), np.int32),
                weights=np.ones((B,), np.float32),
            ))
            for _ in range(3):
                trainer.state = trainer._train_step(trainer.state, b_ns)
            drain(trainer.state)
            steps = 10
            t0 = time.perf_counter()
            for i in range(steps):
                trainer.state = trainer._train_step(trainer.state, b_ns)
            drain((trainer.state.metrics.loss_sum,
                   trainer.state.params.table[0, 0], trainer.state.step))
            dt = time.perf_counter() - t0
            stats = {}
            try:
                stats = jax.devices()[0].memory_stats() or {}
            except Exception:  # noqa: BLE001 - optional on some backends
                pass
            emit(json.dumps({
                "step": f"NORTH-STAR vocab=2^26 sparse_apply=tile B={B}",
                "ms_per_step": round(dt * 1e3 / steps, 2),
                "examples_per_sec": round(B * steps / dt, 1),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            }))
            del trainer
        except Exception as e:  # noqa: BLE001 - OOM IS the data point
            emit(json.dumps({
                "step": "NORTH-STAR vocab=2^26 sparse_apply=tile",
                "error": f"{type(e).__name__}: {e}"[:400],
            }))

    if args.out:
        flags = "".join(
            f" --{name.replace('_', '-')}" for name in
            ("quick", "smoke", "sweep_blocks")
            if getattr(args, name)
        )
        header = [
            "# TPU validation results",
            "",
            f"`python tools/tpu_validate.py{flags} --out {args.out}`"
            f" — B={B}, F={F}, k={K}, vocab=2^{V.bit_length() - 1}.",
            "",
            "```",
        ]
        with open(args.out, "w") as f:
            f.write("\n".join(header + report + ["```", ""]))
        print(f"report written to {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
