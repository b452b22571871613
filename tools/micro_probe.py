#!/usr/bin/env python
"""On-chip micro-experiments behind the step-time hot spots.

The first v5e run (Criteo-Kaggle shapes, before PERF.md's benchmark
existed) showed three XLA-side costs dwarfing the kernels: the 640k-row
table gather, the id sort and a length-640k cumsum.  Each experiment
here isolates one design question for those:

  gather:  does row width (burst size) or index sortedness change the
           achieved row rate?  Decides whether packing the table to
           128-lane rows is worth plumbing through the framework.
  cumsum:  XLA lowers 1-D cumsum to log-depth passes; a blocked
           [rows, 128] reformulation (cumsum inside lanes via matmul
           with a triangular matrix + row-offset broadcast) keeps it
           MXU/VPU-shaped.  Decides how _prep should compute upos.
  sort:    cost vs N and vs key width (the sharded path sorts N/shards
           per device; 32- vs 64-bit keys tests packing id+perm into
           one key as an alternative to sort_key_val).

Timing: completion is forced by fetching one scalar from every output
leaf (``bench`` / ``_drain`` below).  ``k2p_apply`` is the packed-layout
apply-kernel candidate of ROADMAP.md Speed 1; tests/test_tpu_lowering.py
keeps it lowering.  (The transposed candidate, ``k2t_apply``, became
the one-device apply's stream writer: ops/sparse_apply.py, PR 34.)
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


from functools import partial as _partial

import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from fast_tffm_tpu.ops import sparse_apply as sa
from fast_tffm_tpu.platform import use_interpret


def _drain(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        np.asarray(jax.device_get(
            leaf.reshape(-1)[:1] if hasattr(leaf, "reshape") else leaf
        ))


def bench(fn, *args, steps=20):
    for _ in range(2):
        _drain(fn(*args))
    t0 = time.perf_counter()
    r = None
    for _ in range(steps):
        r = fn(*args)
    _drain(r)
    return (time.perf_counter() - t0) * 1e3 / steps


def _k2p_kernel(ts_ref, table_ref, acc_ref, u_hbm_ref, table_out_ref,
                acc_out_ref, u_vmem, sem, *, tile, group, d, lr, eps):
    """Packed-layout K2: tables stored [V/8, 128] — 8 consecutive rows
    of 16 lanes (d values + pad) per 128-lane line, so the physical HBM
    stream is ~1.8x logical instead of the ~14x a lane-padded [V, 9]
    layout costs (decision tree in PERF.md, "One v5e window, round 4").  Placement: entry
    payloads are lane-shifted into their slot with pure VPU iota math
    (no relayout reshapes), then one [R, lines] one-hot matmul sums
    them per packed line."""
    lines = tile // 8
    # Loop-invariant one-hot constants, hoisted out of the unrolled
    # subtile loop (this kernel is timed against production — redundant
    # per-iteration VPU constant builds would bias the comparison).
    # Lane-slot packing is done with one-hot matmuls: lane gathers/
    # shuffles have no reliable Mosaic lowering, and 0/1 matrices are
    # bf16-exact so only u needs the hi/lo split.  G_g1[a, c] =
    # (a == c%16 < d) spreads the g1 lanes into every 16-lane slot.
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, 128), 1)
    a_iota = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    cmod = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1) % 16
    keep = cmod < d
    g_g1 = ((a_iota == cmod) & keep).astype(jnp.bfloat16)
    g_g2 = ((a_iota == cmod + d) & keep).astype(jnp.bfloat16)
    l_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, lines), 1)
    dn = (((0,), (0,)), ((), ()))  # contract entries

    def body(j, u, cnt):
        valid = e_iota < cnt
        u = jnp.where(valid, u, 0.0)
        lrow = u[:, 2 * d:2 * d + 1].astype(jnp.int32)  # [R, 1]
        # slotmask keeps only the entry's own 16-lane slot.
        slotmask = ((c_iota // 16) == (lrow % 8)).astype(jnp.float32)
        u_hi = u.astype(jnp.bfloat16)
        u_lo = (u - u_hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def spread(gmat):  # [R, 128] with pay lanes in every slot
            return (
                jax.lax.dot(u_hi, gmat,
                            preferred_element_type=jnp.float32)
                + jax.lax.dot(u_lo, gmat,
                              preferred_element_type=jnp.float32)
            )

        g1_sl = spread(g_g1) * slotmask  # [R, 128] slotted
        g2_sl = spread(g_g2) * slotmask
        # Line one-hot [R, lines] and the two placement matmuls.
        p = (((lrow // 8) == l_iota) & valid).astype(jnp.bfloat16)

        def place(x):
            x_hi = x.astype(jnp.bfloat16)
            x_lo = (x - x_hi.astype(jnp.float32)).astype(jnp.bfloat16)
            return (
                jax.lax.dot_general(p, x_hi, dn,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(p, x_lo, dn,
                                      preferred_element_type=jnp.float32)
            )  # [lines, 128]

        g1p = place(g1_sl)
        g2p = place(g2_sl)
        rows = pl.ds(j * lines, lines)
        acc_new = acc_ref[rows, :] + g2p
        table_out_ref[rows, :] = table_ref[rows, :] - lr * g1p * (
            jax.lax.rsqrt(acc_new + eps))
        acc_out_ref[rows, :] = acc_new

    sa._window_loop_raw(
        ts_ref, u_hbm_ref, u_vmem, sem, tile=tile, group=group, body=body
    )


def k2p_apply(table_p, acc_p, ids_, g_rows, *, lr, eps):
    """table_p/acc_p are packed [vocab/8, 128] (8 rows x 16 lanes)."""
    vocab = table_p.shape[0] * 8
    d = g_rows.shape[1]
    u, tile_start = sa._dedup_and_starts(ids_, g_rows, vocab)
    tile, group = sa.TILE, sa._group_for(vocab // sa.TILE)
    block_lines = (tile * group) // 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(vocab // (tile * group),),
        in_specs=[pl.BlockSpec((block_lines, 128), lambda t, *_: (t, 0))] * 2
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((block_lines, 128),
                                lambda t, *_: (t, 0))] * 2,
        scratch_shapes=[
            pltpu.VMEM((2, tile, u.shape[1]), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _partial(_k2p_kernel, tile=tile, group=group, d=d, lr=lr, eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((vocab // 8, 128), jnp.float32)] * 2,
        input_output_aliases={1: 0, 2: 1},
        interpret=use_interpret(),
    )(tile_start, table_p, acc_p, u)


def pack_table(t, d):
    """[V, d] -> packed [V/8, 128] (8 rows x 16 lanes, zero pad)."""
    v = t.shape[0]
    padded = jnp.concatenate(
        [t, jnp.zeros((v, 16 - d), t.dtype)], axis=1
    )
    return padded.reshape(v // 8, 128)


def unpack_table(tp, d):
    v8 = tp.shape[0]
    return tp.reshape(v8 * 8, 16)[:, :d]


def main() -> int:
    import jax

    # The packed-key sort experiment needs real int64: without x64 JAX
    # silently downcasts to int32 and (id << 20) wraps for id >= 2^12,
    # timing a 32-bit sort of garbage keys.
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    print(f"devices: {jax.devices()}", flush=True)
    rng = np.random.default_rng(0)
    V, N = 1 << 22, 16384 * 39

    # ---- physical size of narrow-minor-dim HBM buffers ----------------
    # If XLA tiles [V, 9] f32 to 128 lanes in HBM, the table physically
    # occupies ~14x its logical bytes and K2's "stream the table" pass
    # moves ~8.6 GB/step instead of ~600 MB — the deciding fact for a
    # packed [V/8, 128] storage format.
    dev = jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if stats:
        base = stats["bytes_in_use"]
        tb = jax.device_put(jnp.zeros((V, 9), jnp.float32))
        tb.block_until_ready()
        used = dev.memory_stats()["bytes_in_use"] - base
        logical = V * 9 * 4
        print(
            f"  [V,9] f32 table: logical {logical / 1e6:.0f} MB, device "
            f"{used / 1e6:.0f} MB ({used / logical:.1f}x)", flush=True)
        del tb
    else:
        print("  memory_stats unavailable on this backend", flush=True)

    # ---- gather: row width x index sortedness ------------------------
    ids_np = rng.integers(0, V, (N,)).astype(np.int32)
    ids = jax.device_put(jnp.asarray(ids_np))
    ids_sorted = jax.device_put(jnp.asarray(np.sort(ids_np)))
    gather = jax.jit(lambda tb, i: tb[i])
    for d in (9, 16, 32, 64, 128):
        tb = jax.device_put(
            jnp.asarray(rng.uniform(-1, 1, (V, d)), jnp.float32))
        ms_r = bench(gather, tb, ids)
        ms_s = bench(gather, tb, ids_sorted)
        rate = N / (ms_r * 1e-3) / 1e6
        print(
            f"  gather [{V},{d:3d}] x {N}: random {ms_r:7.3f} ms "
            f"({rate:5.1f}M rows/s)  sorted {ms_s:7.3f} ms", flush=True)
        del tb

    # Packed-layout gather: table as [V/8, 128] super-rows (8 logical
    # rows x 16-lane slots).  The gather touches V/8-row space at 512-
    # byte rows; the slot select is VPU work.  Compares the end-to-end
    # cost of producing the same [N, 16] rows against the [V, 9] gather
    # above — decides whether packing pays on the lookup side too.
    packed = jax.device_put(
        jnp.asarray(rng.uniform(-1, 1, (V // 8, 128)), jnp.float32))

    def packed_gather(tb, i):
        sup = tb[i >> 3]  # [N, 128]
        slot = (i & 7).astype(jnp.int32)
        oh = (slot[:, None] == jnp.arange(8, dtype=jnp.int32)[None, :])
        sel = jnp.einsum(
            "ns,nsl->nl", oh.astype(jnp.float32),
            sup.reshape(-1, 8, 16), precision=jax.lax.Precision.HIGHEST)
        return sel  # [N, 16]

    pg = jax.jit(packed_gather)
    ms_r = bench(pg, packed, ids)
    ms_s = bench(pg, packed, ids_sorted)
    print(
        f"  packed-gather [V/8,128]+select: random {ms_r:7.3f} ms  "
        f"sorted {ms_s:7.3f} ms", flush=True)
    del packed

    # Transposed-table gather: storing the table as [9, V] (minor dim
    # dense, sublanes 9->16) cuts its physical HBM footprint ~8x vs the
    # lane-padded [V, 9], which would shrink K2's table streaming the
    # same way — IF gathering 640k columns isn't pathological.
    tb_t = jax.device_put(
        jnp.asarray(rng.uniform(-1, 1, (9, V)), jnp.float32))
    cg = jax.jit(lambda tb, i: tb[:, i])
    ms_r = bench(cg, tb_t, ids)
    ms_s = bench(cg, tb_t, ids_sorted)
    print(
        f"  column-gather [9,V] x {N}: random {ms_r:7.3f} ms  "
        f"sorted {ms_s:7.3f} ms", flush=True)
    del tb_t

    # ---- lane efficiency of [B, F, 9] elementwise chains --------------
    # fwd/bwd stream [B, F, D] arrays whose minor dim pads 9 -> 128
    # (7% lane use).  Times one representative op in three layouts.
    B, F = 16384, 39
    r3 = jax.device_put(
        jnp.asarray(rng.uniform(-1, 1, (B, F, 9)), jnp.float32))
    vals2 = jax.device_put(
        jnp.asarray(rng.uniform(0.1, 1.0, (B, F)), jnp.float32))
    t_bfd = bench(
        jax.jit(lambda r, v: jnp.sum(r * v[..., None], axis=1)), r3, vals2)
    rflat = jax.device_put(
        jnp.asarray(rng.uniform(-1, 1, (B, F * 9)), jnp.float32))
    # Same logical workload as the [B,F,9] variant: vals stay [B, F] and
    # broadcast per-factor inside the jitted fn (an independent [B,F*9]
    # vals array would add ~3x the vals HBM traffic and bias the
    # comparison against the flat layout).
    t_flat = bench(
        jax.jit(lambda r, v: jnp.sum(
            (r * jnp.repeat(v, 9, axis=1)).reshape(-1, F, 9), axis=1)),
        rflat, vals2)
    t_flat_nosum = bench(
        jax.jit(lambda r, v: r * jnp.repeat(v, 9, axis=1)), rflat, vals2)
    print(
        f"  elementwise+field-sum: [B,F,9] {t_bfd:6.3f} ms   "
        f"[B,F*9]->reshape-sum {t_flat:6.3f} ms   "
        f"[B,F*9] mult-only {t_flat_nosum:6.3f} ms", flush=True)

    # one-hot matmul gather at 128 width for contrast (tile-streamed
    # idea lower bound, measured as pure XLA): skipped, O(N*V) infeasible.

    # ---- reshape relayout + forward-path variants ---------------------
    # fm_pallas calls rows.reshape(b, F*D) "a free bitcast" — on TPU the
    # two shapes tile differently ([B,F,9] pads 9->128 lanes; [B,351]
    # pads to 384), so the reshape may be a real relayout copy.  Time it,
    # and time three full forward implementations: the production jnp
    # oracle, the Pallas kernel, and a pure-XLA version of the kernel's
    # flat one-hot-matmul math (no Pallas overhead; XLA free to fuse).
    Dd = 9
    rows3 = r3  # reuse the lane-efficiency section's arrays (and vals2)
    t_resh = bench(
        jax.jit(lambda r: r.reshape(B, F * Dd) + 1.0), rows3)
    t_noop = bench(jax.jit(lambda r: r + 1.0), rows3)
    print(
        f"  reshape [B,F,9]->[B,351] (+1): {t_resh:6.3f} ms   "
        f"(+1 alone in 3-D: {t_noop:6.3f} ms)", flush=True)

    from fast_tffm_tpu.ops import fm_pallas, interaction

    fwd_flat_xla = interaction._scores_flat  # the production flat impl

    import functools

    jnp_fwd = jax.jit(interaction._scores_jnp)
    flat_fwd = jax.jit(fwd_flat_xla)
    t_jnp = bench(jnp_fwd, rows3, vals2)
    if jax.default_backend() != "cpu":
        # fm_scores_pallas is itself jitted (reshape/pad fused in); the
        # partial only pins the static interpret flag.
        t_pal = bench(
            functools.partial(fm_pallas.fm_scores_pallas, interpret=False),
            rows3, vals2)
    else:
        t_pal = float("nan")  # compiled Pallas needs the chip
    t_flatx = bench(flat_fwd, rows3, vals2)
    s_ref, _ = jnp_fwd(rows3, vals2)
    s_got, _ = flat_fwd(rows3, vals2)
    err = float(jnp.max(jnp.abs(s_ref - s_got)))
    print(
        f"  fwd: jnp {t_jnp:6.3f} ms   pallas {t_pal:6.3f} ms   "
        f"flat-xla {t_flatx:6.3f} ms (err {err:.1e})", flush=True)

    # ---- scatter-add: same axes --------------------------------------
    for d in (9, 128):
        tb = jax.device_put(jnp.zeros((V, d), jnp.float32))
        g = jax.device_put(
            jnp.asarray(rng.uniform(-1, 1, (N, d)), jnp.float32))
        sc = jax.jit(lambda tb, i, g: tb.at[i].add(g))
        ms_r = bench(sc, tb, ids, g)
        ms_s = bench(sc, tb, ids_sorted, g)
        print(
            f"  scatter-add [{V},{d:3d}]: random {ms_r:7.3f} ms  "
            f"sorted {ms_s:7.3f} ms", flush=True)
        del tb, g

    # ---- packed-K2 prototype ------------------------------------------
    # A layout option: [V/8, 128] super-rows (8 rows x 16 lanes).
    # Physical stream ~1.8x logical (16/9) with a dense 128-lane minor
    # dim — vs ~14x for lane-padded [V, 9].  Costs two extra lane-spread
    # matmuls per subtile; whether that trade wins is exactly what this
    # times against production's row-major K2.
    d9 = 9
    gk = jax.device_put(
        jnp.asarray(rng.uniform(-1e-2, 1e-2, (N, d9)), jnp.float32))
    tbl = jax.device_put(
        jnp.asarray(rng.uniform(-0.1, 0.1, (V, d9)), jnp.float32))
    accv = jnp.full((V, d9), 0.1, jnp.float32)
    k2p = jax.jit(_partial(k2p_apply, lr=0.05, eps=1e-7))
    try:
        if jax.default_backend() == "cpu":
            vs, ns = 4096, 2048
            tbs = jnp.asarray(rng.uniform(-0.1, 0.1, (vs, d9)), jnp.float32)
            acs = jnp.full((vs, d9), 0.1, jnp.float32)
            idss = jnp.asarray(rng.integers(0, vs, (ns,)), jnp.int32)
            gs = jnp.asarray(
                rng.uniform(-1e-2, 1e-2, (ns, d9)), jnp.float32)
            t_p, a_p = k2p(
                pack_table(tbs, d9), pack_table(acs, d9), idss, gs)
            a_ref3 = acs.at[idss].add(gs * gs)
            t_ref3 = tbs.at[idss].add(
                -0.05 * gs * jax.lax.rsqrt(a_ref3[idss] + 1e-7))
            errp = float(jnp.max(jnp.abs(unpack_table(t_p, d9) - t_ref3)))
            print(f"  K2-packed parity err {errp:.2e} (interpret, "
                  f"V={vs} n={ns})", flush=True)
        else:
            tp, ap = pack_table(tbl, d9), pack_table(accv, d9)
            t_p, a_p = k2p(tp, ap, ids, gk)
            a_ref3 = accv.at[ids].add(gk * gk)
            t_ref3 = tbl.at[ids].add(
                -0.05 * gk * jax.lax.rsqrt(a_ref3[ids] + 1e-7))
            errp = float(jnp.max(jnp.abs(unpack_table(t_p, d9) - t_ref3)))
            ms_pk = bench(k2p, tp, ap, ids, gk)
            print(
                f"  K2 packed [V/8,128]: {ms_pk:7.3f} ms (parity err "
                f"{errp:.2e}); compare the production K2",
                flush=True)
        del t_p, a_p
    except Exception as exc:  # noqa: BLE001 — a probe must not die here
        print(f"  K2-packed probe FAILED: {type(exc).__name__}: "
              f"{str(exc).splitlines()[0][:140]}", flush=True)
    del gk, tbl, accv

    # ---- cumsum variants ---------------------------------------------
    flags = jax.device_put(
        jnp.asarray(rng.integers(0, 2, (N,)), jnp.int32))
    t_plain = bench(jax.jit(lambda f: jnp.cumsum(f)), flags)
    t_assoc = bench(
        jax.jit(lambda f: jax.lax.associative_scan(jnp.add, f)), flags)

    def cumsum_blocked(f):
        # [N] -> [rows, 128]; within-row prefix via triangular matmul,
        # across-row offsets via a tiny second cumsum on row sums.
        rows = f.shape[0] // 128
        m = f.reshape(rows, 128).astype(jnp.float32)
        # within[r, c] = sum_{k<=c} m[r, k] needs tri[k, c] = (k <= c),
        # i.e. upper-triangular (tril would give suffix sums).
        tri = jnp.triu(jnp.ones((128, 128), jnp.float32))
        within = jax.lax.dot_general(
            m, tri, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row_tot = within[:, -1]
        offs = jnp.cumsum(row_tot) - row_tot
        return (within + offs[:, None]).reshape(-1).astype(jnp.int32)

    t_block = bench(jax.jit(cumsum_blocked), flags)
    ref = np.cumsum(np.asarray(flags))
    got = np.asarray(jax.jit(cumsum_blocked)(flags))
    ok = bool((ref == got).all())
    print(
        f"  cumsum[{N}]: plain {t_plain:6.3f} ms  assoc {t_assoc:6.3f} ms"
        f"  blocked-matmul {t_block:6.3f} ms (exact={ok})", flush=True)

    # ---- sort scaling -------------------------------------------------
    iota = jnp.arange(N, dtype=jnp.int32)
    for n in (N // 8, N // 2, N):
        sub = ids[:n]
        t_kv = bench(
            jax.jit(lambda i: jax.lax.sort_key_val(i, iota[: i.shape[0]])),
            sub)
        packed = (sub.astype(jnp.int64) << 20) | iota[:n].astype(jnp.int64)
        t_pk = bench(jax.jit(lambda p: jnp.sort(p)), packed)
        t_1 = bench(jax.jit(lambda i: jnp.sort(i)), sub)
        print(
            f"  sort n={n:7d}: key_val(i32,i32) {t_kv:7.3f} ms   "
            f"packed-i64 {t_pk:7.3f} ms   keys-only {t_1:7.3f} ms",
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
