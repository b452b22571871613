"""Sparse optimizer apply on the TPU: sort + dedup, then a unique-row
write (a transposed tile stream or a scatter loop) or the tile kernels.

The reference applies sparse updates with TF's SparseApplyAdagrad/-Ftrl over
``IndexedSlices`` (SURVEY.md §2 #8, §3.2): per step it updates only the rows
the batch touched.  The direct XLA translation (``table.at[ids].add`` per
occurrence) is correct but slow on TPU: the scatter writes rows one after the
other, ~0.1 us each on v5e whatever the data (640k occurrences: 73 ms;
2.56 M: 262 ms), and sparse Adagrad needed two such scatters and a re-gather
of the accumulator, each over every occurrence.

The replacements here start from the same prep (``_prep``: sort the
occurrence ids, K1 sums ``g`` and ``g^2`` per unique row):

* ``scatter_apply_unique`` — the ``scatter`` apply mode on one device,
  into the un-padded ``[V, D]`` tables, at any V the chip holds.  Which
  of its two writers moves the unique rows is decided from static shapes
  (``stream_wins``; gauge ``train.apply_stream``):

  - the **stream** (``_stream_call``): every table whole through VMEM
    once, as ``table.T`` — how ``[V, D]`` rests on the TPU, so no copy —
    in blocks of TILE * group rows, the block's unique entries placed by
    float32-exact one-hot matmuls.  Cost follows V * D: 29–30 ms in both
    train cells of the benchmark (PERF.md §5), where the kernels run
    compiled and V is whole subtiles;
  - the **scatter loop**: gathers, updates and scatters the unique rows
    with XLA's scatter, in chunks, as many as the batch's unique rows
    need.  Cost follows the unique rows, 11–20 ns an element: a small
    batch over a huge table, a vocabulary that is not whole subtiles,
    and every run whose kernels would be interpreted.
* the tile kernels — the ``tile`` / ``sharded`` apply modes, which need
  a TILE-aligned vocabulary and today a table small enough to be copied
  into the kernels' row-major layout (PERF.md §4).

The tile path replaces the scatter with a sort + two Pallas kernels, turning
the random-access scatter into sequential streams and MXU matmuls:

1. XLA prep: sort occurrence ids (carrying a permutation), mark segment
   starts, prefix-sum to get each occurrence's *unique-row position* (upos).
2. ``K1`` (dedup): grid over chunks of C sorted occurrences.  A one-hot
   [C, C] matmul segment-sums each chunk's payload ``(g, g², lrow·last)``
   per unique id; a VMEM carry accumulates segments that span chunk
   boundaries (hot features can span many chunks); each chunk DMAs its
   window of unique rows to HBM at dynamic offset upos_start — last writer
   per row holds the complete sum.
3. ``K2`` (apply): grid over table tiles of R rows.  Streams the table (and
   optimizer-state tables) tile by tile, DMAs in the ≤R unique entries that
   land in the tile (a tile of R rows can hold at most R unique ids — the
   bound that makes the window exact), places them with a one-hot [R, R]
   matmul, and applies the optimizer formula on the whole tile in VPU.

Per step this costs one pass over the table (streaming) plus the MXU
placement matmuls, independent of duplicate structure.  No benchmark
cell runs it yet, so its speed against the unique-row scatter is not
measured (PERF.md §4 and §7 row 1: blocked on the table copy).  The
one-hot matmuls run as two-pass bf16 hi/lo splits: a product keeps 16
bits (a value that occurs once is off by up to 2^-17 of itself; sums of
many occurrences average it out).  The unique-row scatter asks K1 for
three passes, which keep all 24: its cell is stated as float32, and its
tile-index column must come back exact at any vocabulary (two passes
carry 17 bits of an integer: vocab <= 2^25 at TILE = 256; PERF.md §6,
PR 27).

Semantics match train.sparse exactly: per-occurrence g² accumulation,
shared post-update denominator for duplicates (Adagrad), single -sigma*w
correction per row (FTRL).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block sizes.  Only CHUNK and TILE must themselves be multiples of 8
# (sublanes).  GROUP is a plain loop trip count; K1_GROUP does scale a
# tiled dimension ([CHUNK*K1_GROUP, lanes] payload blocks — see its
# comment below) but needs no own multiple because CHUNK keeps the
# product sublane-aligned.  TILE additionally gates supports_tile's
# vocab-divisibility check.
CHUNK = 512
TILE = 256
# Subtiles processed per K2/K-place grid step.  On real v5e the first
# hardware sweep showed per-grid-step overhead (~2-3us: DMA latency not
# overlapped, step bookkeeping) dominating the apply at V/TILE = 16k
# steps; grouping G subtiles per step with double-buffered window DMAs
# divides that overhead by G while keeping the placement matmul at the
# MXU-optimal [TILE, TILE] shape.  Any positive count works (it is a
# loop trip count, not a tiled dimension); VMEM for the table blocks
# grows linearly with it.
GROUP = 8
# Chunks per K1 grid step.  Same grid-overhead motivation, but K1's
# grouping IS a tiled dimension (the payload input block becomes
# [CHUNK*K1_GROUP, lanes], so pipelined VMEM grows with it), and its
# output DMA pipelines differently (one in-flight copy, ordered: see
# _k1_kernel) — hence a constant independent of the K2 one.
K1_GROUP = 8


def ftrl_solve(z, n, lr, l1, l2, beta):
    """FTRL-proximal closed form — the ONE copy all paths share.

    Used by the scatter path (train.sparse), the K2 tile kernel, and the
    sharded elementwise update; tile/scatter parity tests assume these stay
    bit-identical.
    """
    denom = (beta + jnp.sqrt(n)) / lr + l2
    return jnp.where(
        jnp.abs(z) <= l1, jnp.zeros_like(z), -(z - jnp.sign(z) * l1) / denom
    )


# Dense-delta optimizer updates: (sum g, sum g^2, *state tables) -> new
# tables.  The ONE elementwise copy shared by the shard_map wrappers below
# and train.shardmap_step (the K2 kernels fuse the same formulas in-kernel,
# via the shared ftrl_solve).
def adagrad_update(g1, g2, table, acc, *, lr, eps):
    acc_new = acc + g2
    return table - lr * g1 * jax.lax.rsqrt(acc_new + eps), acc_new


def ftrl_update(g1, g2, table, z, n, *, lr, l1, l2, beta):
    n_new = n + g2
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / lr
    z_new = z + g1 - sigma * table
    return ftrl_solve(z_new, n_new, lr, l1, l2, beta), z_new, n_new


def sgd_update(g1, g2, table, *, lr):
    del g2
    return (table - lr * g1,)


from fast_tffm_tpu.platform import use_interpret as _use_interpret


def supports_tile(vocab: int, optimizer: str) -> bool:
    return vocab % TILE == 0 and vocab >= TILE and optimizer in (
        "adagrad", "ftrl", "sgd",
    )


# ---------------------------------------------------------------- K1: dedup


def _k1_kernel(starts_ref, firsts_ref, ends_ref, payload_ref, upos_ref,
               out_ref, u_vmem, carry_ref, sem, *, chunk, group, lanes,
               passes):
    t = pl.program_id(0)
    prev_cp = None  # the single in-flight output copy
    for j in range(group):  # unrolled: all slices static
        cj = t * group + j  # global chunk index (scalar arrays use it)
        upos_s = starts_ref[cj]
        rows = pl.ds(j * chunk, chunk)
        payload = payload_ref[rows, :]  # [C, L] f32
        # [1, C] local segment index, in [0, C)
        l = upos_ref[0:1, pl.ds(j * chunk, chunk)] - upos_s
        # onehotT[s, i] = (l[i] == s): segment s on sublanes, occurrence
        # i on lanes — built directly in the orientation the matmul
        # wants.
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        oh = (
            jnp.broadcast_to(l, (chunk, chunk)) == s_iota
        ).astype(jnp.bfloat16)
        # Segment-sum on the MXU.  The f32 payload goes through as
        # ``passes`` bf16 terms, each rounding what the ones before left
        # over, all accumulated in f32: two keep 16 bits of a value
        # (17 of an integer), three all 24, so that a row that occurs
        # once comes out bit for bit.
        u_local, rest = None, payload
        for _ in range(passes):
            term = rest.astype(jnp.bfloat16)
            rest = rest - term.astype(jnp.float32)
            part = jax.lax.dot(oh, term, preferred_element_type=jnp.float32)
            u_local = part if u_local is None else u_local + part  # [C, L]
        # Segment spanning in from the previous chunk: add its partial
        # sums to row 0 via an iota mask — `.at[0:1].add` would emit a
        # scatter-add HLO, which Mosaic has no TPU lowering for
        # (tests/test_tpu_lowering.py).
        continues = (firsts_ref[cj] == 0) & (cj > 0)
        row0 = jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 0) == 0
        u_local = u_local + jnp.where(
            row0 & continues,
            jnp.broadcast_to(carry_ref[0:1, :], (chunk, lanes)),
            0.0,
        )
        # Segment spanning out into the next chunk: move it to the carry
        # and write a zero — the chunk holding the segment's last
        # occurrence is the last writer of that row and will hold the
        # complete sum.  Row l_last is selected with an iota mask:
        # value-level dynamic_slice / dynamic_update_slice have no
        # Mosaic lowering either (same class as the scatter-add above).
        l_last = ends_ref[cj] - upos_s
        cont_next = firsts_ref[cj + 1] == 0
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 0)
        is_last = r_iota == l_last
        last_row = jnp.sum(
            jnp.where(is_last, u_local, 0.0), axis=0, keepdims=True
        )  # [1, lanes] == u_local[l_last]
        carry_ref[...] = jnp.broadcast_to(
            jnp.where(cont_next, last_row, 0.0), (8, lanes)
        )
        # If the segment continues, zero its row here; otherwise leave it
        # (writing last_row back to its own row would be a no-op).
        u_local = jnp.where(is_last & cont_next, 0.0, u_local)
        # Output windows of consecutive chunks OVERLAP whenever a chunk
        # holds duplicates (upos advances by its unique count < chunk),
        # and correctness rests on the later chunk's rows landing last —
        # so at most ONE copy may be in flight.  Waiting for chunk j-1's
        # copy only HERE (after this chunk's matmul) still hides the DMA
        # behind the compute; the single buffer is safe to overwrite
        # because nothing is in flight after the wait.
        if prev_cp is not None:
            prev_cp.wait()
        u_vmem[...] = u_local
        prev_cp = pltpu.make_async_copy(
            u_vmem, out_ref.at[pl.ds(upos_s, chunk)], sem
        )
        prev_cp.start()
    # Drain before returning: the next grid step (or pallas epilogue)
    # must not race the final window's write.
    prev_cp.wait()


def _k1_tiles(payload, upos, starts, firsts, ends, n_out, passes=2):
    """K1 over a payload of any width: one call a 128-lane tile, the
    outputs as a list.  The output windows land at dynamic row offsets,
    and only a [rows, 128] float32 array is row-contiguous in HBM's
    (8, 128) tiling: a wider one keeps a row's lane tiles apart, and
    Mosaic refuses the unaligned window ("Failed to prove that a tile
    index in dimension 0 is divisible by the tiling (8)": field-aware
    FM's 2 * 157 + 2 payload columns, three tiles).  The slices are
    whole tiles, so they move no lane.  The stream writer windows the
    tiles as they are (the same refusal meets a read)."""
    return [
        _k1_dedup(payload[:, j:j + 128], upos, starts, firsts, ends,
                  n_out, passes)
        for j in range(0, payload.shape[1], 128)
    ]


def _k1_dedup(payload, upos, starts, firsts, ends, n_out, passes=2):
    n, lanes = payload.shape
    if lanes > 128:
        # Whole tiles side by side again: moves no lane either.
        return jnp.concatenate(
            _k1_tiles(payload, upos, starts, firsts, ends, n_out, passes),
            axis=1,
        )
    chunk = CHUNK
    group = _group_for(n // chunk, K1_GROUP)
    block = chunk * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, lanes), lambda j, *_: (j, 0)),
            pl.BlockSpec((1, block), lambda j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((chunk, lanes), jnp.float32),
            pltpu.VMEM((8, lanes), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _k1_kernel, chunk=chunk, group=group, lanes=lanes,
            passes=passes,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, lanes), jnp.float32),
        interpret=_use_interpret(),
    )(starts, firsts, ends, payload, upos.reshape(1, n))


# ---------------------------------------------------------------- K2: apply


def _placed_sums(u, cnt, d, tile):
    """Window entries -> dense per-row sums [R, D] x2 via one-hot matmul."""
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    mask = e_iota < cnt  # [R, 1] valid-entry mask
    # The window tail belongs to later tiles (or is uninitialized); zero it
    # with where() — a multiply would keep NaN garbage (NaN*0 == NaN).
    u = jnp.where(mask, u, 0.0)  # [R, L]
    # Tile-local row as int32 for the iota compare: tpu.iota is
    # integer-only (a f32 iota fails Mosaic verification).  The f32 value
    # is exact for any TILE < 2^24 (f32 integers are exact below that),
    # so the cast is too.
    lrow = u[:, 2 * d:2 * d + 1].astype(jnp.int32)  # [R, 1] tile-local row
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    p = ((lrow == r_iota) & mask).astype(jnp.bfloat16)  # [entry, row]
    u_hi = u.astype(jnp.bfloat16)
    u_lo = (u - u_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dn = (((0,), (0,)), ((), ()))  # contract the entry dim of both
    dense = (
        jax.lax.dot_general(p, u_hi, dn, preferred_element_type=jnp.float32)
        + jax.lax.dot_general(p, u_lo, dn, preferred_element_type=jnp.float32)
    )  # [row, L]
    return dense[:, :d], dense[:, d:2 * d]  # sum(g), sum(g^2) per row


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _group_for(n_tiles: int, want: int | None = None) -> int:
    """Largest group <= want (default GROUP) dividing the tile count."""
    group = max(1, min(GROUP if want is None else want, n_tiles))
    while n_tiles % group:
        group -= 1
    return group


def _window_loop_raw(ts_ref, u_hbm_ref, u_vmem, sem, *, tile, group, body,
                     base=None):
    """Double-buffered entry-window loop — the ONE copy of the
    slot/semaphore rotation protocol (the packed-layout prototype in
    tools/micro_probe.py reuses it too; keep it that way).

    Walks ``group`` subtiles, DMA-ing each one's entry window while the
    previous subtile's compute runs (subtile j+1's copy is in flight
    during subtile j's compute), and calls ``body(j, u_window, cnt)``.
    ``base`` is the first subtile's global index (defaults to the grid
    position; the compact K2 variant passes the remapped group index).
    """
    if base is None:
        base = pl.program_id(0) * group

    def window(j, slot):
        start = ts_ref[base + j]
        return pltpu.make_async_copy(
            u_hbm_ref.at[pl.ds(start, tile)], u_vmem.at[slot], sem.at[slot]
        )

    window(0, 0).start()
    for j in range(group):  # unrolled: all slices static
        slot = j % 2
        if j + 1 < group:
            window(j + 1, (j + 1) % 2).start()
        window(j, slot).wait()
        cnt = ts_ref[base + j + 1] - ts_ref[base + j]
        body(j, u_vmem[slot], cnt)


def _window_loop(ts_ref, u_hbm_ref, u_vmem, sem, *, tile, group, d, body,
                 base=None):
    """_window_loop_raw + the standard [R, R] one-hot placement:
    ``body(j, g1, g2)`` receives the placed per-row sums."""

    def raw_body(j, u, cnt):
        g1, g2 = _placed_sums(u, cnt, d, tile)
        body(j, g1, g2)

    _window_loop_raw(
        ts_ref, u_hbm_ref, u_vmem, sem, tile=tile, group=group,
        body=raw_body, base=base,
    )


def _k2_group_kernel(ts_ref, *args, n_tables, tile, group, d, update):
    """Generic K2 body: a group of subtiles per grid step.

    ``update(g1, g2, *table_slices) -> new_table_slices`` is one of the
    shared elementwise optimizer formulas (adagrad_update/...).
    """
    _k2_body(ts_ref, None, args, n_tables, tile, group, d, update)


def _k2_group_kernel_compact(ts_ref, cg_ref, *args, n_tables, tile, group,
                             d, update):
    """Compact K2 body: grid step t works on group ``cg_ref[t]`` instead
    of group t — the BlockSpec index_maps use the same remapping, so the
    table blocks arriving in VMEM match the entry windows."""
    _k2_body(
        ts_ref, cg_ref[pl.program_id(0)] * group, args, n_tables, tile,
        group, d, update,
    )


def _k2_body(ts_ref, base, args, n_tables, tile, group, d, update):
    ins = args[:n_tables]
    u_hbm_ref = args[n_tables]
    outs = args[n_tables + 1:2 * n_tables + 1]
    u_vmem, sem = args[2 * n_tables + 1:]

    def body(j, g1, g2):
        rows = pl.ds(j * tile, tile)
        new = update(g1, g2, *(r[rows, :] for r in ins))
        for out_ref, val in zip(outs, new):
            out_ref[rows, :] = val

    _window_loop(
        ts_ref, u_hbm_ref, u_vmem, sem, tile=tile, group=group, d=d,
        body=body, base=base,
    )


def _compact_auto(n_entries: int, n_groups: int) -> bool:
    """Auto-engage compact K2 only when the entry count bounds touched
    groups to <= half the table's groups — streaming the whole table is
    faster when most blocks are touched anyway (no remap indirection,
    denser pipelining)."""
    return 2 * min(n_entries, n_groups) <= n_groups


def _compact_groups(tile_start, n_groups, group, t_max):
    """Indices of the touched tile-groups, padded to static length t_max.

    ``comp[j]`` is the group index the j-th grid step should process:
    the j-th touched group for j < touched-count, then (padding) the
    FIRST UNTOUCHED group.  The filler must be untouched — revisiting a
    touched group would re-apply its update — and identical across all
    filler steps (consecutive same-block revisits are the pipeline
    pattern BlockSpecs handle); an untouched group's update is the
    identity, so rewriting it any number of times is safe.  When every
    group is touched (only possible when t_max == n_groups) there are no
    filler steps, so the clamped fallback index is never used.
    """
    ts_g = tile_start[::group]  # [n_groups + 1] entry offsets per group
    touched = (ts_g[1:] > ts_g[:-1]).astype(jnp.int32)
    c = _cumsum_counts(touched)  # inclusive: c[gi] = touched in [0, gi]
    count = c[-1]
    j = jnp.arange(t_max, dtype=jnp.int32)
    comp = jnp.searchsorted(c, jnp.minimum(j + 1, count)).astype(jnp.int32)
    un_c = jnp.arange(1, n_groups + 1, dtype=c.dtype) - c  # untouched cum.
    first_un = jnp.minimum(
        jnp.searchsorted(un_c, 1).astype(jnp.int32), n_groups - 1
    )
    return jnp.where(j < count, comp, first_un)


def _k2_call(update, tile_start, u, tables, lanes, compact=None):
    """Stream ``tables`` (tuple) through the grouped K2 apply kernel.

    ``compact``: None = static auto-decision, True/False = force.  The
    compact variant visits only tile-groups the entry stream touches
    (via a scalar-prefetched group list driving the BlockSpec index
    maps); unvisited blocks are never fetched or written — their rows
    survive through the input/output aliasing.  HBM streaming then
    scales with min(touched groups, V/block) instead of V — the
    IndexedSlices property (SURVEY.md §3.2) for the apply's table
    traffic.  Only engaged when the entry count bounds touched groups
    to <= half the table (streaming the whole table is faster when most
    blocks are touched anyway).
    """
    v, d = tables[0].shape
    tile = TILE
    group = _group_for(v // tile)
    n_arrays = len(tables)
    block = tile * group
    n_groups = v // block
    n_entries = u.shape[0] - tile  # stream length minus window slack
    t_max = min(n_groups, n_entries)
    if compact is None:
        compact = _compact_auto(n_entries, n_groups)
    if compact:
        comp = _compact_groups(tile_start, n_groups, group, t_max)
        grid = (t_max,)
        num_prefetch = 2
        # index_map args: (grid idx, tile_start ref, compact ref).
        block_index = lambda t, ts, cg: (cg[t], 0)  # noqa: E731
        kernel = functools.partial(
            _k2_group_kernel_compact, n_tables=n_arrays, tile=tile,
            group=group, d=d, update=update,
        )
        prefetch_args = (tile_start, comp)
    else:
        grid = (n_groups,)
        num_prefetch = 1
        block_index = lambda t, *_: (t, 0)  # noqa: E731
        kernel = functools.partial(
            _k2_group_kernel, n_tables=n_arrays, tile=tile, group=group,
            d=d, update=update,
        )
        prefetch_args = (tile_start,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=grid,
        in_specs=[pl.BlockSpec((block, d), block_index)] * n_arrays
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((block, d), block_index)] * n_arrays,
        scratch_shapes=[
            pltpu.VMEM((2, tile, lanes), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((v, d), jnp.float32) for _ in range(n_arrays)
        ],
        input_output_aliases={
            num_prefetch + i: i for i in range(n_arrays)
        },
        interpret=_use_interpret(),
    )(*prefetch_args, *tables, u)


# ------------------------------------------------- K-place: dense expansion


def _kplace_kernel(ts_ref, u_hbm_ref, out_ref, u_vmem, sem,
                   *, tile, group, d):
    """Expand the unique-entry stream into dense [R, 2D] delta blocks."""

    def body(j, g1, g2):
        out_ref[pl.ds(j * tile, tile), :] = jnp.concatenate(
            [g1, g2], axis=-1
        )

    _window_loop(
        ts_ref, u_hbm_ref, u_vmem, sem, tile=tile, group=group, d=d,
        body=body,
    )


def _kplace_call(tile_start, u, vocab_local, d, lanes):
    tile = TILE
    group = _group_for(vocab_local // tile)
    block = tile * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(vocab_local // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, 2 * d), lambda t, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, lanes), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kplace_kernel, tile=tile, group=group, d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vocab_local, 2 * d), jnp.float32),
        interpret=_use_interpret(),
    )(tile_start, u)


def dense_delta(ids, g_rows, *, vocab, vocab_local, row_lo):
    """Per-shard dense (sum g, sum g^2) delta [vocab_local, 2D].

    ``row_lo`` (traced OK) is the first global row of the local table
    shard; only occurrences landing in [row_lo, row_lo + vocab_local)
    contribute.  This is the sharded-tile building block: shard_map runs it
    per device on the device's data shard, psums the result over the data
    axis, and applies the optimizer formula elementwise.
    """
    d = g_rows.shape[1]
    payload, upos, starts, firsts, ends, sidx, n_pad = _prep(
        ids, g_rows, vocab
    )
    u = _k1_dedup(payload, upos, starts, firsts, ends, n_pad + TILE)
    tile_start = _tile_starts(
        sidx, upos, row_lo + jnp.arange(0, vocab_local + 1, TILE,
                                        dtype=sidx.dtype)
    )
    return _kplace_call(tile_start, u, vocab_local, d, u.shape[1])


# ------------------------------------------- entries exchange (sharded path)


def resolve_exchange(mode: str, *, n_local_occ: int, vocab_local: int,
                     d: int, data_shards: int) -> str:
    """Resolve a sparse_exchange config value for static shapes.

    "dense" psums a [vocab_local, 2D] delta over the data axis — bytes
    grow with vocab, independent of the batch.  "entries" all-gathers
    the deduped touched-row streams — bytes grow with the batch,
    independent of vocab (the reference PS design's IndexedSlices
    scaling, SURVEY.md §3.2).  "auto" picks whichever moves fewer
    words per device over a ring:

      entries  S-shard all-gather of cap*(2D+1) words:
               (S-1) * cap * (2D+1) per device,
      dense    ring all-reduce of vocab_local*2D words (reduce-scatter
               + all-gather phases): 2 * vocab_local*2D * (S-1)/S.

    Dropping the common (S-1) factor gives the comparison below; the
    dense side carries the all-reduce's 2x buffer traffic (ADVICE r5 —
    the unweighted comparison was ~2x biased toward 'dense' and could
    pick the slower exchange near the crossover).
    """
    if mode != "auto":
        return mode
    if data_shards == 1:
        # Nothing to exchange either way; entries' fast path is then the
        # plain single-device K1+K2 apply, strictly less work than
        # materializing and elementwise-applying a dense delta.
        return "entries"
    cap = entries_cap(n_local_occ, vocab_local)
    entries_words = data_shards * cap * (2 * d + 1)
    dense_words = 2 * vocab_local * 2 * d
    return "entries" if entries_words < dense_words else "dense"


def entries_cap(n_occurrences: int, vocab: int) -> int:
    """Static per-shard entry-stream capacity for the entries exchange.

    Exact worst case — unique touched rows can't exceed the occurrence
    count (CHUNK-padded, the stream's real-entry bound) or the vocab
    range (CHUNK-rounded so the merged stream stays CHUNK-divisible).
    Always-correct by construction: no overflow path exists.
    """
    n_pad = -(-n_occurrences // CHUNK) * CHUNK
    return min(n_pad, -(-vocab // CHUNK) * CHUNK)


def unique_entries(ids, g_rows, *, vocab, cap, segment_sums=_k1_dedup,
                   pad_first=False):
    """Deduped touched-row entry stream: (rows [cap] i32, pay [cap, 2D]
    f32, count).

    The batch-proportional half of the reference's IndexedSlices push
    (SURVEY.md §3.2): instead of a dense [vocab, 2D] delta, emit only
    the rows the batch touched — sorted, deduped (sum g / sum g² per
    row), sentinel-padded (row == vocab, zero payload) to the static
    ``cap``.  Rows are recovered from the K1 stream's lrow/tidx
    metadata columns (integer-valued f32 — see _prep; exact through
    K1's default two passes while vocab / TILE <= 2^17, through three at
    any vocabulary).  ``segment_sums`` stands in for K1 (same signature
    as _k1_dedup); ``pad_first`` as in _prep.
    """
    d = g_rows.shape[1]
    payload, upos, starts, firsts, ends, sidx, n_pad = _prep(
        ids, g_rows, vocab, pad_first
    )
    if cap > n_pad:
        raise ValueError(f"cap={cap} exceeds padded occurrences {n_pad}")
    u = segment_sums(payload, upos, starts, firsts, ends, n_pad + TILE)
    count = _tile_starts(
        sidx, upos, jnp.full((1,), vocab, sidx.dtype)
    )[0]  # uniques among real (non-sentinel) rows
    valid = jnp.arange(cap, dtype=jnp.int32) < count
    lrow = u[:cap, 2 * d].astype(jnp.int32)
    tidx = u[:cap, 2 * d + 1].astype(jnp.int32)
    rows = jnp.where(valid, tidx * TILE + lrow, vocab)
    pay = jnp.where(valid[:, None], u[:cap, :2 * d], 0.0)
    return rows, pay, count


# ----------------------------------------- unique-row scatter (one device)

# Entries per trip of scatter_apply_unique's loop.  A trip's fixed cost
# does not show on v5e (2,048 to 32,768 entries a trip read within 2%
# of each other), but an out-of-range row costs what a real one does, so
# the last trip's padding is paid in full: 65,536 read 16 ms slower than
# 4,096 at 806k unique rows (PERF.md §6, PR 27).
SCATTER_CHUNK = 4096


# bf16 terms K1 splits a float32 into for the unique-row scatter: three
# carry all 24 bits, so single-occurrence rows come out bit for bit and
# the tile-index column is exact for any int32 vocabulary.  Two (K1's
# default, what the tile path runs) lose the low 8 bits of a product and
# recover wrong rows once vocab / TILE passes 2^17 (tested).
_EXACT_PASSES = 3


def _k1_exact(payload, upos, starts, firsts, ends, n_out):
    """K1 in _EXACT_PASSES (``segment_sums``' signature)."""
    return _k1_dedup(payload, upos, starts, firsts, ends, n_out,
                     _EXACT_PASSES)


def _xla_segment_sums(payload, upos, starts, firsts, ends, n_out):
    """K1's stand-in where Pallas kernels run interpreted (off the
    TPU): the same per-segment sums of the sorted payload by XLA's
    sorted segment_sum (plain f32 adds).  An interpreted kernel is a
    correctness tool, orders of magnitude slower; on the TPU this
    scatter-add costs 311 ms where K1 costs 7.5 (two passes) or 9.5
    (three; n = 2.56 M; PERF.md §6, PR 27)."""
    del starts, firsts, ends  # K1's chunk-boundary scalars
    return jax.ops.segment_sum(
        payload, upos, num_segments=n_out, indices_are_sorted=True
    )


# --------------------------------- the two writers of the one-device apply
#
# The tables do not rest row-major on the TPU: the compiler lays
# f32[V, D] out {0,1:T(8,128)} — V on the 128 lanes, a row's floats on
# sublanes — so XLA's scatter writes a "row" as D separate elements
# (11 ns an element at D = 9, 20 ns at D = 157; PERF.md §5, PR 27 / 33)
# and a row-major kernel costs whole-table copies there and back.  Read
# the other way round that layout IS f32[D, V]{1,0:T(8,128)}, Mosaic's
# default: a kernel handed ``table.T`` gets it without a copy, and a
# [D, TILE * group] block of it is dense on the lanes.  So the apply has
# two ways to move the same bytes, one algorithm and one update formula:
#
# * the scatter loop (scatter_apply_unique's second half): cost follows
#   the batch's unique rows;
# * the stream (_stream_call): every table whole through VMEM, once,
#   the unique entries placed by one-hot matmuls: cost follows V.
#
# stream_wins() chooses from static shapes.


# Entries a trip of the stream writer places: one 128 x 128 transpose a
# payload lane tile, and a one-hot whose contraction fills a 128-deep
# MXU pass.  A grid step's TILE * group rows hold ~50 unique entries in
# both train cells (PERF.md §6, PR 34), so one trip a step is the rule
# and the counted loop is for the fuller block.
STREAM_WINDOW = 128


def _placed_sums_t(u, cnt, first_tile, d, block):
    """Window entries -> the block's dense per-row sums, TRANSPOSED
    ([2 D to 16 sublanes, block]: rows 0..D-1 sum g, D..2D-1 sum g^2),
    and how many entries hit each row ([1, block]).

    ``u`` holds the window of each 128-lane stream, [streams, E, 128]
    (the payload columns [g | g^2 | lrow | tidx | 0...] cut into lane
    tiles); its first ``cnt`` entries belong to this block, whose first
    TILE-row subtile is ``first_tile``.  ``dense_t[L, R] = u^T[L, E] .
    onehot[E, R]`` with u as three bf16 terms, each rounding what the
    ones before left over: an output column has ONE non-zero product a
    term, so the three partial results add up to the float32 bit for
    bit (as _EXACT_PASSES asks of K1; two terms would be a different
    result).
    """
    window = u.shape[1]

    def column(c):  # a metadata column as int32 [E, 1]: tpu.iota is
        part, lane = divmod(c, 128)  # integer-only, the f32 an exact int
        return u[part][:, lane:lane + 1].astype(jnp.int32)

    # The window's tail belongs to later blocks or was never written:
    # whatever it converts to is masked.
    row = (column(2 * d + 1) - first_tile) * TILE + column(2 * d)
    e_col = jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (window, block), 1)
    match = (row == r_iota) & (e_col < cnt)  # [entry, row of the block]
    hits = jnp.sum(match.astype(jnp.float32), axis=0, keepdims=True)
    p = match.astype(jnp.bfloat16)
    # u^T, only the 2 D rows that hold sums (to whole bf16 sublane tiles).
    ut = jnp.concatenate([
        u[k].T[:min(128, _round_up(2 * d - 128 * k, 16))]
        for k in range(-(-2 * d // 128))
    ], axis=0)  # [L, E]
    e_row = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
    # where(), not a multiply: the tail may hold NaN garbage.
    rest = jnp.where(e_row < cnt, ut, 0.0)
    terms = []
    for _ in range(_EXACT_PASSES):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    lp = ut.shape[0]
    if _EXACT_PASSES * lp <= 128:
        # A narrow row: the one-hot is loaded into the MXU once for the
        # three terms stacked on the rows, not three times.
        placed = jax.lax.dot(
            jnp.concatenate(terms, axis=0), p,
            preferred_element_type=jnp.float32)
        parts = [placed[i * lp:(i + 1) * lp] for i in range(_EXACT_PASSES)]
    else:
        parts = [jax.lax.dot(t, p, preferred_element_type=jnp.float32)
                 for t in terms]
    dense = parts[0]
    for part in parts[1:]:
        dense = dense + part
    return dense, hits


def _stream_kernel(gs_ref, *args, n_tables, n_streams, tile, group, d,
                   update, additive):
    """One grid step of the stream writer: a block of TILE * group rows
    of every (transposed) table.  The block's unique entries, stream
    positions ``gs_ref[t] .. gs_ref[t + 1]``, are placed STREAM_WINDOW
    at a time (double-buffered DMA windows) into a VMEM accumulator;
    then the update runs over the block a subtile at a time.  ``update``
    and ``additive`` as scatter_apply_unique takes them (the weights'
    new value is the old one PLUS what ``update`` makes of zeros: the
    scatter writer's arithmetic operation for operation); a row the
    batch did not touch keeps its bits whatever ``update`` would make of
    zero sums (FTRL's recompute is NOT leaned on)."""
    ins = args[:n_tables]
    u_hbm_refs = args[n_tables:n_tables + n_streams]
    outs = args[n_tables + n_streams:2 * n_tables + n_streams]
    u_vmem, sem, sums_ref, hits_ref = args[2 * n_tables + n_streams:]
    t = pl.program_id(0)
    start, end = gs_ref[t], gs_ref[t + 1]
    window, block = STREAM_WINDOW, tile * group
    trips = (end - start + window - 1) // window

    def copies(k, slot):
        return [
            pltpu.make_async_copy(
                ref.at[pl.ds(start + k * window, window)],
                u_vmem.at[slot, s], sem.at[slot, s],
            )
            for s, ref in enumerate(u_hbm_refs)
        ]

    @pl.when(trips > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    sums_ref[...] = jnp.zeros_like(sums_ref)
    hits_ref[...] = jnp.zeros_like(hits_ref)

    def trip(k, carry):
        slot = k % 2

        @pl.when(k + 1 < trips)
        def _():
            for cp in copies(k + 1, 1 - slot):
                cp.start()

        for cp in copies(k, slot):
            cp.wait()
        dense, hits = _placed_sums_t(
            u_vmem[slot], end - start - k * window, t * group, d, block)
        sums_ref[...] += dense
        hits_ref[...] += hits
        return carry

    jax.lax.fori_loop(0, trips, trip, 0)
    for j in range(group):  # unrolled: all slices static
        cols = pl.ds(j * tile, tile)
        sums = sums_ref[:, cols]
        g1, g2 = sums[:d], sums[d:2 * d]
        touched = hits_ref[:, cols] > 0.0
        old = [r[:, cols] for r in ins]
        if additive:
            new = update(g1, g2, jnp.zeros_like(old[0]), *old[1:])
            new = (old[0] + new[0],) + tuple(new[1:])
        else:
            new = update(g1, g2, *old)
        for out_ref, val, keep in zip(outs, new, old):
            out_ref[:, cols] = jnp.where(touched, val, keep)


# VMEM the stream writer's table blocks may take: each table has a
# [D to 8 sublanes, TILE * group] float32 block in and one out, double-
# buffered.  Half of the 16 MiB a v5e kernel gets by default; the
# accumulator, the one-hot and the matmul's operands take 3 MiB more at
# D = 157.
_STREAM_BLOCK_BYTES = 8 << 20


# Subtiles a grid step of the stream writer, at most.  FM cell's shape
# on v5e, the write alone (my chip runs, PR 34): 2 -> 85.0 ms, 4 ->
# 55.3, 8 -> 37.6, 16 -> 29.0, 32 -> 32.1 (a block of 8,192 rows holds
# ~197 entries: two trips of STREAM_WINDOW every time).
STREAM_GROUP = 16


def _stream_group(n_tiles, d, n_tables):
    """Subtiles a grid step of the stream writer: as _group_for, within
    STREAM_GROUP and _STREAM_BLOCK_BYTES (16 at D = 9; 4 at D = 157,
    two or three tables)."""
    per_subtile = _round_up(d, 8) * TILE * 4 * 4 * n_tables
    return _group_for(n_tiles, max(1, min(
        STREAM_GROUP, _STREAM_BLOCK_BYTES // per_subtile)))


def _stream_call(update, group_start, u_tiles, tables, additive, group):
    """Stream ``tables`` whole through the transposed apply kernel.

    ``u_tiles``: the unique-entry stream as its 128-lane tiles
    (_k1_tiles), ``group_start`` the first entry of each block of
    TILE * ``group`` rows (_tile_starts).  Each table goes in as ``t.T``
    — a bitcast of how [V, D] rests on the TPU — aliased in place, and
    comes back as ``out.T``: no whole-table copy, no write loop.
    """
    v, d = tables[0].shape
    n_tables, n_streams = len(tables), len(u_tiles)
    block = TILE * group
    spec = pl.BlockSpec((d, block), lambda t, *_: (0, t))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(v // block,),
        in_specs=[spec] * n_tables
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_streams,
        out_specs=[spec] * n_tables,
        scratch_shapes=[
            pltpu.VMEM((2, n_streams, STREAM_WINDOW, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, n_streams)),
            pltpu.VMEM((_round_up(2 * d, 16), block), jnp.float32),
            pltpu.VMEM((1, block), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _stream_kernel, n_tables=n_tables, n_streams=n_streams,
            tile=TILE, group=group, d=d, update=update, additive=additive,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((d, v), t.dtype) for t in tables],
        input_output_aliases={1 + i: i for i in range(n_tables)},
        interpret=_use_interpret(),
    )(group_start, *(t.T for t in tables), *u_tiles)
    return tuple(o.T for o in out)


# The rule's two constants, v5e (my chip runs, PR 34; PERF.md §6).
# The stream: the write alone over the table bytes it reads and writes —
# FM cell's shape (V = 2^25, D = 9, two tables: 8.59e9 B) 29.0 ms =
# 0.0034 ns a byte; FFM cell's (V = 2^22, D = 157: 10.74e9 B) 30.0 ms =
# 0.0028; the slower one.
_STREAM_NS_PER_TABLE_BYTE = 0.0034
# The scatter loop: its time over occurrences x D x tables at the
# cells' 0.31-0.34 unique rows an occurrence — FM cell 179.5 ms /
# (2,555,904 x 9 x 2) = 3.9 ns (11 ns an element written); FFM cell
# 1,442 ms / (638,976 x 157 x 2) = 7.2 ns (20 ns); the cheaper one, so
# that a batch with fewer repeats than Zipf(1.1) only widens the
# stream's lead where it is taken.
_SCATTER_NS_PER_ELEMENT = 3.9


def stream_wins(n_occurrences: int, vocab: int, d: int,
                n_tables: int) -> bool:
    """Which writer the one-device apply takes, from static shapes: True
    = the stream.  The stream reads and writes every table whole,
    ``vocab * (D to 8 sublanes) * 4 B * 2`` a table, whatever the batch;
    the scatter writes the batch's unique rows an element at a time, at
    most one row an occurrence.  Both train cells take the stream (FM:
    29 against 179 ms, FFM: 37 against 783 by this count); 40k
    occurrences over 2^25 rows keep the scatter (2.8 against 29 ms: the
    crossover there is 417k occurrences).  The stream needs whole
    subtiles (vocab % TILE == 0)."""
    if vocab % TILE or vocab < TILE:
        return False
    stream_ns = (
        vocab * _round_up(d, 8) * 4 * 2 * n_tables
        * _STREAM_NS_PER_TABLE_BYTE
    )
    scatter_ns = (
        min(n_occurrences, vocab) * d * n_tables * _SCATTER_NS_PER_ELEMENT
    )
    return stream_ns < scatter_ns


def takes_stream(n_occurrences: int, vocab: int, d: int,
                 n_tables: int) -> bool:
    """What scatter_apply_unique does with ``stream=None``: the rule,
    where the kernels run compiled (interpreted, the stream is a
    correctness tool, as K1 is: its XLA stand-in and the scatter loop
    run instead)."""
    return not _use_interpret() and stream_wins(
        n_occurrences, vocab, d, n_tables)


def scatter_apply_unique(update, tables, ids, g_rows, *, additive=False,
                         stream=None):
    """One-device optimizer apply that writes each touched row ONCE.

    The per-occurrence scatter costs ~0.1 us a row on v5e whatever the
    data (PERF.md §5), and hashed CTR batches repeat ids heavily (69% of
    the occurrences of a Zipf(1.1) Criteo batch are duplicates).  So:
    sort + segment-sum the occurrences into the unique stream
    (:func:`unique_entries`: ``sum g`` and the per-occurrence ``sum g²``
    per row), then write only the unique rows.  ``update(g1, g2,
    *table_rows) -> new_table_rows`` is one of the shared elementwise
    formulas (adagrad_update / ftrl_update / sgd_update), exactly as K2
    takes it.

    ``stream``: which of the two writers above moves the rows — None =
    :func:`stream_wins` decides from the shapes, where the kernels run
    compiled (an interpreted stream is a correctness tool: tests force
    it with True).  Both run the same prep and the same ``update`` and
    agree bit for bit.

    The scatter writer gathers, updates and scatters the unique rows
    with sorted, unique indices.  ``additive``: ``update``'s first
    output is ``tables[0]`` minus a term that does not read it (Adagrad,
    SGD).  The weights are then not gathered: ``update`` sees zeros in
    their place and what it returns is scatter-ADDed, the same float32
    subtraction done by the scatter.  (The stream reads every table
    anyway, and does the same two operations.)

    The unique stream has the static length ``cap`` but only ``count``
    real entries; for the scatter its tail is padded with DISTINCT
    out-of-range rows
    (``vocab, vocab+1, ...`` — so ``unique_indices`` stays true) that
    ``mode="drop"`` never writes.  A dropped row costs what a written
    one does, so the apply walks the stream in SCATTER_CHUNK pieces and
    stops after ``ceil(count / chunk)`` of them: its cost follows the
    batch's unique rows, not ``cap``.  Against the per-occurrence
    apply at n = 2.56 M on v5e: 245 against 584 ms at 0.31 unique rows
    an occurrence, even at ~0.88, and 660 against 583 ms where no id
    repeats (the sort, payload and K1 come on top of as many writes) —
    accepted: hashed CTR batches sit far below, and the caller's
    ``count`` (gauge ``train.apply_unique_frac``) shows where a run is.

    Single-device only (a global sort under GSPMD would all-gather the
    batch, and the partitioner cannot split the Mosaic call).  Returns
    ``(new_tables, count)``.
    """
    vocab, d = tables[0].shape
    if stream is None:
        stream = takes_stream(ids.shape[0], vocab, d, len(tables))
    if stream:
        return _stream_apply_unique(update, tables, ids, g_rows, additive)
    cap = entries_cap(ids.shape[0], vocab)
    if vocab + cap >= 1 << 31:
        raise ValueError(
            f"vocab {vocab} + stream cap {cap} overflows int32 row ids"
        )
    with jax.named_scope("tffm.apply_prep"):  # sort, payload, K1
        rows, pay, count = unique_entries(
            ids, g_rows, vocab=vocab, cap=cap, pad_first=True,
            segment_sums=(
                _xla_segment_sums if _use_interpret() else _k1_exact
            ),
        )
    chunk = min(SCATTER_CHUNK, cap)
    pad = -cap % chunk
    if pad:  # the last trip must not run off the stream
        rows = jnp.pad(rows, (0, pad))
        pay = jnp.pad(pay, ((0, pad), (0, 0)))
    tail = jnp.arange(cap + pad, dtype=jnp.int32) - count
    rows_u = jnp.where(tail < 0, rows, vocab + tail)
    index = dict(indices_are_sorted=True, unique_indices=True)
    writes = ["add" if additive else "set"] + ["set"] * (len(tables) - 1)

    def body(i, tabs):
        r = jax.lax.dynamic_slice_in_dim(rows_u, i * chunk, chunk)
        p = jax.lax.dynamic_slice_in_dim(pay, i * chunk, chunk)
        # A padding row reads a clamped row; what update() makes of it
        # is dropped by the scatter below.
        old = [
            jnp.zeros((chunk, d), t.dtype) if how == "add"
            else t.at[r].get(mode="clip", **index)
            for t, how in zip(tabs, writes)
        ]
        new = update(p[:, :d], p[:, d:], *old)
        return tuple(
            getattr(t.at[r], how)(v, mode="drop", **index)
            for t, v, how in zip(tabs, new, writes)
        )

    trips = (count + chunk - 1) // chunk
    with jax.named_scope("tffm.apply_write"):
        tables = jax.lax.fori_loop(0, trips, body, tuple(tables))
    return tables, count


def _stream_apply_unique(update, tables, ids, g_rows, additive):
    """scatter_apply_unique through the stream writer: the same sort,
    payload and three-pass K1 (its XLA stand-in where interpreted); the
    entries are found per block of TILE * group rows by ``group_start``
    and placed by their lrow / tidx columns, not recovered as rows."""
    vocab, d = tables[0].shape
    with jax.named_scope("tffm.apply_prep"):  # sort, payload, K1
        payload, upos, starts, firsts, ends, sidx, n_pad = _prep(
            ids, g_rows, vocab, pad_first=True
        )
        if _use_interpret():
            u = _xla_segment_sums(
                payload, upos, starts, firsts, ends, n_pad + TILE)
            u_tiles = [u[:, j:j + 128] for j in range(0, u.shape[1], 128)]
        else:
            u_tiles = _k1_tiles(payload, upos, starts, firsts, ends,
                                n_pad + TILE, _EXACT_PASSES)
        group = _stream_group(vocab // TILE, d, len(tables))
        bounds = jnp.arange(0, vocab + 1, TILE * group, dtype=sidx.dtype)
        # the binary search unrolled: the step holds no loop of XLA's
        group_start = _tile_starts(sidx, upos, bounds, "scan_unrolled")
        count = group_start[-1]  # uniques among real (non-sentinel) rows
    with jax.named_scope("tffm.apply_write"):
        tables = _stream_call(
            update, group_start, u_tiles, tables, additive, group)
    return tables, count


def merge_entries(rows, pay, *, vocab):
    """Merge concatenated per-shard entry streams into one K2-ready
    stream: (u [N+TILE, 128], tile_start).

    Each source stream is already deduped, so a row appears at most once
    per shard; the merge re-sorts the concatenation and K1 sums the <=S
    partial (sum g, sum g²) contributions per row — totals identical to
    the dense psum's, so the downstream optimizer math is unchanged.
    Sentinel entries (row == vocab) sort last and fall outside
    tile_start's coverage.
    """
    n = rows.shape[0]
    if n % CHUNK:
        raise ValueError(f"merged stream length {n} not a CHUNK multiple")
    sidx, perm = jax.lax.sort_key_val(rows, jnp.arange(n, dtype=jnp.int32))
    pay_sorted = pay[perm]
    upos, last, starts, firsts, ends = _sorted_stream_meta(sidx)
    lrow = (sidx % TILE).astype(jnp.float32)
    # pay already holds (sum g, sum g²) — concatenate the placement
    # metadata column instead of re-deriving squares (_payload would
    # square the partial sums).
    payload = _pad_lanes(
        jnp.concatenate([pay_sorted, (lrow * last)[:, None]], axis=1)
    )
    u = _k1_dedup(payload, upos, starts, firsts, ends, n + TILE)
    tile_start = _tile_starts(
        sidx, upos, jnp.arange(0, vocab + 1, TILE, dtype=sidx.dtype)
    )
    return u, tile_start


def k2_apply(update, tile_start, u, tables, compact=None):
    """Apply an elementwise optimizer ``update`` from a K2-ready entry
    stream (as produced by merge_entries) to ``tables``."""
    return _k2_call(update, tile_start, u, tables, u.shape[1],
                    compact=compact)


def merge_entries_stream(rows, pay, *, vocab, group, segment_sums=None):
    """merge_entries in the stream writer's form: ``(u_tiles,
    group_start)`` as _stream_call takes them.

    The concatenated, all-gathered per-shard streams (``rows`` [N],
    ``pay`` [N, 2 D] = sum g | sum g^2 per row and shard) are sorted
    again and K1 sums the <= S partial contributions per row, as
    merge_entries does — but the payload carries BOTH placement columns
    (lrow, tidx: the writer places an entry by them, in a block of
    TILE * ``group`` rows), K1 runs _EXACT_PASSES so that the tile index
    comes back whole at any shard size, and the starts are a block's,
    not a subtile's.  The rows are lane-padded before they are permuted
    (see _payload_padded_first: N is two or more batches' worth).
    ``segment_sums`` stands in for K1 (default: three-pass K1 compiled,
    XLA's sorted segment sum interpreted, as the one-device stream).
    """
    n = rows.shape[0]
    if n % CHUNK:
        raise ValueError(f"merged stream length {n} not a CHUNK multiple")
    if segment_sums is None:
        segment_sums = _xla_segment_sums if _use_interpret() else _k1_exact
    with jax.named_scope("tffm.exchange_merge"):  # sort, payload, K1
        sidx, perm = jax.lax.sort_key_val(
            rows, jnp.arange(n, dtype=jnp.int32))
        upos, last, starts, firsts, ends = _sorted_stream_meta(sidx)
        lrow = (sidx % TILE).astype(jnp.float32)
        tidx = (sidx // TILE).astype(jnp.float32)
        payload = _padded_then_permuted(
            [pay], perm, [lrow * last, tidx * last])
        u = segment_sums(payload, upos, starts, firsts, ends, n + TILE)
        # whole lane tiles: the slices move nothing (see _k1_tiles)
        u_tiles = [u[:, j:j + 128] for j in range(0, u.shape[1], 128)]
        bounds = jnp.arange(0, vocab + 1, TILE * group, dtype=sidx.dtype)
        group_start = _tile_starts(sidx, upos, bounds, "scan_unrolled")
    return u_tiles, group_start


def merged_stream_apply(update, tables, rows, pay):
    """Apply ``update`` from the concatenated per-shard entry streams
    through the stream writer (_stream_call): every table of the shard
    whole through VMEM once as ``table.T``, no row-major copy.  Returns
    ``(new_tables, count)``, ``count`` the merged stream's real entries
    (the rows written)."""
    vocab, d = tables[0].shape
    group = _stream_group(vocab // TILE, d, len(tables))
    u_tiles, group_start = merge_entries_stream(
        rows, pay, vocab=vocab, group=group)
    with jax.named_scope("tffm.apply_write"):
        tables = _stream_call(
            update, group_start, u_tiles, tuple(tables), False, group)
    return tables, group_start[-1]


def gather_entries(lids, g_rows, *, vocab_local, data_axis, rows_all=None):
    """First half of the entries exchange (shard_map body): dedupe the
    LOCAL-coordinate occurrences and all-gather the touched-entry
    streams over ``data_axis``.  Returns the concatenated ``(rows [S *
    cap], pay [S * cap, 2 D])``.  K1 runs _EXACT_PASSES: the rows are
    recovered as ``tidx * TILE + lrow``, and two bf16 passes carry the
    tile index only while ``vocab_local / TILE <= 2^17`` — a shard of
    2^25 rows puts its sentinel exactly there.  ``rows_all``: see
    entries_exchange."""
    with jax.named_scope("tffm.exchange"):
        cap = entries_cap(lids.shape[0], vocab_local)
        # pad_first: the same payload, bit for bit, lane-padded before
        # it is permuted (a data shard's occurrences are a whole batch's
        # on one device: _payload_padded_first's side of its reading)
        rows_e, pay_e, _ = unique_entries(
            lids, g_rows, vocab=vocab_local, cap=cap, pad_first=True,
            segment_sums=_k1_exact,
        )
        if rows_all is None:
            rows_all = jax.lax.all_gather(
                rows_e, data_axis, axis=0, tiled=True
            )
        pay_all = jax.lax.all_gather(pay_e, data_axis, axis=0, tiled=True)
    return rows_all, pay_all


def entries_exchange(lids, g_rows, *, vocab_local, data_axis,
                     data_shards, rows_all=None):
    """The ONE copy of the entries-exchange protocol (shard_map body):
    dedupe LOCAL-coordinate occurrences (off-shard ids pre-mapped to the
    sentinel ``vocab_local``, their payloads zeroed), all-gather the
    touched-entry streams over ``data_axis`` (gather_entries), merge.
    Returns the K2-ready ``(u, tile_start)``; entries_exchange_apply is
    the exchange with its apply, and what both the shardmap step and the
    GSPMD sharded apply call — keep it the only copy.

    ``data_shards`` (static) short-circuits the degenerate pure
    model-parallel case: with one data shard there is nothing to
    exchange, and the single-device dedup already produces the K2
    stream — the gather + second sort + second K1 pass would only
    re-derive it.

    ``rows_all`` (optional) is the pre-gathered ID PLANE: the
    concatenated per-data-shard row streams this call would otherwise
    all-gather itself.  The id plane is a pure function of the batch
    ids (dedup order never looks at payloads), so a caller that knows
    the NEXT super-batch's ids can compute and gather it one scan step
    early (:func:`make_entries_prefetch`) and overlap that collective
    with the previous step's compute — only the payload gather stays
    on the critical path.  Bitwise-identical results by construction.
    """
    if data_shards == 1:
        return _dedup_and_starts(lids, g_rows, vocab_local)
    rows_all, pay_all = gather_entries(
        lids, g_rows, vocab_local=vocab_local, data_axis=data_axis,
        rows_all=rows_all,
    )
    return merge_entries(rows_all, pay_all, vocab=vocab_local)


def exchange_takes_stream(data_shards: int) -> bool:
    """Which apply entries_exchange_apply ends in: the merged stream goes
    through the stream writer wherever kernels run compiled (interpreted,
    the writer is a correctness tool and the row-major K2 stays) and the
    data axis has more than one shard (one keeps its short cut into K2).
    Both callers hand over shards of whole subtiles
    (supports_tile_sharded), which is all the writer asks."""
    return data_shards > 1 and not _use_interpret()


def entries_exchange_apply(update, tables, lids, g_rows, *, vocab_local,
                           data_axis, data_shards, rows_all=None):
    """The entries exchange and the apply on top of it (shard_map body):
    ``update(g1, g2, *table_rows) -> new_table_rows`` applied to the
    shard's ``tables`` from every data shard's occurrences of its rows.
    Returns ``(new_tables, count)``, ``count`` the merged stream's real
    entries.  Where exchange_takes_stream says no, the row-major K2
    (whole-table copies into its layout: the compiler refuses them from
    2^24 rows a shard, PERF.md §4)."""
    tables = tuple(tables)
    if not exchange_takes_stream(data_shards):
        u, tile_start = entries_exchange(
            lids, g_rows, vocab_local=vocab_local, data_axis=data_axis,
            data_shards=data_shards, rows_all=rows_all,
        )
        return tuple(k2_apply(update, tile_start, u, tables)), tile_start[-1]
    rows_all, pay_all = gather_entries(
        lids, g_rows, vocab_local=vocab_local, data_axis=data_axis,
        rows_all=rows_all,
    )
    return merged_stream_apply(update, tables, rows_all, pay_all)


def make_entries_prefetch(mesh, data_axis, model_axis, vocab):
    """Build the id-plane prefetch for the overlapped entries exchange.

    Returns ``prefetch(ids) -> rows_all``: a shard_map program that runs
    the per-device id dedup of :func:`unique_entries` (payloads zeroed —
    the row stream is payload-independent) and all-gathers the streams
    over the data axis, producing the ``rows_all`` operand
    :func:`entries_exchange` accepts.  The output is a ``P(model)``
    global array ([model_shards * data_shards * cap]): every data
    replica of a model column computes the identical gathered stream,
    and the scan carries it to the NEXT step's apply — where it enters
    with an in_spec of ``P(model)``, landing each device exactly the
    block it would have gathered itself.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    model_shards = mesh.shape[model_axis]
    vocab_local = vocab // model_shards

    def local(ids_l):
        m = jax.lax.axis_index(model_axis)
        row_lo = m * vocab_local
        in_range = (ids_l >= row_lo) & (ids_l < row_lo + vocab_local)
        lids = jnp.where(
            in_range, ids_l - row_lo, vocab_local
        ).astype(jnp.int32)
        cap = entries_cap(lids.shape[0], vocab_local)
        zeros = jnp.zeros((lids.shape[0], 1), jnp.float32)
        rows_e, _, _ = unique_entries(
            lids, zeros, vocab=vocab_local, cap=cap,
            segment_sums=_k1_exact,
        )
        return jax.lax.all_gather(rows_e, data_axis, axis=0, tiled=True)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=P(data_axis),
        out_specs=P(model_axis),
        check_vma=False,
    )


# ------------------------------------------------------------ orchestration


def _tile_starts(sidx, upos, boundaries, method="scan"):
    """Unique-entry index of the first id >= each row boundary
    (``method`` as jnp.searchsorted takes it)."""
    n_unique = upos[-1] + 1
    upos_ext = jnp.concatenate([upos, n_unique[None]])
    ss = jnp.searchsorted(sidx, boundaries, method=method)
    return upos_ext[ss].astype(jnp.int32)


def _cumsum_mxu(flags):
    """Prefix sum of 0/1 flags via one triangular matmul — exact only
    while the total stays < 2^24 (f32 integers)."""
    n = flags.shape[0]
    m = flags.reshape(n // 128, 128).astype(jnp.float32)
    # within[r, c] = sum_{k<=c} m[r, k] needs tri[k, c] = (k <= c):
    # upper-triangular (tril would give suffix sums).
    tri = jnp.triu(jnp.ones((128, 128), jnp.float32))
    within = jax.lax.dot_general(
        m, tri, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    row_tot = within[:, -1]
    offs = jnp.cumsum(row_tot) - row_tot
    return (within + offs[:, None]).reshape(n).astype(flags.dtype)


def _cumsum_counts(flags):
    """Prefix sum of 0/1 flags, MXU-shaped and exact at any length.

    XLA lowers a length-640k 1-D cumsum to log-depth VPU passes in a
    lane-hostile layout (~4.7 ms measured on v5e — comparable to the
    whole K1 kernel).  Reshaping to [rows, 128] turns the within-row
    prefix into one [rows,128]x[128,128] triangular matmul plus a
    128x-shorter cumsum of row totals; f32 keeps that exact below 2^24
    counts.  Above (the flagship B=262k step has 10.2M occurrences),
    a two-level split stays exact: segments of < 2^24 get the MXU
    prefix (segment-LOCAL counts < segment length, f32-exact), and the
    tiny integer cumsum of segment totals supplies exact int32 offsets.
    Falls back to jnp.cumsum only when no 128-multiple segment divides n.
    """
    n = flags.shape[0]
    if n % 128:
        return jnp.cumsum(flags)
    if n < 1 << 24:
        return _cumsum_mxu(flags)
    seg = 1 << 23
    while n % seg:
        seg >>= 1
    if seg < 128:  # n % 128 == 0 makes this unreachable; belt+braces
        return jnp.cumsum(flags)
    m = flags.reshape(n // seg, seg)
    within = jax.vmap(_cumsum_mxu)(m)  # [S, seg], ints < 2^23 each
    seg_tot = within[:, -1]
    offs = jnp.cumsum(seg_tot) - seg_tot  # int32: exact at any total
    return (within + offs[:, None]).reshape(n)


def _pad_lanes(x):
    """Pad the minor dim to the 128-lane tile.

    The unique-entry stream is DMA'd at dynamic offsets (K1 out,
    K2/K-place in), and Mosaic requires manually sliced HBM memrefs to
    be lane-aligned ("Slice shape along dimension 1 must be aligned to
    tiling (128)" on real v5e — auto-pipelined BlockSpecs pad for free,
    manual `.at[pl.ds(...)]` copies do not).  HBM storage is already
    physically padded to 128 lanes by tiling, so the zeros cost no extra
    traffic.
    """
    n, lanes = x.shape
    lanes_pad = -(-lanes // 128) * 128
    if lanes_pad != lanes:
        x = jnp.concatenate(
            [x, jnp.zeros((n, lanes_pad - lanes), x.dtype)], axis=1
        )
    return x


def _payload(g_sorted, lrow_last, tidx_last=None):
    """[g | g^2 | lrow·last | tidx·last?] per sorted occurrence, 128-lane
    padded (see _pad_lanes).

    ``tidx_last`` (the occurrence's tile index, · last-in-segment flag)
    is carried only where the deduped stream's global rows must be
    recoverable afterwards — the entries exchange and the unique-row
    scatter.  Like lrow, K1's segment sum leaves exactly the value on
    the unique entry because only the last occurrence contributes.
    """
    cols = [g_sorted, g_sorted * g_sorted, lrow_last[:, None]]
    if tidx_last is not None:
        cols.append(tidx_last[:, None])
    return _pad_lanes(jnp.concatenate(cols, axis=1))


def _payload_padded_first(g_rows, perm, lrow_last, tidx_last):
    """``_payload(g_rows[perm], ...)`` bit for bit, in another order:
    the rows are squared and lane-padded BEFORE they are permuted, and
    the (already sorted) metadata lanes selected in afterwards.  At the
    unique-row scatter's n = 2.56 M on v5e a gather of 512-byte rows
    costs ~10 ns a row where the 9-wide ``g_rows[perm]`` costs 23 ns and
    the concatenation of the sorted narrow arrays as much again: 45.7
    against 85.4 ms for the whole prep.  Not so at the tile path's
    n = 640k, where this order read 1.5 ms slower (53.7 against 52.3 ms
    an apply), so that path keeps _payload (PERF.md §6, PR 27)."""
    return _padded_then_permuted(
        [g_rows, g_rows * g_rows], perm, [lrow_last, tidx_last])


def _padded_then_permuted(cols, perm, meta):
    """``[cols...[perm] | meta...]`` lane-padded, the padding done
    before the permutation (see _payload_padded_first): ``cols`` the
    unsorted column groups, ``meta`` the already sorted metadata columns
    that follow them."""
    n = cols[0].shape[0]
    width = sum(c.shape[1] for c in cols)
    p = _pad_lanes(jnp.concatenate(
        cols + [jnp.zeros((n, len(meta)), cols[0].dtype)], axis=1,
    ))[perm]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, p.shape[1]), 1)
    for k, col in enumerate(meta):
        p = jnp.where(lane == width + k, col[:, None], p)
    return p


def _sorted_stream_meta(sidx):
    """Segment metadata for a sorted id stream: (upos, last-flags, and the
    K1 chunk-boundary scalars).  Shared by _prep and merge_entries."""
    flag_first = jnp.concatenate([jnp.full((1,), -1, sidx.dtype), sidx[:-1]])
    flags = (sidx != flag_first).astype(jnp.int32)  # segment starts
    upos = _cumsum_counts(flags) - 1  # unique-row position per occurrence
    nxt = jnp.concatenate([sidx[1:], jnp.full((1,), -2, sidx.dtype)])
    last = (sidx != nxt).astype(jnp.float32)  # segment ends
    starts = upos[::CHUNK]
    firsts = jnp.concatenate([flags[::CHUNK], jnp.ones((1,), jnp.int32)])
    ends = upos[CHUNK - 1::CHUNK]
    return upos, last, starts, firsts, ends


def _prep(ids, g_rows, vocab, pad_first=False):
    """Sort, dedup-position, and chunk-boundary metadata (all XLA).
    ``pad_first`` picks _payload_padded_first's order of the same
    payload."""
    n = ids.shape[0]
    d = g_rows.shape[1]
    n_pad = -(-n // CHUNK) * CHUNK
    if n_pad != n:
        # Sentinel occurrences: id = vocab sorts last, lands in no real
        # tile (tile_start covers rows < vocab), grads are zero anyway.
        ids = jnp.concatenate(
            [ids, jnp.full((n_pad - n,), vocab, ids.dtype)]
        )
        g_rows = jnp.concatenate(
            [g_rows, jnp.zeros((n_pad - n, d), g_rows.dtype)]
        )
    sidx, perm = jax.lax.sort_key_val(ids, jnp.arange(n_pad, dtype=jnp.int32))
    upos, last, starts, firsts, ends = _sorted_stream_meta(sidx)
    lrow = (sidx % TILE).astype(jnp.float32)  # tile-local row, exact < TILE
    # Tile index, f32-exact while vocab/TILE < 2^24 (true for any vocab
    # < 2^31 at TILE >= 256 — int32 ids cap vocab below that anyway).
    # K1 must carry it whole: two bf16 passes hold 17 bits of it.
    tidx = (sidx // TILE).astype(jnp.float32)
    if pad_first:
        payload = _payload_padded_first(g_rows, perm, lrow * last, tidx * last)
    else:
        payload = _payload(g_rows[perm], lrow * last, tidx * last)
    return payload, upos, starts, firsts, ends, sidx, n_pad


def _dedup_and_starts(ids, g_rows, vocab, meta=None):
    if meta is not None:
        n, d = g_rows.shape
        n_pad = meta.perm.shape[0]
        # The producer baked CHUNK/TILE into these shapes; a mismatch
        # means pipeline and kernels disagree on the constants — running
        # anyway would misplace rows, so fail loudly at trace time.
        if (
            n_pad != -(-n // CHUNK) * CHUNK
            or meta.starts.shape[0] != n_pad // CHUNK
            or meta.tile_start.shape[0] != vocab // TILE + 1
        ):
            raise ValueError(
                "sort_meta shapes disagree with CHUNK/TILE/vocab: "
                f"perm={meta.perm.shape} starts={meta.starts.shape} "
                f"tile_start={meta.tile_start.shape} vs n={n} "
                f"CHUNK={CHUNK} TILE={TILE} vocab={vocab}"
            )
        if n_pad != n:
            g_rows = jnp.concatenate(
                [g_rows, jnp.zeros((n_pad - n, d), g_rows.dtype)]
            )
        g_sorted = g_rows[meta.perm]
        payload = _payload(g_sorted, meta.lrow_last)
        u = _k1_dedup(
            payload, meta.upos, meta.starts, meta.firsts, meta.ends,
            n_pad + TILE,
        )
        return u, meta.tile_start
    payload, upos, starts, firsts, ends, sidx, n_pad = _prep(
        ids, g_rows, vocab
    )
    u = _k1_dedup(payload, upos, starts, firsts, ends, n_pad + TILE)
    tile_start = _tile_starts(
        sidx, upos, jnp.arange(0, vocab + 1, TILE, dtype=sidx.dtype)
    )
    return u, tile_start


def adagrad_apply(table, acc, ids, g_rows, *, lr, eps, meta=None,
                  compact=None):
    """Sparse Adagrad over touched rows: exact SparseApplyAdagrad semantics."""
    vocab, d = table.shape
    u, tile_start = _dedup_and_starts(ids, g_rows, vocab, meta)
    update = functools.partial(adagrad_update, lr=lr, eps=eps)
    table, acc = _k2_call(update, tile_start, u, (table, acc), u.shape[1],
                          compact=compact)
    return table, acc


def sgd_apply(table, ids, g_rows, *, lr, meta=None, compact=None):
    vocab, d = table.shape
    u, tile_start = _dedup_and_starts(ids, g_rows, vocab, meta)
    update = functools.partial(sgd_update, lr=lr)
    (table,) = _k2_call(update, tile_start, u, (table,), u.shape[1],
                        compact=compact)
    return table


def ftrl_apply(table, z, n, ids, g_rows, *, lr, l1, l2, beta, meta=None,
               compact=None):
    # Recomputing w for untouched rows inside ftrl_update is idempotent:
    # their (z, n) are unchanged and w is always ftrl_solve(z, n)
    # (train.sparse initializes z so this holds from step 0).  This
    # invariant is a CONTRACT: the full sweep recomputes every row while
    # compact K2 skips untouched ones, and the two only agree because
    # recompute == stored value.  A caller handing in a table that is
    # not ftrl_solve(z, n) gets sweep-dependent untouched rows.
    vocab, d = table.shape
    u, tile_start = _dedup_and_starts(ids, g_rows, vocab, meta)
    update = functools.partial(ftrl_update, lr=lr, l1=l1, l2=l2, beta=beta)
    table, z, n = _k2_call(update, tile_start, u, (table, z, n), u.shape[1],
                           compact=compact)
    return table, z, n


# ------------------------------------------------------- sharded (shard_map)


def supports_tile_sharded(vocab: int, optimizer: str, model_shards: int) -> bool:
    return (
        optimizer in ("adagrad", "ftrl", "sgd")
        and vocab % (model_shards * TILE) == 0
        and vocab // model_shards >= TILE
    )


def _sharded_call(update_fn, mesh, data_axis, model_axis, tables, ids,
                  g_rows, vocab, exchange="dense", rows_all=None):
    """shard_map wrapper: per-device K1 dedup, then either a dense
    per-shard delta psum over the data axis (``exchange="dense"``) or a
    batch-proportional all-gather of the touched-entry streams
    (``"entries"``), then the optimizer update on the local table shard.

    This is the GSPMD-era replacement for the reference's PS scatter push
    (SURVEY.md §3.2): dense mode uses the sync-DP gradient-allreduce
    collective pattern (O(vocab) bytes); entries mode keeps the PS
    design's IndexedSlices property — bytes scale with the batch,
    independent of vocab.

    ``rows_all`` is the prefetched id plane for the overlapped entries
    exchange (see :func:`entries_exchange` / :func:`make_entries_prefetch`)
    — only legal with ``exchange="entries"`` and a multi-shard data axis.
    """
    from jax.sharding import PartitionSpec as P

    model_shards = mesh.shape[model_axis]
    vocab_local = vocab // model_shards
    n_tables = len(tables)
    if rows_all is not None and (
        exchange != "entries" or mesh.shape[data_axis] == 1
    ):
        raise ValueError(
            "a prefetched id plane (rows_all) only applies to the "
            "entries exchange over a multi-shard data axis"
        )

    def local(ids_l, g_l, *rest):
        if rows_all is not None:
            rows_in, tables_l = rest[0], rest[1:]
        else:
            rows_in, tables_l = None, rest
        m = jax.lax.axis_index(model_axis)
        row_lo = m * vocab_local
        d = g_l.shape[1]
        if exchange == "entries":
            in_range = (ids_l >= row_lo) & (ids_l < row_lo + vocab_local)
            lids = jnp.where(
                in_range, ids_l - row_lo, vocab_local
            ).astype(jnp.int32)
            g_masked = jnp.where(in_range[:, None], g_l, 0.0)
            # the apply expects update -> tuple; the single-table (sgd)
            # wrapper returns a bare array.
            upd = (
                update_fn if n_tables > 1
                else (lambda g1, g2, *t: (update_fn(g1, g2, *t),))
            )
            out, _ = entries_exchange_apply(
                upd, tables_l, lids, g_masked, vocab_local=vocab_local,
                data_axis=data_axis, data_shards=mesh.shape[data_axis],
                rows_all=rows_in,
            )
            return tuple(out) if n_tables > 1 else out[0]
        dense = dense_delta(
            ids_l, g_l, vocab=vocab,
            vocab_local=vocab_local, row_lo=row_lo,
        )
        dense = jax.lax.psum(dense, data_axis)
        return update_fn(dense[:, :d], dense[:, d:], *tables_l)

    from jax import shard_map

    extra = () if rows_all is None else (rows_all,)
    extra_specs = () if rows_all is None else (P(model_axis),)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(data_axis), P(data_axis, None)) + extra_specs
        + (P(model_axis, None),) * n_tables,
        out_specs=(P(model_axis, None),) * n_tables
        if n_tables > 1 else P(model_axis, None),
        check_vma=False,  # pallas_call outputs carry no vma annotations
    )(ids, g_rows, *extra, *tables)


def adagrad_apply_sharded(table, acc, ids, g_rows, *, lr, eps, mesh,
                          data_axis, model_axis, exchange="dense",
                          rows_all=None):
    def update(g1, g2, table_l, acc_l):
        return adagrad_update(g1, g2, table_l, acc_l, lr=lr, eps=eps)

    return _sharded_call(
        update, mesh, data_axis, model_axis, (table, acc), ids, g_rows,
        table.shape[0], exchange=exchange, rows_all=rows_all,
    )


def sgd_apply_sharded(table, ids, g_rows, *, lr, mesh, data_axis,
                      model_axis, exchange="dense", rows_all=None):
    def update(g1, g2, table_l):
        return sgd_update(g1, g2, table_l, lr=lr)[0]

    return _sharded_call(
        update, mesh, data_axis, model_axis, (table,), ids, g_rows,
        table.shape[0], exchange=exchange, rows_all=rows_all,
    )


def ftrl_apply_sharded(table, z, n, ids, g_rows, *, lr, l1, l2, beta, mesh,
                       data_axis, model_axis, exchange="dense",
                       rows_all=None):
    def update(g1, g2, table_l, z_l, n_l):
        return ftrl_update(
            g1, g2, table_l, z_l, n_l, lr=lr, l1=l1, l2=l2, beta=beta
        )

    return _sharded_call(
        update, mesh, data_axis, model_axis, (table, z, n), ids, g_rows,
        table.shape[0], exchange=exchange, rows_all=rows_all,
    )
