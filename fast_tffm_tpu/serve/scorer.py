"""Compiled fixed-shape scorers: the serving path's device half.

Online traffic arrives at arbitrary sizes; a jit that traces per
request shape would recompile constantly (a multi-second stall per new
shape) and XLA executables only exist at fixed shapes anyway.  The
scorers here pin a small LADDER of microbatch shapes (``{64, 256,
1024}`` examples x ``max_features`` by default, ``serve_batch_sizes``)
and pad every request/chunk up to the smallest rung that holds it:

- every rung is precompiled at startup through an AOT
  ``.lower().compile()`` cache (:meth:`warmup`), so steady-state
  serving NEVER compiles — the zero-compile contract the serving tests
  pin (``steady_compiles``);
- input buffers are donated (``donate_argnums``): XLA may reuse the
  microbatch's device memory for the result, and the host side fills
  recycled per-rung staging buffers (the prefetcher's staging-pool
  discipline) instead of allocating per dispatch;
- parameters are an ARGUMENT of the compiled function, not a constant —
  so a warm checkpoint hot-swap is one reference swap between
  dispatches (:meth:`swap`), zero recompiles, and a dispatch always
  scores against exactly one table (old or new, never torn).

Two variants share the plumbing: :class:`FixedShapeScorer` scores a
dense device-resident table (the ordinary checkpoint format), and
:class:`OverlayScorer` scores straight from a huge-V ``tiered.npz``
sparse-overlay checkpoint — per chunk it remaps the batch's unique ids
to a compact bucket-padded table gathered from the host cold store
(the same compact-table trick the tiered trainer's validation path
uses), so a V >= 2^28 model serves without ever materializing [V, D].

Compile accounting mirrors the trainer's sentinel: every compile is
timed into a ``serve.compile`` timer and written as a ``record:
compile`` JSONL entry (``where: serve``); a compile at a shape OUTSIDE
the ladder bumps ``serve.recompiles_unexpected`` and warns — on the
serving path an unexpected compile is a multi-second latency cliff.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fast_tffm_tpu import obs
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.ops import autotune as autotune_lib
from fast_tffm_tpu.ops import quant
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train import checkpoint
from fast_tffm_tpu.train import tiered as tiered_lib

log = logging.getLogger(__name__)

__all__ = [
    "FixedShapeScorer", "OverlayScorer", "load_model", "make_scorer",
]


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@contextlib.contextmanager
def _quiet_donation():
    """Silence the per-compile "donated buffers were not usable"
    UserWarning: donation is a best-effort device-memory optimization
    (it pays off where input/output buffers can alias, e.g. TPU); on
    backends where it can't, one warning per ladder rung at startup is
    pure noise."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message=".*donated buffers were not usable.*"
        )
        yield


class _Flight:
    """One launched rung whose scores are still on the device: what
    :meth:`_LadderScorer.launch_rung` hands out and
    :meth:`_LadderScorer.read_rung` takes.  ``launch_s`` / ``readback_s``
    are the two halves' own seconds (a sampled request's span reads
    them: timer totals interleave once two groups are under way)."""

    __slots__ = ("out", "rung", "launch_s", "readback_s")

    def __init__(self, out, rung: int, launch_s: float):
        self.out = out
        self.rung = rung
        self.launch_s = launch_s
        self.readback_s = 0.0


class _LadderScorer:
    """Shared rung/pool/compile plumbing of the two scorer variants.

    A dispatch has two halves.  :meth:`launch_rung` resolves the model
    reference, calls the compiled rung (the ``launch`` phase) and
    returns the un-read result; :meth:`read_rung` blocks on it (the
    ``readback`` phase).  :meth:`score_rung` and :meth:`score` are the
    read of the launch; the batcher calls the halves itself, so that the
    device runs group n+1 while the host reads and delivers group n.

    Thread contract: launches serialize on one lock, which also guards
    the scorer's own staging pools (:meth:`score` holds it for all its
    chunks, reads included); :meth:`read_rung` takes no lock.  The
    arrays handed to :meth:`launch_rung` stay the caller's and must not
    be written until that launch has been READ: the transfer to the
    device may still be reading them when the call returns.
    :meth:`swap` may run on any thread: it replaces the model REFERENCE
    under ``_swap_lock``, and a launch grabs that reference once and
    uses it for the whole microbatch — so every dispatch scores against
    exactly one model (old or new, never torn; a group in flight across
    a swap stays on the model it was launched with) and a swap never
    waits on traffic.
    """

    def __init__(self, cfg: FmConfig, mesh=None, telemetry=None,
                 writer=None, extra_rungs=()):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(cfg)
        data_n = int(self.mesh.shape[mesh_lib.DATA_AXIS])
        rungs = sorted({
            _round_up(int(b), data_n)
            for b in tuple(cfg.serve_ladder) + tuple(extra_rungs)
            if int(b) > 0
        })
        self.ladder = tuple(rungs)
        self.max_rung = self.ladder[-1]
        self._ladder_set = set(self.ladder)
        tel = telemetry if telemetry is not None else obs.NULL
        self._tel = tel
        self._t_compile = tel.timer("serve.compile")
        # One observation a dispatch, made at its read: the launch
        # half's seconds plus the read's — NOT launch to read on the
        # clock, which would count the time a group sat in flight while
        # its caller served another.
        self._t_dispatch = tel.timer("serve.dispatch")
        # The two halves of a dispatch, each also a tffm:serve.<phase>
        # annotation (obs.Phase): the rung call (implicit H2D of the
        # numpy arguments + enqueue) and the blocking read of its scores.
        self._t_launch = tel.timer("serve.launch")
        self._t_readback = tel.timer("serve.readback")
        # Wait for the dispatch lock: a timer and never an annotation —
        # a span over a wait on another thread would cover whole idle
        # gaps and hide what the working thread was doing.
        self._t_lock_wait = tel.timer("serve.lock_wait")
        self._c_unexpected = tel.counter("serve.recompiles_unexpected")
        self._c_swaps = tel.counter("serve.swaps")
        self._writer = writer
        self._lock = threading.Lock()  # serializes dispatch + pools
        self._swap_lock = threading.Lock()
        # Previous model retained by a keep_prev swap (the canary
        # protocol's rollback window): (model_ref, step) or None.
        # Holding it costs one standby table's memory, so it exists
        # only between a keep_prev swap and the promote()/rollback()
        # decision.
        self._prev = None
        self._cache: dict = {}
        self._pools: dict = {}  # rung -> (ids, vals, fields) host buffers
        self._no_fields: dict = {}  # rung -> all-zero fields, never written
        self._inflight = 0  # the launch under way's `inflight` stat
        self._warmed = False
        # Whether EXPECTED compiles may legitimately happen after
        # warmup: False for the dense scorer (warmup compiles the whole
        # ladder, so any later compile is the latency-cliff signal);
        # True for the overlay scorer (compact-table buckets compile
        # lazily, O(log) of them, by design).
        self._lazy_expected_ok = False
        self.steady_compiles = 0  # post-warmup latency-cliff compiles
        self.compiles = 0
        self.step = 0  # checkpoint step currently served (0 = in-memory)
        F = cfg.max_features
        sh = mesh_lib.batch_sharding(self.mesh)
        self._arg_sh = (sh["ids"], sh["vals"], sh["fields"])
        self._arg_dtypes = (np.int32, np.float32, np.int32)
        self._n_args = 3 if cfg.field_num else 2
        self._feat = F
        # Compile accounting may run from several warmup threads (the
        # rung ladder compiles concurrently); a lock keeps the counter
        # increments, steady accounting, and record writes coherent.
        self._compile_lock = threading.Lock()
        self.warmup_wall_s = 0.0  # wall clock of the last warmup()
        self.warmup_compile_s = 0.0  # summed compile time inside warmup
        # Kernel autotune (ops/autotune.py): with interaction_impl set,
        # resolve the serve-path interaction impl at the max rung's
        # shape (auto measures + parity-gates against reference; pins
        # and the single-candidate CPU case skip measurement).  The
        # resolved internal name routes the rung score math through
        # ops.interaction._forward; None keeps the historical
        # closed-form path bit-identical (reference IS that path).
        self._impl = None
        self.kernel_impl = "reference"
        if cfg.interaction_impl:
            d = autotune_lib.resolve(
                cfg, context="serve", batch=self.max_rung, writer=writer,
            )
            self.kernel_impl = d.impl
            self._impl = None if d.interaction == "jnp" else d.interaction

    # -- rung / pool helpers -------------------------------------------

    def rung_for(self, n: int) -> int:
        """Smallest ladder rung holding ``n`` examples (the max rung for
        anything larger — callers chunk)."""
        for b in self.ladder:
            if n <= b:
                return b
        return self.max_rung

    def slots_for(self, n: int) -> int:
        """Total padded slots :meth:`score` dispatches for ``n``
        examples — the chunk policy's accounting twin, kept HERE so
        fill-fraction bookkeeping can never drift from the actual
        chunking."""
        slots = 0
        pos = 0
        while pos < n:
            c = min(n - pos, self.max_rung)
            slots += self.rung_for(c)
            pos += c
        return slots

    def _pool(self, b: int):
        bufs = self._pools.get(b)
        if bufs is None:
            bufs = tuple(
                np.zeros((b, self._feat), dt) for dt in self._arg_dtypes
            )
            self._pools[b] = bufs
        return bufs

    def _finish(self, s):
        """Score post-processing shared by both variants: probabilities
        for logistic loss, raw scores for mse (predict's contract)."""
        if self.cfg.loss_type == "logistic":
            s = jax.nn.sigmoid(s)
        return s

    # -- compile accounting --------------------------------------------

    def _account_compile(self, wall: float, key, expected: bool) -> None:
        with self._compile_lock:
            self._t_compile.observe(wall)
            self.compiles += 1
            if not self._warmed:
                self.warmup_compile_s += wall
            if self._warmed and not (expected and self._lazy_expected_ok):
                self.steady_compiles += 1
        if not expected:
            self._c_unexpected.add()
            log.warning(
                "UNEXPECTED serve-path compile (%s, %.2fs): the shape "
                "is outside the configured serve_batch_sizes ladder — "
                "a multi-second latency cliff on the hot path",
                key, wall,
            )
        if self._writer is not None:
            try:
                self._writer.write({
                    "record": "compile",
                    "where": "serve",
                    "time": time.time(),
                    "shape": list(key) if isinstance(key, tuple) else key,
                    "compile_s": round(wall, 4),
                    "expected": bool(expected),
                })
            except Exception as e:  # noqa: BLE001 - never kill a compile
                log.warning("serve compile record write failed: %s", e)

    def warmup(self) -> int:
        """Precompile every ladder rung; returns the compile count.
        After this returns, a correctly-configured server never
        compiles again (``steady_compiles`` stays 0).

        Rungs compile CONCURRENTLY: each rung is an independent
        ``.lower().compile()`` at a distinct cache key and XLA releases
        the GIL while compiling, so a thread per rung overlaps what
        used to be a serial multi-second ladder walk.  The saving is
        recorded (``warmup_compile_s`` summed vs ``warmup_wall_s``
        observed) — with a populated persistent compile cache both
        collapse to near zero and the warm-spawn zero-fresh-lowers
        contract is checkable.
        """
        t0 = time.perf_counter()
        with self._lock:
            if len(self.ladder) > 1:
                with ThreadPoolExecutor(
                    max_workers=min(len(self.ladder), 8),
                    thread_name_prefix="tffm-warmup",
                ) as ex:
                    # list() re-raises the first rung failure, matching
                    # the serial path's error contract.
                    list(ex.map(self._warm_rung, self.ladder))
            else:
                for b in self.ladder:
                    self._warm_rung(b)
        self.warmup_wall_s = time.perf_counter() - t0
        self._warmed = True
        self.steady_compiles = 0
        if self.compiles and self.warmup_compile_s > self.warmup_wall_s:
            log.info(
                "concurrent ladder warmup: %.2fs of compiles in %.2fs "
                "wall (%.2fs saved)",
                self.warmup_compile_s, self.warmup_wall_s,
                self.warmup_compile_s - self.warmup_wall_s,
            )
        return self.compiles

    # -- scoring -------------------------------------------------------

    def _call_rung(self, fn, args, b: int):
        """Call the compiled rung as the ``launch`` phase; returns its
        un-read result (the subclasses' ``_dispatch_rung`` ends here)."""
        with obs.Phase(self._t_launch, "tffm:serve.launch", rung=b,
                       inflight=self._inflight):
            return fn(*args)

    def _launch(self, ids, vals, fields, b: int, inflight: int) -> _Flight:
        """The launch half; the caller holds ``_lock``."""
        t0 = time.perf_counter()
        if fields is None and self._n_args == 3:
            # A fields-less group scores against zeros of its own: a
            # buffer nobody writes, so no group in flight can see it change.
            fields = self._no_fields.get(b)
            if fields is None:
                fields = self._no_fields[b] = np.zeros(
                    (b, self._feat), np.int32
                )
        self._inflight = inflight
        out = self._dispatch_rung(ids, vals, fields, b)
        return _Flight(out, b, time.perf_counter() - t0)

    def launch_rung(self, ids: np.ndarray, vals: np.ndarray,
                    fields: Optional[np.ndarray], b: int,
                    inflight: int = 0) -> _Flight:
        """First half of one dispatch of exactly-rung-shaped arrays:
        returns once the rung is enqueued, its scores un-read.
        ``inflight`` is how many launches the caller has not read yet
        (a stat on the ``launch`` phase, nothing else)."""
        t_ask = time.perf_counter()
        with self._lock:
            self._t_lock_wait.observe(time.perf_counter() - t_ask)
            return self._launch(ids, vals, fields, b, inflight)

    def read_rung(self, flight: _Flight) -> np.ndarray:
        """Second half: block on the launch's scores and bring them to
        the host.  The score goes back to a client, so the D2H is
        request latency."""
        with obs.Phase(self._t_readback, "tffm:serve.readback",
                       rung=flight.rung) as ph:
            scores = np.asarray(flight.out)
        flight.readback_s = ph.seconds
        self._t_dispatch.observe(flight.launch_s + flight.readback_s)
        return scores

    def score(self, ids: np.ndarray, vals: np.ndarray,
              fields: Optional[np.ndarray] = None) -> np.ndarray:
        """Scores for ``n`` examples (``[n, max_features]`` arrays), any
        ``n``: chunks at the max rung, pads the tail chunk up to its
        rung with zero rows (``vals == 0`` rows are mathematically inert
        and their outputs are discarded)."""
        n = len(ids)
        out = np.empty((n,), np.float32)
        pos = 0
        t_ask = time.perf_counter()
        with self._lock:
            self._t_lock_wait.observe(time.perf_counter() - t_ask)
            while pos < n:
                c = min(n - pos, self.max_rung)
                b = self.rung_for(c)
                bi, bv, bf = self._pool(b)
                bi[:c] = ids[pos:pos + c]
                bv[:c] = vals[pos:pos + c]
                if c < b:
                    bi[c:] = 0
                    bv[c:] = 0.0
                if self._n_args == 3:
                    if fields is not None:
                        bf[:c] = fields[pos:pos + c]
                    else:
                        bf[:c] = 0
                    if c < b:
                        bf[c:] = 0
                # Read before the next chunk refills the pool.
                scores = self.read_rung(self._launch(bi, bv, bf, b, 0))
                out[pos:pos + c] = scores[:c]
                pos += c
        return out

    def score_rung(self, ids: np.ndarray, vals: np.ndarray,
                   fields: Optional[np.ndarray], b: int) -> np.ndarray:
        """One blocking dispatch of exactly-rung-shaped arrays."""
        return self.read_rung(self.launch_rung(ids, vals, fields, b))

    # -- canary promote / rollback -------------------------------------

    def promote(self) -> None:
        """Drop the previous model a ``keep_prev`` swap retained: the
        new params are now the fleet's truth and the rollback window is
        closed (frees the standby table's memory)."""
        with self._swap_lock:
            self._prev = None

    def rollback(self) -> bool:
        """Restore the model a ``keep_prev`` swap replaced (the canary
        failed its shadow compare).  One reference swap between
        dispatches, same never-torn contract as :meth:`swap`.  Returns
        False when there is nothing to roll back to."""
        with self._swap_lock:
            if self._prev is None:
                return False
            model, step = self._prev
            self._prev = None
            self._set_model(model)
            self.step = int(step)
        self._c_swaps.add()
        log.info("serving params rolled back to step %d", step)
        return True

    # -- subclass hooks ------------------------------------------------

    def _set_model(self, model) -> None:
        """Install a model reference (rollback path); caller holds
        ``_swap_lock``."""
        raise NotImplementedError

    def _warm_rung(self, b: int) -> None:
        raise NotImplementedError

    def _dispatch_rung(self, ids, vals, fields, b: int):
        """Resolve the model reference once and launch the rung on it:
        returns :meth:`_call_rung`'s un-read result."""
        raise NotImplementedError


class FixedShapeScorer(_LadderScorer):
    """Dense-table scorer: params device-resident, hot-swappable.

    ``params`` may be a host-numpy or device :class:`fm.FmParams`
    (fp32), or — for a ``quant.npz`` checkpoint — a ``(w0,
    quant.QuantTable)`` pair; it is placed with the mesh's param
    sharding either way.

    ``serve_table_dtype`` picks the DEVICE-RESIDENT storage format:

    - ``fp32`` — the historical path, bit-identical scores;
    - ``bf16`` — the table device-residents as bfloat16 (half the
      bytes); the compiled rungs gather compact rows and the existing
      f32 upcast in the score math widens them in-register;
    - ``int8`` — codes + per-``quant_chunk``-rows fp32 scales
      (~quarter the bytes); the compiled rungs run
      ``fm.fm_scores_dequant`` (gather codes + scale chunk, widen
      in-register, score).

    Either way the rung shapes are unchanged, so the AOT ladder /
    zero-steady-compile contract and the hot-swap protocol carry over
    verbatim: an fp32 checkpoint swap quantizes host-side into standby
    buffers off-traffic.  ``serve.table_bytes`` gauges the resident
    table footprint and ``serve.quant_error_max`` the max
    |score_fp32 − score_quant| on a deterministic probe batch measured
    at placement time (0 for fp32).
    """

    def __init__(self, cfg: FmConfig, params, mesh=None,
                 telemetry=None, writer=None, extra_rungs=(), step=0):
        super().__init__(cfg, mesh=mesh, telemetry=telemetry,
                         writer=writer, extra_rungs=extra_rungs)
        self.step = int(step)
        self.table_dtype = quant.validate_dtype(
            cfg.serve_table_dtype, "serve_table_dtype"
        )
        self._chunk = cfg.quant_chunk
        self._param_sh = mesh_lib.param_sharding(self.mesh)
        self._g_table_bytes = self._tel.gauge("serve.table_bytes")
        self._g_quant_err = self._tel.gauge("serve.quant_error_max")
        self._params = self._place(params)
        impl = self._impl  # autotune-resolved interaction routing
        if self.table_dtype == "int8":
            chunk = self._chunk
            if cfg.field_num:
                def score_fn(params, ids, vals, fields):
                    return self._finish(fm.fm_scores_dequant(
                        params.w0, params.codes, params.scales, chunk,
                        ids, vals, fields,
                        factor_num=cfg.factor_num,
                        field_num=cfg.field_num,
                    ))
            else:
                def score_fn(params, ids, vals):
                    return self._finish(fm.fm_scores_dequant(
                        params.w0, params.codes, params.scales, chunk,
                        ids, vals, None,
                        factor_num=cfg.factor_num, field_num=0,
                        impl=impl,
                    ))
            param_sh_tree = quant.QuantParams(
                w0=self._param_sh.w0,
                codes=self._param_sh.table,
                # The scale vector is tiny (V / chunk floats) and 1-D:
                # replicate it rather than invent a 1-axis sharding.
                scales=NamedSharding(self.mesh, P()),
            )
        else:
            # fp32 and bf16 share the FmParams score path: the gather
            # reads whatever dtype the table stores and the score
            # math's astype widens it in-register (ops/interaction.py).
            if cfg.field_num:
                def score_fn(params, ids, vals, fields):
                    return self._finish(fm.fm_scores(
                        params, ids, vals, fields,
                        factor_num=cfg.factor_num,
                        field_num=cfg.field_num,
                    ))
            else:
                def score_fn(params, ids, vals):
                    return self._finish(fm.fm_scores(
                        params, ids, vals, None,
                        factor_num=cfg.factor_num, field_num=0,
                        impl=impl,
                    ))
            param_sh_tree = self._param_sh
        self._jit = jax.jit(
            score_fn,
            in_shardings=(
                (param_sh_tree,) + self._arg_sh[:self._n_args]
            ),
            donate_argnums=tuple(range(1, 1 + self._n_args)),
        )

    # -- placement (construction + hot-swap staging) -------------------

    def _probe_quant_error(self, w0, table_f32: np.ndarray,
                           qt: "quant.QuantTable") -> float:
        """max |served_fp32 − served_quant| on a deterministic probe
        batch — host-side eager math (no ladder compile, so warmup
        accounting stays exact), gathering ONLY the probe rows from
        either side (dequantizing the full [V, D] table to read a few
        hundred rows would be a multi-GB allocation per hot-swap at
        real vocabularies); the REAL compiled-path tolerance is pinned
        by tests/test_quant.py."""
        cfg = self.cfg
        rng = np.random.default_rng(0xC0FFEE)
        n = min(256, cfg.vocabulary_size)
        ids = rng.integers(
            0, cfg.vocabulary_size, (n, cfg.max_features)
        ).astype(np.int64)
        vals = rng.uniform(0.1, 1.0, ids.shape).astype(np.float32)
        fields = (
            rng.integers(0, cfg.field_num, ids.shape).astype(np.int32)
            if cfg.field_num else None
        )
        w0j = jnp.asarray(w0, jnp.float32)

        def score(rows):
            return self._finish(fm.scores_from_rows(
                w0j, jnp.asarray(rows), jnp.asarray(vals),
                None if fields is None else jnp.asarray(fields),
                factor_num=cfg.factor_num, field_num=cfg.field_num,
            ))

        return float(jnp.max(jnp.abs(
            score(table_f32[ids]) - score(quant.dequantize_rows(qt, ids))
        )))

    def _place(self, params):
        dtype = self.table_dtype
        if isinstance(params, fm.FmParams):
            qt = None
        else:
            try:
                w0_in, qt = params
            except (TypeError, ValueError):
                raise ValueError(
                    "FixedShapeScorer params must be fm.FmParams or a "
                    f"(w0, QuantTable) pair, got {type(params).__name__}"
                ) from None
        if dtype == "fp32":
            if qt is not None:
                raise ValueError(
                    "a quantized (quant.npz) table cannot serve with "
                    "serve_table_dtype=fp32 — set serve_table_dtype to "
                    f"the checkpoint's dtype ({qt.dtype}) or convert "
                    "it back (python -m tools.convert_checkpoint "
                    "<dir> --to fp32)"
                )
            placed = fm.FmParams(
                w0=jax.device_put(
                    jnp.asarray(params.w0, jnp.float32),
                    self._param_sh.w0,
                ),
                table=jax.device_put(
                    jnp.asarray(params.table, jnp.float32),
                    self._param_sh.table,
                ),
            )
            table_bytes = (
                self.cfg.vocabulary_size * self.cfg.embedding_dim * 4
            )
            err = 0.0  # fp32 serving IS the reference
        else:
            if qt is None:
                # Quantize an fp32 checkpoint host-side, off-traffic
                # (construction or hot-swap staging).
                w0_in = np.float32(np.asarray(params.w0))
                table = np.asarray(params.table, np.float32)
                qt = quant.quantize_table(table, dtype, self._chunk)
                err = self._probe_quant_error(w0_in, table, qt)
            else:
                if qt.dtype != dtype:
                    raise ValueError(
                        f"quantized checkpoint is {qt.dtype} but "
                        f"serve_table_dtype={dtype}; they must match "
                        "(or convert the checkpoint)"
                    )
                if dtype == "int8" and int(qt.chunk) != int(self._chunk):
                    raise ValueError(
                        f"quantized checkpoint uses quant_chunk="
                        f"{qt.chunk} but the server is configured "
                        f"with quant_chunk={self._chunk}; they must "
                        "match (scale indexing is chunk-derived)"
                    )
                # No fp32 reference in hand (the checkpoint IS the
                # quantized table): -1 marks the gauge UNKNOWN rather
                # than leaving a previous placement's number (or a
                # lying 0) standing — documented in the metric schema.
                err = -1.0
            w0d = jax.device_put(
                jnp.asarray(w0_in, jnp.float32), self._param_sh.w0
            )
            if dtype == "bf16":
                placed = fm.FmParams(
                    w0=w0d,
                    table=jax.device_put(
                        jnp.asarray(qt.codes, jnp.bfloat16),
                        self._param_sh.table,
                    ),
                )
            else:
                placed = quant.QuantParams(
                    w0=w0d,
                    codes=jax.device_put(
                        jnp.asarray(qt.codes), self._param_sh.table
                    ),
                    scales=jax.device_put(
                        jnp.asarray(qt.scales, jnp.float32),
                        NamedSharding(self.mesh, P()),
                    ),
                )
            table_bytes = qt.nbytes
        jax.block_until_ready(placed)
        self._g_table_bytes.set(int(table_bytes))
        self._g_quant_err.set(float(err))
        return placed

    def swap(self, params, step: int = 0, keep_prev: bool = False
             ) -> None:
        """Warm hot-swap: stage the new params into standby device
        buffers (off the dispatch lock — traffic keeps scoring the old
        table; a quantized scorer quantizes the incoming fp32 table
        here too), then swap the reference atomically between
        dispatches.  Shapes are unchanged, so the compiled rungs serve
        on with zero recompiles; no request ever sees a torn table.
        ``keep_prev`` retains the replaced model for a later
        :meth:`rollback` (the canary window) at the cost of one standby
        table's memory until :meth:`promote`."""
        placed = self._place(params)  # standby buffers, fully resident
        with self._swap_lock:
            if keep_prev:
                # ANCHOR, don't clobber: if a rollback window is
                # already open (a canary check that died between its
                # reload and its verdict retries the reload), the
                # restorable params must stay the last VETTED ones —
                # overwriting them with the current (unvetted) model
                # would make a later rollback silently a no-op.
                if self._prev is None:
                    self._prev = (self._params, self.step)
            else:
                self._prev = None
            self._params = placed
            self.step = int(step)
        self._c_swaps.add()
        log.info("serving params hot-swapped to step %d", step)

    def _set_model(self, model) -> None:
        self._params = model

    def _compiled(self, b: int):
        fn = self._cache.get(b)
        if fn is not None:
            return fn
        structs = tuple(
            jax.ShapeDtypeStruct((b, self._feat), dt)
            for dt in self._arg_dtypes[:self._n_args]
        )
        p_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self._params
        )
        t0 = time.perf_counter()
        with _quiet_donation(), obs.trace_span("tffm:serve.compile", rung=b):
            fn = self._jit.lower(p_struct, *structs).compile()
        self._account_compile(
            time.perf_counter() - t0, b, expected=b in self._ladder_set
        )
        self._cache[b] = fn
        return fn

    def _warm_rung(self, b: int) -> None:
        self._compiled(b)

    def _dispatch_rung(self, ids, vals, fields, b: int):
        fn = self._compiled(b)
        with self._swap_lock:
            params = self._params
        args = (params, ids, vals, fields)[:1 + self._n_args]
        return self._call_rung(fn, args, b)


class OverlayScorer(_LadderScorer):
    """Huge-V scorer over a ``tiered.npz`` sparse-overlay checkpoint.

    Per chunk: the chunk's unique logical ids gather their current rows
    from the host cold store (written overlay + deterministic init),
    the compact table bucket-pads to O(log) distinct row counts
    (``tiered._bucket``), and ids remap to local indices — identical
    math to a full-table gather without ever materializing [V, D].
    Compile cache keys on (rung, bucketed rows): both dimensions come
    from small ladders, so the executable set stays tiny and every
    compile at a bucketed shape is expected.
    """

    def __init__(self, cfg: FmConfig, w0: float, store, mesh=None,
                 telemetry=None, writer=None, extra_rungs=(), step=0):
        super().__init__(cfg, mesh=mesh, telemetry=telemetry,
                         writer=writer, extra_rungs=extra_rungs)
        self.step = int(step)
        self._lazy_expected_ok = True  # bucket shapes compile lazily
        self._rep = NamedSharding(self.mesh, P())
        self._model = (np.float32(w0), store)
        dim = cfg.embedding_dim
        if cfg.field_num:
            def score_fn(w0, table, ids, vals, fields):
                return self._finish(fm.fm_scores(
                    fm.FmParams(w0=w0, table=table), ids, vals, fields,
                    factor_num=cfg.factor_num, field_num=cfg.field_num,
                ))
        else:
            def score_fn(w0, table, ids, vals):
                return self._finish(fm.fm_scores(
                    fm.FmParams(w0=w0, table=table), ids, vals, None,
                    factor_num=cfg.factor_num, field_num=0,
                ))
        # The compact table is replicated: it is per-chunk data, not the
        # sharded logical table (which never materializes).
        self._jit = jax.jit(
            score_fn,
            in_shardings=(
                (self._rep, self._rep)
                + tuple(
                    NamedSharding(self.mesh, P(mesh_lib.DATA_AXIS, None))
                    for _ in range(self._n_args)
                )
            ),
            donate_argnums=tuple(range(2, 2 + self._n_args)),
        )
        self._dim = dim

    def swap(self, w0: float, store, step: int = 0,
             keep_prev: bool = False) -> None:
        """Hot-swap to a freshly restored overlay (new cold store +
        scalars).  One reference swap between dispatches — a chunk
        gathers its compact table from exactly one store.
        ``keep_prev`` retains the replaced overlay for
        :meth:`rollback` until :meth:`promote`."""
        with self._swap_lock:
            if keep_prev:
                # Same anchoring rule as the dense scorer: an open
                # rollback window keeps pointing at the last vetted
                # overlay across repeated keep_prev swaps.
                if self._prev is None:
                    self._prev = (self._model, self.step)
            else:
                self._prev = None
            self._model = (np.float32(w0), store)
            self.step = int(step)
        self._c_swaps.add()
        log.info("serving overlay hot-swapped to step %d", step)

    def _set_model(self, model) -> None:
        self._model = model

    def _compiled(self, b: int, rows: int):
        key = (b, rows)
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        structs = (
            jax.ShapeDtypeStruct((), np.float32),
            jax.ShapeDtypeStruct((rows, self._dim), np.float32),
        ) + tuple(
            jax.ShapeDtypeStruct((b, self._feat), dt)
            for dt in self._arg_dtypes[:self._n_args]
        )
        t0 = time.perf_counter()
        with _quiet_donation(), obs.trace_span("tffm:serve.compile", rung=b):
            fn = self._jit.lower(*structs).compile()
        # Bucketed compact-table shapes are all expected: the row
        # ladder is log-sized by construction, the rung ladder by
        # config.  Off-ladder RUNGS still flag.
        expected = b in self._ladder_set and rows == tiered_lib._bucket(
            max(1, rows), lo=8
        )
        self._account_compile(time.perf_counter() - t0, key, expected)
        self._cache[key] = fn
        return fn

    def _warm_rung(self, b: int) -> None:
        # Warm the smallest compact-table bucket per rung; larger
        # buckets compile lazily (still expected — log-many of them).
        self._compiled(b, tiered_lib._bucket(1))

    def _dispatch_rung(self, ids, vals, fields, b: int):
        with self._swap_lock:
            w0, store = self._model
        vocab = self.cfg.vocabulary_size
        flat = ids.reshape(-1).astype(np.int64, copy=False)
        safe = np.where((flat >= 0) & (flat < vocab), flat, 0)
        u, inv = np.unique(safe, return_inverse=True)
        rows = tiered_lib._bucket(max(1, len(u)))
        mini = np.zeros((rows, self._dim), np.float32)
        mini[:len(u)] = store.gather(u)
        local_ids = inv.astype(np.int32).reshape(ids.shape)
        fn = self._compiled(b, rows)
        args = (w0, mini, local_ids, vals, fields)[:2 + self._n_args]
        return self._call_rung(fn, args, b)


# ----------------------------------------------------------------------
# checkpoint loading (construction + the hot-swap watcher's reload)
# ----------------------------------------------------------------------


def load_model(cfg: FmConfig, mesh=None):
    """Load the servable model from ``cfg.model_file``.

    Returns ``("dense", step, device FmParams)``, ``("tiered", step,
    (w0, params ColdStore))`` or ``("quant", step, (w0, QuantTable))``
    — whichever format the checkpoint directory holds (the formats are
    mutually exclusive; the save paths enforce that).  Raises if none
    exists.  A quant.npz must match the configured
    ``serve_table_dtype`` / ``quant_chunk`` — refused loudly on
    mismatch (scale indexing is chunk-derived; serving a table under
    the wrong descriptor would silently mis-score).

    Dense restores carry the local mesh's TARGET shardings (the same
    template discipline the trainer/old-predict used): orbax places
    each shard directly where this topology wants it, so a checkpoint
    saved on more devices restores fine on fewer — restoring through
    sharding-less host templates would fall back to the
    sharding-from-file path orbax documents as topology-unsafe.
    """
    if checkpoint.exists_tiered(cfg.model_file):
        step, scalars, stores = checkpoint.restore_tiered(cfg.model_file)
        payload = stores["table"]
        want = tiered_lib._virtual_descriptor(cfg, "table")
        got = payload.get("descriptor")
        if got is not None and got != want:
            raise ValueError(
                f"tiered checkpoint store 'table' was written under a "
                f"different init ({got} != {want}); seed/"
                "init_value_range must match the run that saved it"
            )
        store = tiered_lib._virtual_store(cfg, "table")
        store.import_overlay(payload)
        return "tiered", step, (float(scalars["w0"]), store)
    got = checkpoint.restore_quant(cfg.model_file)
    if got is not None:
        step, w0, qt = got
        desc = qt.descriptor()
        if (
            desc["vocab"] != cfg.vocabulary_size
            or desc["dim"] != cfg.embedding_dim
        ):
            raise ValueError(
                f"quantized checkpoint table is [{desc['vocab']}, "
                f"{desc['dim']}] but the config wants "
                f"[{cfg.vocabulary_size}, {cfg.embedding_dim}]"
            )
        if qt.dtype != cfg.serve_table_dtype:
            raise ValueError(
                f"quantized checkpoint at {cfg.model_file} is "
                f"{qt.dtype} but serve_table_dtype="
                f"{cfg.serve_table_dtype}; set the knob to the "
                "checkpoint's dtype or convert it "
                "(python -m tools.convert_checkpoint)"
            )
        return "quant", step, (np.float32(w0), qt)
    if checkpoint.exists(cfg.model_file):
        mesh = mesh if mesh is not None else mesh_lib.make_mesh(cfg)
        param_sh = mesh_lib.param_sharding(mesh)
        shapes = jax.eval_shape(
            partial(fm.init_params, cfg=cfg), jax.random.PRNGKey(0)
        )
        template = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sh
            ),
            shapes, param_sh,
        )
        params, step = checkpoint.restore_params(cfg.model_file, template)
        return "dense", step, fm.FmParams(*params)
    raise ValueError(
        f"no servable checkpoint at {cfg.model_file} (neither the "
        "dense params/opt dirs nor a tiered.npz overlay)"
    )


def make_scorer(cfg: FmConfig, mesh=None, telemetry=None, writer=None,
                extra_rungs=()):
    """Build the right scorer for whatever ``cfg.model_file`` holds."""
    fmt, step, model = load_model(cfg, mesh=mesh)
    if fmt == "tiered":
        w0, store = model
        return OverlayScorer(
            cfg, w0, store, mesh=mesh, telemetry=telemetry,
            writer=writer, extra_rungs=extra_rungs, step=step,
        )
    # "dense" passes fm.FmParams, "quant" a (w0, QuantTable) pair —
    # FixedShapeScorer places either per serve_table_dtype.
    return FixedShapeScorer(
        cfg, model, mesh=mesh, telemetry=telemetry, writer=writer,
        extra_rungs=extra_rungs, step=step,
    )
