"""Kernel autotuner: measured promotion of the interaction hot path.

The repo carries three implementations of the FM interaction
scores/grads (ops/interaction.py's reference elementwise math, the
Mosaic kernels in ops/fm_pallas.py, and the packed flat-layout
one-hot-matmul variant) plus the int8 fused-gather serving forward
(models.fm.fm_scores_dequant).  Which one is fastest depends on the
run's actual shapes (batch, F, D), the backend, and the table dtype —
the hardware window used to A/B them by hand.  This module is the
selection mechanism:

- ``resolve(cfg, context=...)`` maps the ``interaction_impl`` knob to a
  concrete implementation.  Pins (``reference``/``pallas``/``packed``)
  bypass measurement entirely; ``auto`` benchmarks the candidate set
  for the run's shapes, keeps only candidates that pass an element-wise
  parity gate against reference (scores AND grads in the train
  context), and picks the fastest survivor.
- Decisions persist in a per-backend/shape JSON cache
  (``autotune_cache.json``) keyed on (context, backend, batch, F, D,
  field_num, table dtype, compute dtype, jax version) — any drift in
  the key re-measures; a hit skips measurement entirely, so replica
  fleets and restarts pay nothing.
- Every decision is observable: a ``record: autotune`` JSONL entry
  (candidates, per-candidate times, winner, parity error) via
  :func:`write_record`, and ``kernel_impl`` in the run header / serve
  block.

Off-TPU the candidate set collapses to ``("reference",)`` — the Mosaic
kernels would run in interpret mode and the packed one-hot matmuls are
a CPU pessimization, so reference provably wins at zero measurement
cost (tests/test_autotune.py pins that no candidate is timed).  On a
TPU backend all candidates enter measurement — that is the point.

Offline: ``python tools/autotune.py`` pre-populates the cache for a
config; ``--check`` validates cache self-consistency and the
reference-wins-on-CPU invariant (wired into tools/verify.sh).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time

log = logging.getLogger(__name__)

__all__ = [
    "Decision", "resolve", "write_record", "default_candidates",
    "cache_key", "default_cache_path", "load_cache", "save_cache",
    "measurement_count", "PARITY_TOL", "INTERNAL", "USER",
]

# User-facing impl name -> ops.interaction dispatch name.  "packed" is
# the flat [B, F*D] one-hot-matmul layout (ops.interaction._scores_flat
# — the XLA-fused twin of the packed-K2 kernel layout, see
# EMBEDDING.md "Packed layout").
INTERNAL = {"reference": "jnp", "pallas": "pallas", "packed": "flat"}
USER = {v: k for k, v in INTERNAL.items()}

# Element-wise parity gate, pinned: a candidate whose scores or grads
# drift beyond TOL * max(1, |reference|_max) from reference is rejected
# no matter how fast it measured.  2e-3 relative covers f32
# accumulation-order drift between the elementwise, MXU-matmul, and
# Mosaic formulations (their observed drift is ~1e-6..1e-5) while
# rejecting anything actually wrong.
PARITY_TOL = 2e-3

# Module-level measurement counter: bumped once per candidate actually
# benchmarked.  Tests pin cache hits / pins / single-candidate
# resolutions to "skips measurement" through this.
_MEASUREMENTS = 0

_CACHE_VERSION = 1
_MEM_CACHE: dict = {}  # in-process cache (works with cache_path="")


def measurement_count() -> int:
    """How many candidate benchmarks ran in this process."""
    return _MEASUREMENTS


@dataclasses.dataclass
class Decision:
    """One interaction-impl selection, however it was reached."""

    impl: str  # user-facing: reference | pallas | packed
    interaction: str  # ops.interaction dispatch name: jnp | pallas | flat
    source: str  # pinned | legacy | single_candidate | cache | measured
    context: str  # train | serve
    key: str  # the cache key (empty for pins/legacy)
    candidates: tuple = ()
    times_ms: dict = dataclasses.field(default_factory=dict)
    parity_err: dict = dataclasses.field(default_factory=dict)
    cache_file: str = ""


# ---------------------------------------------------------------- keys


def cache_key(context: str, backend: str, batch: int, features: int,
              dim: int, field_num: int, table_dtype: str,
              compute_dtype: str, jax_version: str | None = None) -> str:
    """The persistent-cache key: every axis that can change the winner.
    A drift in ANY component (shape, dtype, backend, jax version) is a
    miss — stale winners never leak across upgrades or re-shapes."""
    if jax_version is None:
        import jax

        jax_version = jax.__version__
    return "|".join((
        context, backend, f"b{int(batch)}", f"f{int(features)}",
        f"d{int(dim)}", f"p{int(field_num)}", table_dtype,
        compute_dtype, f"jax{jax_version}",
    ))


def default_cache_path(cfg) -> str:
    """Where the persistent cache lives for this run: the
    ``FAST_TFFM_AUTOTUNE_CACHE`` env override (empty string = memory
    only), else alongside the persistent compile cache (the directory
    ``platform.compile_cache_dir`` resolves: JAX_COMPILATION_CACHE_DIR
    wins over the cfg knob), else next to the model checkpoint (the
    serve fleet reads the same file)."""
    env = os.environ.get("FAST_TFFM_AUTOTUNE_CACHE")
    if env is not None:
        return env
    from fast_tffm_tpu.platform import compile_cache_dir

    cc = compile_cache_dir(getattr(cfg, "compile_cache_dir", ""))
    if cc:
        return os.path.join(cc, "autotune_cache.json")
    if getattr(cfg, "model_file", ""):
        d = os.path.dirname(os.path.abspath(cfg.model_file))
        return os.path.join(d, "autotune_cache.json")
    return ""


def load_cache(path: str) -> dict:
    """Read a cache file; corruption or absence is an empty cache (the
    autotuner re-measures — never a crash)."""
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or doc.get("version") != _CACHE_VERSION:
            return {}
        entries = doc.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError) as e:
        log.warning("autotune cache %s unreadable (%s); re-measuring",
                    path, e)
        return {}


def save_cache(path: str, entries: dict) -> None:
    """Atomic write (tmp + rename): a killed run never leaves a torn
    cache behind for the next one to trip on."""
    if not path:
        return
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": _CACHE_VERSION, "entries": entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:  # persistence is an optimization, not a need
        log.warning("autotune cache write to %s failed: %s", path, e)


# ---------------------------------------------------------- candidates


def default_candidates(field_num: int = 0) -> tuple:
    """The candidate set for the current backend.

    FFM (field_num > 0) always uses its closed-form op — impl routing
    does not apply, so reference is the only candidate.  Off-TPU the
    Mosaic kernels execute in interpret mode (orders of magnitude
    slower) and the packed one-hot matmuls pessimize the VPU-less CPU
    path, so reference wins by construction and the single-candidate
    fast path skips measurement entirely — the provably-near-zero
    overhead the CPU acceptance gate pins.  On TPU every selectable
    impl enters measurement.
    """
    if field_num:
        return ("reference",)
    from fast_tffm_tpu.platform import is_tpu_backend

    if is_tpu_backend():
        return ("reference", "pallas", "packed")
    return ("reference",)


def _candidate_fns(cfg, context: str, batch: int, table_dtype: str):
    """(make_fn, args): ``make_fn(user_impl)`` returns a jitted callable
    of ``args`` whose outputs are element-wise comparable across
    impls.

    Train context: forward scores + closed-form row grads through
    ``ops.interaction.fm_interaction`` — the fused-scan step's actual
    hot pair.  Serve context: the forward-only score path INCLUDING the
    gather (and, for an int8 table, the fused dequant gather of
    ``fm.fm_scores_dequant``) — what a compiled rung runs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.ops import interaction

    b, feat, dim = int(batch), cfg.max_features, cfg.embedding_dim
    rng = np.random.default_rng(0xA070)
    vals = jnp.asarray(rng.uniform(0.1, 1.0, (b, feat)).astype(np.float32))

    if context == "train":
        rows = jnp.asarray(
            rng.uniform(-0.1, 0.1, (b, feat, dim)).astype(np.float32)
        )

        def make(user_impl):
            impl = INTERNAL[user_impl]

            def f(r, v):
                scores = interaction.fm_interaction(r, v, impl)
                grads = jax.grad(
                    lambda rr: jnp.sum(interaction.fm_interaction(rr, v, impl))
                )(r)
                return scores, grads

            return jax.jit(f)

        return make, (rows, vals)

    # serve: gather + score over a representative table slice (capped —
    # gather cost scales with the batch, not the vocabulary).
    vocab = min(cfg.vocabulary_size, 1 << 14)
    table = rng.uniform(-0.1, 0.1, (vocab, dim)).astype(np.float32)
    ids = jnp.asarray(
        rng.integers(0, vocab, (b, feat)).astype(np.int32)
    )
    w0 = jnp.float32(0.0)

    if table_dtype == "int8":
        from fast_tffm_tpu.ops import quant

        qt = quant.quantize_table(table, "int8", cfg.quant_chunk)
        codes = jnp.asarray(qt.codes)
        scales = jnp.asarray(qt.scales, jnp.float32)
        chunk = int(qt.chunk)

        def make(user_impl):
            impl = INTERNAL[user_impl]
            impl = None if impl == "jnp" else impl

            def f(i, v):
                return fm.fm_scores_dequant(
                    w0, codes, scales, chunk, i, v, None,
                    factor_num=cfg.factor_num, field_num=0, impl=impl,
                )

            return jax.jit(f)

        return make, (ids, vals)

    tbl = jnp.asarray(
        table, jnp.bfloat16 if table_dtype == "bf16" else jnp.float32
    )
    params = fm.FmParams(w0=w0, table=tbl)

    def make(user_impl):
        impl = INTERNAL[user_impl]
        impl = None if impl == "jnp" else impl

        def f(i, v):
            return fm.fm_scores(
                params, i, v, None,
                factor_num=cfg.factor_num, field_num=0, impl=impl,
            )

        return jax.jit(f)

    return make, (ids, vals)


def _flat_outputs(out):
    import jax

    return [x for x in jax.tree_util.tree_leaves(out)]


def _parity_error(out, ref_out) -> float:
    """Max element-wise |candidate - reference| over every output,
    relative to max(1, |reference|_max)."""
    import numpy as np

    worst = 0.0
    for a, b in zip(_flat_outputs(out), _flat_outputs(ref_out)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        scale = max(1.0, float(np.max(np.abs(b))) if b.size else 1.0)
        worst = max(worst, float(np.max(np.abs(a - b))) / scale)
    return worst


def _time_ms(fn, args, reps: int = 3, inner: int = 5) -> float:
    """Best-of-``reps`` mean wall time per call (ms), post-compile."""
    import jax

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1000.0


def _measure(cfg, context: str, batch: int, table_dtype: str,
             candidates, candidate_fns=None):
    """Benchmark every candidate at the run's shapes; returns
    (winner_user_name, times_ms, parity_err).  Reference is always the
    parity oracle and always survives the gate."""
    global _MEASUREMENTS
    import jax

    if candidate_fns is None:
        make, args = _candidate_fns(cfg, context, batch, table_dtype)
    else:
        make, args = candidate_fns
    names = list(candidates)
    if "reference" not in names:
        names.insert(0, "reference")
    ref_fn = make("reference")
    ref_out = ref_fn(*args)
    jax.block_until_ready(ref_out)
    times_ms: dict = {}
    parity: dict = {}
    survivors = []
    for name in names:
        fn = ref_fn if name == "reference" else make(name)
        # A candidate that fails to compile or run RAISES: losing
        # quietly would let a kernel the chip's compiler refuses give
        # way to the reference with nothing in the result to show it.
        out = fn(*args)
        jax.block_until_ready(out)
        _MEASUREMENTS += 1
        err = 0.0 if name == "reference" else _parity_error(out, ref_out)
        parity[name] = round(err, 9)
        if err > PARITY_TOL:
            log.warning(
                "autotune candidate %s FAILED the parity gate "
                "(err %.3g > %.3g) and is excluded from selection",
                name, err, PARITY_TOL,
            )
            continue
        times_ms[name] = round(_time_ms(fn, args), 4)
        survivors.append(name)
    winner = min(survivors, key=lambda n: times_ms[n])
    return winner, times_ms, parity


# ------------------------------------------------------------- resolve


def resolve(cfg, *, context: str = "train", batch: int | None = None,
            writer=None, cache_path: str | None = None,
            candidates=None, table_dtype: str | None = None,
            jax_version: str | None = None,
            candidate_fns=None) -> Decision:
    """Map ``cfg.interaction_impl`` to a concrete implementation.

    Pins and the legacy surface never measure.  ``auto`` measures only
    when the candidate set has more than one entry AND the persistent
    cache has no valid entry for this exact key.  ``writer`` (a JSONL
    writer) gets one ``record: autotune`` entry per decision.

    ``candidates`` / ``candidate_fns`` / ``jax_version`` exist for
    tests and the offline CLI: forcing a multi-candidate measurement on
    CPU, injecting a deliberately-wrong candidate at the parity gate,
    and exercising key drift without a jax upgrade.
    """
    import jax

    knob = cfg.interaction_impl
    if batch is None:
        batch = cfg.batch_size
    if table_dtype is None:
        table_dtype = (
            cfg.serve_table_dtype if context == "serve" else "fp32"
        )
    if knob in ("reference", "pallas", "packed"):
        d = Decision(impl=knob, interaction=INTERNAL[knob],
                     source="pinned", context=context, key="")
    elif knob != "auto":  # "" — the legacy interaction/use_pallas surface
        internal = cfg.interaction_resolved
        d = Decision(impl=USER.get(internal, "reference"),
                     interaction=internal, source="legacy",
                     context=context, key="")
    else:
        cands = tuple(
            candidates if candidates is not None
            else default_candidates(cfg.field_num)
        )
        key = cache_key(
            context, jax.default_backend(), batch, cfg.max_features,
            cfg.embedding_dim, cfg.field_num, table_dtype,
            cfg.compute_dtype, jax_version,
        )
        if cache_path is None:
            cache_path = default_cache_path(cfg)
        if len(cands) == 1:
            d = Decision(impl=cands[0], interaction=INTERNAL[cands[0]],
                         source="single_candidate", context=context,
                         key=key, candidates=cands,
                         cache_file=cache_path)
        else:
            entries = dict(_MEM_CACHE)
            entries.update(load_cache(cache_path))
            hit = entries.get(key)
            if (
                isinstance(hit, dict)
                and hit.get("impl") in INTERNAL
                and tuple(hit.get("candidates", ())) == cands
            ):
                d = Decision(
                    impl=hit["impl"], interaction=INTERNAL[hit["impl"]],
                    source="cache", context=context, key=key,
                    candidates=cands,
                    times_ms=dict(hit.get("times_ms") or {}),
                    parity_err=dict(hit.get("parity_err") or {}),
                    cache_file=cache_path,
                )
            else:
                winner, times_ms, parity = _measure(
                    cfg, context, batch, table_dtype, cands,
                    candidate_fns=candidate_fns,
                )
                d = Decision(
                    impl=winner, interaction=INTERNAL[winner],
                    source="measured", context=context, key=key,
                    candidates=cands, times_ms=times_ms,
                    parity_err=parity, cache_file=cache_path,
                )
                entry = {
                    "impl": winner, "candidates": list(cands),
                    "times_ms": times_ms, "parity_err": parity,
                    "written": time.time(),
                }
                _MEM_CACHE[key] = entry
                entries[key] = entry
                save_cache(cache_path, entries)
    log.info(
        "autotune[%s]: interaction_impl=%s -> %s (%s)%s",
        context, knob or "<legacy>", d.impl, d.source,
        f" times_ms={d.times_ms}" if d.times_ms else "",
    )
    if writer is not None:
        write_record(writer, d)
    return d


def write_record(writer, d: Decision) -> None:
    """One ``record: autotune`` JSONL entry per decision — the
    observability contract OBSERVABILITY.md's record schema pins."""
    try:
        writer.write({
            "record": "autotune",
            "time": time.time(),
            "impl": d.impl,
            "source": d.source,
            "context": d.context,
            "key": d.key,
            "candidates": list(d.candidates),
            "times_ms": d.times_ms,
            "parity_err": d.parity_err,
        })
    except Exception as e:  # noqa: BLE001 - never kill a run over a record
        log.warning("autotune record write failed: %s", e)
