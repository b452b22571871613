"""Backend gates and process-wide jax settings.

The program runs in two places: on the CPU (tests and rehearsals, with
``JAX_PLATFORMS=cpu``; :func:`pin_cpu` also gives a process N virtual
CPU devices for the multi-chip paths) and on a TPU, one process per
chip.  Everything that must differ between the two asks this module:
:func:`is_tpu_backend` / :func:`use_interpret` gate the Pallas kernels,
and :func:`enable_compile_cache` places jax's persistent compilation
cache.
"""

from __future__ import annotations

import contextlib
import logging
import os
import re

log = logging.getLogger(__name__)

_FLAG = "xla_force_host_platform_device_count"


def is_tpu_backend() -> bool:
    """True when the default jax backend executes on a TPU.  Used to gate
    Pallas-vs-interpret and the tile-vs-scatter sparse apply choice."""
    import jax

    return jax.default_backend() == "tpu"


_force_compiled = False


def use_interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU (correctness tool; far
    slower than compiled Mosaic).  The ONE gate all kernel call sites
    share."""
    if _force_compiled:
        return False
    return not is_tpu_backend()


@contextlib.contextmanager
def force_compiled():
    """Trace Pallas calls as compiled (Mosaic) even off-TPU.

    Exists for cross-platform LOWERING tests: Mosaic's jaxpr->MLIR pass
    runs at jax lowering time, so ``jax.export(..., platforms=['tpu'])``
    under this context surfaces "Unimplemented primitive in Pallas TPU
    lowering" errors on a CPU-only machine — the exact failure class that
    interpret-mode tests structurally cannot catch (it lost a whole
    hardware run in round 3).  Never use it to *execute* kernels off-TPU.
    """
    global _force_compiled
    prev = _force_compiled
    _force_compiled = True
    try:
        yield
    finally:
        _force_compiled = prev


def ffm_compute_dtype(compute_dtype):
    """The dtype FFM's einsum operands may use on the current target.

    XLA:CPU's DotThunk cannot EXECUTE bf16 x bf16 -> f32 dots (runtime
    UNIMPLEMENTED; inside a shard_map the aborting device strands the
    others at the next collective).  The TPU MXU runs them natively, so
    bf16 passes through on a TPU backend — and under
    :func:`force_compiled` (cross-platform lowering FOR TPU on a CPU
    host), where falling back would make lowering tests silently
    validate the f32 program instead of the advertised bf16 one.

    The ONE copy of this gate; fm.ffm_scores_from_rows and the shardmap
    FFM step both call it.
    """
    import jax.numpy as jnp

    if compute_dtype == jnp.bfloat16 and not (
        _force_compiled or is_tpu_backend()
    ):
        return jnp.float32
    return compute_dtype


def ffm_matmul_precision(compute_dtype):
    """What FFM's einsums ask of the MXU for operands of
    ``compute_dtype``: float32 goes through whole (``HIGHEST``; the
    TPU's default is ONE bf16 pass, which is what
    ``compute_dtype=bfloat16`` asks for and float32 does not); bf16
    keeps the default, its products being exact in the f32 accumulator
    either way.  The ONE copy: the closed-form op
    (ops.interaction) and the oracle (models.fm) share it."""
    import jax
    import jax.numpy as jnp

    return jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32 else None


# ------------------------------------------------- persistent compile cache
#
# jax's on-disk compilation cache: a restart (or a replica spawn on the
# serve fleet) replays its warmup compiles from disk instead of
# re-lowering through XLA — the multi-second ladder warmup becomes a
# file read.  Placement: where JAX_COMPILATION_CACHE_DIR is set, that
# directory is the cache and nothing in this program names another (the
# path is part of what a machine's owner provisions and finds again);
# otherwise the ``compile_cache_dir`` cfg knob, and for the repo's own
# scripts the fixed REPO_COMPILE_CACHE_DIR (chip_smoke.py; benchmarks/
# names the same directory itself).
# The monitoring listener counts hit/miss events so the
# zero-fresh-lowers contract of a warm spawn is checkable (tests + the
# serve log line), not assumed.

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"
# One fixed path inside the checkout — never a temp name, pid or time:
# the path is part of the cache's key, so a directory that moves never
# hits.
REPO_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

_compile_cache_dir: str | None = None
_compile_cache_events = {"hits": 0, "misses": 0}
_compile_cache_listener_installed = False


def compile_cache_dir(path: str = "") -> str:
    """The directory the persistent cache uses when a caller asks for
    ``path``: the environment's, where JAX_COMPILATION_CACHE_DIR is
    set, else ``path`` itself ("" = no cache)."""
    return os.environ.get(ENV_COMPILE_CACHE, "") or path


def _reset_jax_cache() -> None:
    # jax initializes its cache object AT MOST ONCE per process and
    # latches the dir it saw then — a process that compiled anything
    # before would silently keep its old cache (or none).  Reset so the
    # next compile re-initializes against the configured dir.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def enable_compile_cache(path: str = "") -> bool:
    """Turn on jax's persistent compilation cache at
    ``compile_cache_dir(path)`` (created if missing) and start counting
    cache hit/miss events.  Idempotent; no directory from either source
    turns nothing on (returns False).  The min-size/min-time floors are
    dropped so EVERY executable persists — this project's rung/step
    compiles are small but warmup-critical."""
    global _compile_cache_dir, _compile_cache_listener_installed
    want = compile_cache_dir(path)
    if not want:
        return False
    import jax

    if path and want != path:
        log.info(
            "%s=%s wins over compile_cache_dir=%s",
            ENV_COMPILE_CACHE, want, path,
        )
    if _compile_cache_dir != want:
        os.makedirs(want, exist_ok=True)
        # With the env var set at start-up jax already holds this very
        # value; the update only matters for the cfg knob (and for an
        # env var set after jax was imported).
        if jax.config.jax_compilation_cache_dir != want:
            jax.config.update("jax_compilation_cache_dir", want)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _reset_jax_cache()
        _compile_cache_dir = want
        log.info("persistent compilation cache enabled at %s", want)
    if not _compile_cache_listener_installed:
        def _listener(event, **kw):  # noqa: ANN001 - jax callback API
            if event == "/jax/compilation_cache/cache_hits":
                _compile_cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                _compile_cache_events["misses"] += 1

        jax.monitoring.register_event_listener(_listener)
        _compile_cache_listener_installed = True
    return True


@contextlib.contextmanager
def metadata_in_cache_key():
    """Compile inside this block with the program's metadata (names,
    ``jax.named_scope`` parts, source places) in the persistent cache's
    key.  jax's key drops the metadata by default, and an executable
    loaded from the cache carries the metadata of the program that
    filled the entry: two train steps that differ only in their scopes
    would share an entry, and the compile record's scope table
    (``obs/scopes.py``), read from the executable, would name the other
    program's parts.  Only around the compile whose metadata is read:
    the source places in a key depend on the caller's stack, so keyed
    this way every other program would miss the cache wherever it is
    called from somewhere new.  Whoever turned the cache on (this module
    or a harness around the program), and a no-op without one."""
    import jax

    prev = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        yield
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", prev)


def disable_compile_cache() -> None:
    """Turn the persistent cache back off (tests restore global state;
    the event listener stays — it only counts)."""
    global _compile_cache_dir
    if _compile_cache_dir is None:
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache()  # drop the latched cache object (see enable)
    _compile_cache_dir = None


def compile_cache_stats() -> dict:
    """{'dir', 'hits', 'misses'} — cumulative persistent-cache events
    since the listener was installed.  A warm replica spawn with a
    populated cache performs zero fresh lowers: its warmup adds hits,
    never misses."""
    return {
        "dir": _compile_cache_dir or "",
        "hits": _compile_cache_events["hits"],
        "misses": _compile_cache_events["misses"],
    }


def pin_cpu(n_devices: int | None = None) -> None:
    """Force the CPU platform, optionally with ``n_devices`` virtual CPUs.

    Must run before jax initializes a backend: the XLA flag is read at CPU
    client creation, and a backend cached from an earlier init cannot be
    replaced.  An existing ``--xla_force_host_platform_device_count`` flag
    with a different value is REPLACED (a stale count would make
    multi-device dry runs assert on device count).
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--{_FLAG}={n_devices}"
        if _FLAG in flags:
            flags = re.sub(rf"--{_FLAG}=\d+", want, flags)
        else:
            flags = (flags + " " + want).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
