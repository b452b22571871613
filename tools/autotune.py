#!/usr/bin/env python
"""Offline kernel-autotune driver for the interaction hot path.

Two modes:

- **Pre-populate** (default, needs a config): resolve the
  interaction impl for the config's train and serve shapes exactly as
  a run with ``interaction_impl=auto`` would, and persist the
  decisions to the autotune cache — so the actual run (or a whole
  replica fleet sharing the cache file) starts with zero measurement.

      python tools/autotune.py model.cfg
      python tools/autotune.py model.cfg --cache /shared/autotune_cache.json

- **--check** (no config needed; tools/verify.sh wires this): validate
  the autotuner's own invariants on the current backend —

  1. on CPU, ``auto`` must resolve to ``reference`` WITHOUT running a
     single measurement (the near-zero-overhead contract
     tests/test_autotune.py pins);
  2. a forced multi-candidate measurement must pick a parity-gated
     winner and a second resolve must hit the cache (0 additional
     measurements);
  3. an existing cache file (``--cache``, or the config's default
     location) must be self-consistent: readable, versioned, every
     entry's impl a known name.

  Exit 0 = all hold; nonzero with a message otherwise.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    p.add_argument("cfg", nargs="?", default=None,
                   help="config file to pre-populate the cache for "
                        "(omit with --check)")
    p.add_argument("--cache", default=None, metavar="PATH",
                   help="autotune cache file (default: the config's "
                        "default_cache_path; with --check and no cfg, "
                        "no file check unless given)")
    p.add_argument("--check", action="store_true",
                   help="validate autotuner invariants + cache "
                        "self-consistency instead of pre-populating")
    p.add_argument("--context", choices=["train", "serve", "both"],
                   default="both",
                   help="which shapes to pre-populate (default both)")
    return p


def _load_cfg(path: str):
    from fast_tffm_tpu.config import load_config

    cfg = load_config(path)
    if cfg.interaction_impl not in ("", "auto"):
        print(f"note: config pins interaction_impl="
              f"{cfg.interaction_impl}; the run will not consult the "
              "cache, but pre-populating anyway for auto consumers")
    import dataclasses

    # Pre-population measures what `auto` WOULD choose regardless of
    # what the file currently pins.
    return dataclasses.replace(cfg, interaction_impl="auto",
                               interaction="")


def _prepopulate(args) -> int:
    from fast_tffm_tpu.ops import autotune

    cfg = _load_cfg(args.cfg)
    cache = (
        args.cache if args.cache is not None
        else autotune.default_cache_path(cfg)
    )
    if not cache:
        print("no cache path resolvable (set --cache, compile_cache_dir "
              "or model_file); decisions would not persist", file=sys.stderr)
        return 2
    contexts = (
        ("train", "serve") if args.context == "both" else (args.context,)
    )
    for context in contexts:
        d = autotune.resolve(cfg, context=context, cache_path=cache)
        times = (
            " ".join(f"{k}={v}ms" for k, v in sorted(d.times_ms.items()))
            or "no measurement needed"
        )
        print(f"{context}: {d.impl} ({d.source}; {times})")
    print(f"cache: {cache}")
    return 0


def _check(args) -> int:
    import dataclasses

    import numpy as np

    from fast_tffm_tpu.config import FmConfig, load_config
    from fast_tffm_tpu.ops import autotune
    from fast_tffm_tpu.platform import is_tpu_backend

    failures = []

    # (1) + (2) run against a small synthetic config and a throwaway
    # in-memory cache so --check never touches a real cache file.
    os.environ["FAST_TFFM_AUTOTUNE_CACHE"] = ""
    cfg = FmConfig(vocabulary_size=512, factor_num=4, max_features=8,
                   batch_size=64, interaction_impl="auto")
    d = autotune.resolve(cfg, context="train")
    n0 = autotune.measurement_count()
    if not is_tpu_backend():
        if d.impl != "reference":
            failures.append(
                f"CPU auto resolved to {d.impl!r}, expected reference"
            )
        if d.source not in ("single_candidate",):
            failures.append(
                f"CPU auto source {d.source!r}, expected "
                "single_candidate (zero measurement)"
            )
        if n0 != 0:
            failures.append(
                f"CPU auto ran {n0} measurement(s), expected 0"
            )
    # (2) forced multi-candidate measurement + cache hit.  "packed" is
    # runnable on every backend (pure XLA), so this exercises the full
    # measure -> parity-gate -> persist -> hit loop even on CPU.
    cands = ("reference", "packed")
    d1 = autotune.resolve(cfg, context="train", candidates=cands)
    n1 = autotune.measurement_count()
    if d1.source != "measured" or n1 <= n0:
        failures.append(
            f"forced measurement did not measure (source={d1.source}, "
            f"count {n0}->{n1})"
        )
    if d1.impl not in ("reference", "packed"):
        failures.append(f"measured winner {d1.impl!r} not a candidate")
    bad = [k for k, v in d1.parity_err.items()
           if v > autotune.PARITY_TOL and k in d1.times_ms]
    if bad:
        failures.append(f"parity-gate leak: {bad} timed despite err>tol")
    d2 = autotune.resolve(cfg, context="train", candidates=cands)
    if d2.source != "cache" or autotune.measurement_count() != n1:
        failures.append(
            f"second resolve missed the cache (source={d2.source})"
        )
    if d2.impl != d1.impl:
        failures.append(
            f"cache returned {d2.impl!r} but measurement chose {d1.impl!r}"
        )

    # (3) optional cache-file self-consistency.
    cache = args.cache
    if cache is None and args.cfg:
        fcfg = load_config(args.cfg)
        fcfg = dataclasses.replace(fcfg)
        del os.environ["FAST_TFFM_AUTOTUNE_CACHE"]
        cache = autotune.default_cache_path(fcfg)
    if cache and os.path.exists(cache):
        entries = autotune.load_cache(cache)
        if not entries:
            failures.append(
                f"cache file {cache} exists but holds no valid entries "
                "(corrupt or version drift)"
            )
        for key, e in (entries or {}).items():
            if not isinstance(e, dict) or e.get("impl") not in autotune.INTERNAL:
                failures.append(f"cache entry {key!r} invalid: {e!r}")
        if not failures:
            print(f"cache {cache}: {len(entries)} entrie(s) OK")

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("autotune check OK (backend: %s)" % (
        "tpu" if is_tpu_backend() else "cpu/other"
    ))
    return 0


def main(argv=None) -> int:
    args = _build_argparser().parse_args(argv)
    if args.check:
        return _check(args)
    if not args.cfg:
        print("a config file is required unless --check", file=sys.stderr)
        return 2
    return _prepopulate(args)


if __name__ == "__main__":
    sys.exit(main())
