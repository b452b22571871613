#!/usr/bin/env python3
"""PERF.md 7.1: does the default tile training step copy both whole
tables into T(8,128) on the chip, and does the allocator's peak count it?

Traces a few trainer dispatches at V=2^23, B=4096 (``sparse_apply`` as
given, default tile) and prints the device operations that took most
time with the allocator's readings.  A probe, not a cell.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=1 << 23)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--apply", default="tile")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU: control flow only, no device number")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from fmbench import harness

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    device = harness.look_for_chip(1, args.rehearse)
    if not args.rehearse:
        harness.enable_compile_cache()
    import jax

    train = harness.load_by_path("drivers", "train")
    base = harness.load_json(os.path.join(
        HERE, "configs", "criteo1tb-fm-shard2.json"))
    base["cfg"].update({"vocabulary_size": args.vocab,
                        "batch_size": args.batch,
                        "sparse_apply": args.apply})
    base["harness"]["train_lines"] = args.batch * 8
    work = harness.work_dir("tile-copy-probe")
    cfg_path, keys, _ = train.make_inputs(work, base, 7, False, "")
    tracer = harness.TraceWindow(work, not args.rehearse)
    obs = train.StepObserver(args.seconds, tracer, "", time.time())
    before = jax.local_devices()[0].memory_stats()
    final = train.drive_job(cfg_path, keys["metrics_file"], obs, tracer)
    after = jax.local_devices()[0].memory_stats()
    reduced = tracer.reduce()
    res = final.get("resource", {})
    if reduced is None:
        print(json.dumps({"probe": "tile_copy", "rehearsal": True,
                          "dispatches": obs.win_dispatches,
                          "window_calls": obs.calls_at}))
        return 0
    print(json.dumps({
        "probe": "tile_copy", "device": device, "vocab": args.vocab,
        "batch": args.batch, "sparse_apply": args.apply,
        "dispatches": obs.win_dispatches,
        "window_s": obs.win_t1 - obs.win_t0,
        "peak_bytes_in_use": after.get("peak_bytes_in_use"),
        "bytes_in_use_before": before.get("bytes_in_use"),
        "bytes_in_use_after": after.get("bytes_in_use"),
        "bytes_limit": after.get("bytes_limit"),
        "temp_bytes_compiler": res.get("temp_bytes"),
        "busy_s": reduced["busy_s"], "trace_window_s": reduced["window_s"],
        "programs": reduced["programs"],
        "device_ops": reduced["device_ops"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
