#!/usr/bin/env bash
# One-command correctness gate: the static audits (tier-1 markers, obs
# metric-name drift), the live-observability smoke, and the PINNED
# tier-1 pytest selection of ROADMAP.md under the driver's flags — one
# command, so "it passed locally" and "the gate passed" can never mean
# different commands.
#
#   tools/verify.sh            # lint + obs smoke + full tier-1 suite
#   tools/verify.sh --audit    # static analysis only (milliseconds, no jax)
#
# Exit: 0 = every stage ok; nonzero otherwise.  The DOTS_PASSED line at
# the end is the machine-readable passed count the driver compares
# against the recorded baseline.

set -u
cd "$(dirname "$0")/.."
tmp="${TMPDIR:-/tmp}"

echo "== static analysis (python -m tools.lint; rule catalog: LINTING.md) =="
# All seven analyzers: thread/queue/SHM/server lifecycle, donation/
# aliasing, blocking-under-lock, knob drift, record-schema drift, plus
# the folded-in tier-1 marker audit (T1001) and obs metric-name drift
# (OB001/OB002) that used to run here as separate check_tier1/check_obs
# invocations.  Fails on any NEW finding (tools/lint/baseline.txt
# grandfathers old ones) — run before anything jax-heavy.
python -m tools.lint || exit 1

if [ "${1:-}" = "--audit" ]; then
    exit 0
fi

echo
echo "== kernel-autotune invariants (tools/autotune.py --check) =="
# The autotuner's own contract on this backend: CPU `auto` resolves to
# reference with ZERO measurements (the near-zero-overhead budget), a
# forced multi-candidate measurement parity-gates and caches its
# winner, and any cache file on disk is self-consistent.
JAX_PLATFORMS=cpu python tools/autotune.py --check || exit 1

echo
echo "== fleet parity gate (tools/parity_probe.py --fleet-gate) =="
# Two real gloo ranks (one model column each) vs the single-process
# (1x2) reference: per-shard table hashes must match bitwise at init
# and after each of 3 dispatches.  Catches cross-process init drift
# and step drift in seconds, long before a full fleet run would.
JAX_PLATFORMS=cpu python tools/parity_probe.py --fleet-gate \
    --dispatches 3 --out "$tmp/_fleet_gate.jsonl" || exit 1

echo
echo "== live observability + serving smoke (tools/obs_smoke.py) =="
# A real CLI run with --status_port: /metrics must serve parseable
# Prometheus text (incl. the resource block + tffm_build_info) and
# /status the heartbeat JSON, mid-run; /debug/threadz must dump every
# thread; /profile must capture once and 409 a concurrent request.
# Then the serve smoke against the checkpoint that run wrote:
# run_tffm.py serve must score over the socket, expose tffm_serve_*
# on /metrics, and hot-swap once when a second training run
# republishes the checkpoint manifest.  Then the incident smoke: an
# injected alert breach must dump a valid blackbox bundle,
# report.py --incident must render it, and the TFC1 traffic capture
# must replay bitwise against a fresh server (tools/replay.py).
JAX_PLATFORMS=cpu python tools/obs_smoke.py || exit 1

echo
echo "== quantized-table smoke (tools/quant_smoke.py) =="
# The migration story end-to-end through the real CLI: train with a
# bf16 cold store (~20 steps), predict the fp32 reference, convert the
# checkpoint to int8 (tools/convert_checkpoint), serve it quantized,
# and tolerance-check the served scores against fp32 over the socket.
JAX_PLATFORMS=cpu python tools/quant_smoke.py || exit 1

echo
echo "== tier-1 pytest (the driver's flags: 6 xdist workers, --dist load) =="
set -o pipefail
rm -f "$tmp/_t1.log"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist load -p no:randomly 2>&1 | tee "$tmp/_t1.log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$tmp/_t1.log" \
    | tr -cd . | wc -c)"
exit $rc
