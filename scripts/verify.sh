#!/usr/bin/env bash
# One-stop PR gate: tier-1 tests + tpu-lint + the armed-observability
# overhead guard + the fusion-pass smoke/A-B gate + the bench-trajectory
# sentinel. Run from the repo root:
#
#   bash scripts/verify.sh             # everything (tier-1 is the slow part)
#   bash scripts/verify.sh --fast      # lint + overhead only (skips the
#                                      # fusion stage, sentinel and tier-1)
#   bash scripts/verify.sh --sentinel  # ONLY the perf-regression sentinel
#
# The fusion stage (ROADMAP item 1) proves the profile→pass loop end to
# end: scripts/fusion_smoke.py runs the profiler on the CPU smoke, feeds
# the artifact to jit/fusion.py's FusionPass, asserts BOTH shipped
# regions fuse and that a synthetically stale artifact degrades to
# structured skips; benchmarks/bench_fusion.py then re-runs the ABBA
# admission gates (byte-identity, recompile-neutrality, measured win)
# and its one-line JSON is judged against the BENCH_r*.json trajectory
# (wide 30% relative floor until the fusion series accumulates history).
#
# The sentinel stage replays the checked-in BENCH_r*.json trajectory
# through scripts/bench_sentinel.py (noise-aware MAD bands) — the gate
# ROADMAP item 1 requires before any fusion/perf change is kept. Gate a
# fresh line directly with:
#
#   python scripts/bench_sentinel.py --fresh /tmp/bench_line.json
#
# The chip is not a stage here: `python chip_smoke.py` runs on the TPU
# (through the chip tool) and refuses to run anywhere else. What CAN be
# checked without a chip is — the serving/train steps compile for a
# described v5e:2x2 host with their Pallas kernels (tests/test_tpu_aot.py)
# and chip_smoke.py's control flow holds at a toy size
# (tests/test_chip_smoke.py); both are `slow` and run as stage 11.
#
# Exit codes: 0 all green; first failing stage's code otherwise.
set -u -o pipefail
cd "$(dirname "$0")/.."

fast=0
only_sentinel=0
[ "${1:-}" = "--fast" ] && fast=1
[ "${1:-}" = "--sentinel" ] && only_sentinel=1

if [ "$only_sentinel" = "1" ]; then
    echo "== bench_sentinel (trajectory replay) =="
    python scripts/bench_sentinel.py --replay
    exit $?
fi

echo "== [1/12] tpu-lint (python -m paddle_tpu.analysis; incl. dataflow: page-leak/dtype-flow/cache-key) =="
s0=$SECONDS
python -m paddle_tpu.analysis || exit $?
echo "tpu-lint stage wall: $((SECONDS - s0))s (in-process budget 5s — regressions show here)"

echo "== [2/12] bench_obs_overhead (armed sensor+timeline plane, 3% budget) =="
JAX_PLATFORMS=cpu python benchmarks/bench_obs_overhead.py || exit $?

if [ "$fast" = "1" ]; then
    echo "== [3-12/12] fusion + multichip + multihost + disagg + replay + sentinel + chip bring-up + tier-1 skipped (--fast) =="
    exit 0
fi

echo "== [3/12] fusion pass smoke (profile -> pass -> install, stale skips) =="
JAX_PLATFORMS=cpu python scripts/fusion_smoke.py || exit $?

echo "== [4/12] bench_fusion ABBA gates + sentinel fresh-line judgement =="
JAX_PLATFORMS=cpu python benchmarks/bench_fusion.py > /tmp/_fusion_line.json \
    || exit $?
tail -n 1 /tmp/_fusion_line.json | python scripts/bench_sentinel.py \
    --fresh - --min-history 1 --rel-floor 0.3 || exit $?

echo "== [5/12] multichip serve smoke (mp=2 storm, chip kill, byte-identical rejoin) =="
JAX_PLATFORMS=cpu python scripts/multichip_serve_smoke.py || exit $?

echo "== [6/12] multihost serve smoke (2 processes, page migration, seeded host kill) =="
JAX_PLATFORMS=cpu python scripts/multihost_serve_smoke.py || exit $?

echo "== [7/12] disagg serve smoke (prefill/decode handoff byte-identity, autoscaler vs 10x burst) =="
JAX_PLATFORMS=cpu python scripts/disagg_serve_smoke.py || exit $?

echo "== [8/12] replay smoke (journal -> bundle -> byte-identical replay, planted divergence) =="
JAX_PLATFORMS=cpu python scripts/replay_smoke.py || exit $?

echo "== [9/12] bench_router resize recovery + sentinel fresh-line judgement =="
JAX_PLATFORMS=cpu python benchmarks/bench_router.py > /tmp/_router_line.json \
    || exit $?
tail -n 1 /tmp/_router_line.json | python scripts/bench_sentinel.py \
    --fresh - --min-history 1 --rel-floor 0.4 || exit $?

echo "== [10/12] bench_sentinel (trajectory replay) =="
python scripts/bench_sentinel.py --replay || exit $?

echo "== [11/12] chip bring-up without a chip (AOT compile for v5e:2x2 + chip_smoke.py rehearsal) =="
JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_aot.py \
    tests/test_chip_smoke.py -q -p no:cacheprovider || exit $?

echo "== [12/12] tier-1 test suite =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
exit $rc
