#!/usr/bin/env bash
# The single cheap green signal: schema selftest (generator and
# validator vocabularies agree, incl. the v3 client_stats/alert types),
# committed-artifact schema lint, a fast-fail pass over the round-
# pipeline tests (an input-pipeline regression — leaked thread, broken
# determinism — fails in seconds, before the full suite), then the
# tier-1 suite exactly as ROADMAP.md specifies it (CPU backend, slow
# tests deselected).
#
# Usage: scripts/ci_fast.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

python scripts/check_telemetry_schema.py --selftest runs

env JAX_PLATFORMS=cpu python -m pytest tests/test_pipeline.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# async buffered aggregation + scenario engine: a regression here
# (broken sync-equivalence, unsound merge, scenario nondeterminism)
# fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_async_agg.py \
    tests/test_scenarios.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# adversary injection + robust aggregation + quarantine: a regression
# here (broken HLO identity with defenses off, unsound clip/trim math,
# quarantine semantics drift) fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_defense.py \
    tests/test_quarantine.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# memory ledger + roofline attribution: a regression here (broken
# ledger parse, roofline math drift, ceiling-gate or residency-
# degradation semantics) fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_memory.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# fused sketch encode: a regression here (broken sketch linearity in
# the table-carry scan, streaming_grad drift vs jax.grad, soundness
# guards) fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_fused_encode.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# int8 quantized wire: a regression here (quantizer drifting from its
# numpy reference, lost rounding determinism/resume replay, broken
# byte accounting, a v9 schema/teleview gate drift) fails in seconds,
# before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_wire.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# sharded sketch server: a regression here (lost sharded==replicated
# round parity, a drifting range decode or top-k merge, a table-sized
# all-reduce sneaking back, broken eligibility fail-fasts, the teleview
# per-chip gate) fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_sharded_server.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# layer-wise compression attribution: a regression here (a broken
# group partition / conservation law, a per-group collective unroll,
# lost HLO identity with --signal_groups off, starvation-rule or
# teleview-fallback drift) fails in seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_layer_signals.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# preemption-safe rounds: a regression here (lost bitwise crash-resume,
# checkpoint-integrity fallback drift, telemetry stream clobbering,
# quarantine state dropped on restart, a leaked watchdog thread) fails
# in seconds, before the full suite; the REAL-kill subprocess matrix is
# scripts/crash_matrix.py (slow-marked here)
env JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# population-scale observability: a regression here (a drifted count-min
# or heavy-hitter bound, broken sketch/exact snapshot parity, a
# non-deterministic sidecar that loses bitwise crash-resume, the sidecar
# size guard or the teleview literal fallbacks drifting) fails in
# seconds, before the full suite
env JAX_PLATFORMS=cpu python -m pytest tests/test_population.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly

set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly "$@" 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
exit "$rc"
