#!/usr/bin/env bash
# One-step verify: install dev deps (best effort -- the suite degrades
# gracefully without hypothesis / pytest-cov) and run the tier-1 test command.
#
#   scripts/ci.sh            # full tier-1 suite (+ coverage gate if available)
#   scripts/ci.sh --fast     # quick tier: skips the slow corpus/property tiers
#
# The full tier includes the slow-marked 8-way mesh regressions
# (tests/test_distributed.py -- sharded serving, generational shards, and the
# distributed-wave parity test test_mesh_waves_match_single_device_and_monolithic);
# --fast skips them along with the other slow corpus/property tiers.
#
# Both tiers finish with an examples smoke step -- the streaming-ingest and
# out-of-core demos must run end to end in under 60s each on CPU -- and an
# observability smoke: a traced, metered wave job whose exports must pass
# the schema validator.  Speed is measured on the chip by bench/ (PERF.md),
# not here.
#
# The coverage gate engages whenever pytest-cov is importable; the floor is
# seeded conservatively below the suite's measured coverage so it catches
# wholesale test deletion, not refactors.  Ratchet it up as coverage grows.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install -q -r requirements-dev.txt \
    || echo "warning: dev dep install failed (offline?); continuing" >&2

EXTRA=()
if [[ "${1:-}" == "--fast" ]]; then
    shift
    EXTRA+=(-m "not slow")
fi
if python -c "import pytest_cov" 2>/dev/null; then
    EXTRA+=(--cov=repro --cov-report=term --cov-fail-under=60)
else
    echo "note: pytest-cov not installed; running without the coverage gate" >&2
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
    ${EXTRA[@]+"${EXTRA[@]}"} "$@"

echo "examples smoke: streaming_ingest.py (60s budget)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout 60 \
    python examples/streaming_ingest.py > /dev/null
echo "examples smoke: out_of_core.py (corpus > device budget; 60s budget)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout 60 \
    python examples/out_of_core.py > /dev/null

echo "observability smoke: traced + metered wave job, then schema validation"
OBS_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout 120 \
    python -m repro.launch.ngram --tokens 20000 --sigma 3 --tau 5 \
    --wave-tokens 4000 --trace "$OBS_TMP/trace.json" \
    --metrics "$OBS_TMP/metrics.jsonl" > /dev/null
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.obs.report \
    --validate-trace "$OBS_TMP/trace.json" \
    --validate-metrics "$OBS_TMP/metrics.jsonl"

echo "examples smoke: OK"
