#!/usr/bin/env bash
# Tier-1 CI: static analysis and the full test suite on CPU.
# Mirrors .github/workflows/ci.yml.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu

echo "== static analysis =="
python -m repro.analysis src/ --trace-gate

echo "== tier-1 tests =="
python -m pytest -x -q
