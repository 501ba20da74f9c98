#!/usr/bin/env bash
# One-command gate (ref: python/run-tests.sh — SURVEY.md §2.5): the
# linter, the acceptance subsets one by one (each on its own line so a
# regression there fails loudly, not inside the full-suite noise), the
# full suite on the simulated 8-device CPU mesh, then the multi-chip dry
# run and the entry-point compile check. All of it is a CPU run
# (JAX_PLATFORMS=cpu below); the on-chip check is `python chip_smoke.py`
# through the chip tool.
set -euo pipefail
cd "$(dirname "$0")"
export JAX_PLATFORMS=cpu

echo "== tpudl-check (AST invariant linter, ANALYSIS.md + CONCURRENCY.md) =="
python -m tools.tpudl_check tpudl tools
python -m tools.tpudl_check --registry-audit tpudl tools

echo "== tsan pass (lock sanitizer armed over the concurrency subset) =="
# exit reports go to a scratch dir, not the checkout. User args go
# FIRST: pytest keeps the last -m, so a caller's -m (e.g. 'not slow')
# must not replace the concurrency marker and run everything armed.
TPUDL_TSAN=1 TPUDL_FLIGHT_DIR="$(mktemp -d)" \
    python -m pytest tests/test_concurrency.py -q "$@" -m concurrency

echo "== traceguard subset (jit-boundary rules + traceck sentinel) =="
# The armed-sentinel cases run in subprocesses the tests spawn
# themselves, so no env is set here.
python -m pytest tests/test_traceguard.py -q "$@"

echo "== chaos subset (fault-containment matrix, ISSUE 14 acceptance) =="
# User args go FIRST so a caller's -m cannot replace the chaos marker
# and skip the matrix.
python -m pytest tests/test_supervisor.py -q "$@" -m chaos

echo "== compile subset (buckets + warm starts through the persistent cache) =="
python -m pytest tests/test_compile.py -q "$@"

echo "== virtual-mesh executor subset (ISSUE 11 acceptance) =="
python -m pytest tests/test_mesh_executor.py -q "$@"

echo "== 2-D mesh tensor parallelism subset (ISSUE 16 acceptance) =="
python -m pytest tests/test_mesh2d.py -q "$@"

echo "== serve subset (ISSUE 17: continuous batching acceptance) =="
python -m pytest tests/test_serve.py -q "$@"

echo "== serve telemetry subset (ISSUE 18: traces + SLO acceptance) =="
python -m pytest tests/test_serve_telemetry.py -q "$@"

echo "== text subset (ISSUE 19: tokenizer codec + tokens/s acceptance) =="
python -m pytest tests/test_text.py -q "$@"

echo "== attribution subset (ISSUE 20: scoped ledgers acceptance) =="
python -m pytest tests/test_obs_attribution.py -q "$@"

echo "== pytest (simulated 8-device CPU mesh) =="
python -m pytest tests/ -q "$@"

echo "== multi-chip dryrun (8-device virtual mesh) =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== single-chip entry compile check (CPU; the chip's is chip_smoke.py) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" python - <<'EOF2'
import jax
import numpy as np
import __graft_entry__ as g
fn, args = g.entry()
out = np.asarray(jax.jit(fn)(*args))
assert np.isfinite(out).all()
print(f"entry() ok: {out.shape}")
EOF2

echo "ALL GATES GREEN"
