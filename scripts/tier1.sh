#!/usr/bin/env bash
# Canonical tier-1 gate — the EXACT "Tier-1 verify" line from ROADMAP.md,
# wrapped so CI and humans run the identical command, plus the repo's
# static-analysis and concurrency-sanitizer gates:
#
#   0. `python -m scripts.analysis` — the unified static-analysis gate:
#      graftlint (source AST, GL001–GL011) -> graftcheck (compiled-IR
#      kernel audit GC001–GC004, its own process so it can pin the
#      simulated 8-device mesh before jax loads; writes the kernel_audit
#      report bundle.py embeds) -> graftflow (whole-program
#      interprocedural flow GF001–GF004; writes the flow_audit report =
#      bundle section 11). The bitmask exit code names the failed layer;
#      running them through one module means the three tools cannot
#      drift in invocation.
#   1. the pytest tier-1 suite (exit code preserved; log in /tmp/_t1.log,
#      DOTS_PASSED recount printed — driver-proof pass counting).
#   2. a SURREAL_SANITIZE=1 smoke subset re-run: instrumented locks record
#      the acquisition graph (dumped to /tmp/_t1_locks.json), then
#      `--lock-order` cross-checks observed edges against the declared
#      hierarchy (utils/locks.HIERARCHY) — order cycles, guarded-state
#      violations and inversions fail the gate — and
#      `graftflow --cross-check` asserts the OBSERVED edges are a subset
#      of the STATIC may-edge graph (analysis soundness: a real path the
#      call graph failed to resolve fails here, not silently).
#
# On a non-zero pytest exit the suite dumps a flight-recorder bundle (task
# registry, compile log, slow/error rings, traces, lock report) to
# /tmp/_t1_bundle.json via the conftest sessionfinish hook, so failed runs
# carry their own diagnostics. If the process died before the hook could
# run, a skeleton bundle is captured from a fresh interpreter as a fallback.
#
# Speed is not a tier-1 matter: it is measured on the chip, by
# `python3 benchmarks/run.py --workload <cell>` (BENCHMARK.json, PERF.md).
#
# Opt-in FULL-suite sanitizer (mines lock-order edges the smoke subset
# cannot reach — e.g. the group-commit flusher and delta-feed apply sites):
#   scripts/tier1.sh --sanitize-full     (or scripts/sanitize_full.sh)
# runs the ENTIRE tier-1 suite under SURREAL_SANITIZE=1 and cross-checks
# the observed acquisition graph against locks.HIERARCHY. Slower than the
# normal gates (instrumented locks across every test); not part of the
# default run.
set -o pipefail
cd "$(dirname "$0")/.."

if [ "$1" = "--sanitize-full" ]; then
  rm -f /tmp/_t1_locks_full.json
  timeout -k 10 1500 env JAX_PLATFORMS=cpu \
    SURREAL_SANITIZE=1 SURREAL_SANITIZE_OUT=/tmp/_t1_locks_full.json \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1_sanitize_full.log
  full_rc=${PIPESTATUS[0]}
  if [ ! -s /tmp/_t1_locks_full.json ]; then
    echo "GATE FAILED: sanitize-full produced no lock dump (rc=$full_rc)"
    exit 1
  fi
  python -m scripts.graftlint --no-lint --lock-order /tmp/_t1_locks_full.json
  lock_rc=$?
  python -m scripts.graftflow --no-rules --cross-check /tmp/_t1_locks_full.json
  flow_rc=$?
  [ "$full_rc" -ne 0 ] && echo "GATE FAILED: sanitize-full pytest (rc=$full_rc)"
  [ "$lock_rc" -ne 0 ] && echo "GATE FAILED: sanitize-full lock-order cross-check"
  [ "$flow_rc" -ne 0 ] && echo "GATE FAILED: sanitize-full graftflow observed-vs-static cross-check"
  [ "$full_rc" -ne 0 ] && exit "$full_rc"
  [ "$lock_rc" -ne 0 ] && exit "$lock_rc"
  exit "$flow_rc"
fi

# ---- gate 0: unified static analysis ----------------------------------------
# graftlint -> graftcheck -> graftflow, each its own process (graftcheck
# pins JAX_PLATFORMS/XLA_FLAGS before jax loads). The report paths follow
# the same knobs bundle.py reads, so bundles embedded by the rest of this
# run always see THIS gate's kernel_audit + flow_audit.
audit_report="${SURREAL_KERNEL_AUDIT_REPORT:-/tmp/_graftcheck_report.json}"
flow_report="${SURREAL_FLOW_AUDIT_REPORT:-/tmp/_graftflow_report.json}"
rm -f "$audit_report" "$flow_report"
python -m scripts.analysis
analysis_rc=$?

# ---- gate 1: the canonical tier-1 suite ------------------------------------
rm -f /tmp/_t1.log /tmp/_t1_bundle.json
timeout -k 10 870 env JAX_PLATFORMS=cpu SURREAL_T1_BUNDLE=/tmp/_t1_bundle.json \
  python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
if [ "$rc" -ne 0 ]; then
  if [ ! -s /tmp/_t1_bundle.json ]; then
    # the hook never ran (hard crash / timeout): best-effort skeleton dump
    python -c "from surrealdb_tpu.bundle import write_bundle; write_bundle('/tmp/_t1_bundle.json')" \
      2>/dev/null || true
  fi
  [ -s /tmp/_t1_bundle.json ] && echo "flight-recorder bundle: /tmp/_t1_bundle.json"
fi

# ---- gate 2: lock-order / race sanitizer smoke ------------------------------
rm -f /tmp/_t1_locks.json
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  SURREAL_SANITIZE=1 SURREAL_SANITIZE_OUT=/tmp/_t1_locks.json \
  python -m pytest \
  tests/test_locks_sanitizer.py tests/test_dispatch.py \
  tests/test_flight_recorder.py tests/test_column_scan.py \
  tests/test_column_pipeline.py \
  tests/test_kvs.py tests/test_e2e_crud.py tests/test_cluster.py \
  tests/test_bulk_ingest_v2.py tests/test_faults.py \
  tests/test_cluster_obs.py tests/test_elastic.py \
  tests/test_stats.py tests/test_accounting.py \
  tests/test_tombstone_gc.py tests/test_plan_cache.py \
  -q -p no:cacheprovider -p no:xdist -p no:randomly >/tmp/_t1_sanitize.log 2>&1
san_rc=$?
[ "$san_rc" -ne 0 ] && tail -20 /tmp/_t1_sanitize.log
lock_rc=1
flow_rc=1
if [ -s /tmp/_t1_locks.json ]; then
  python -m scripts.graftlint --no-lint --lock-order /tmp/_t1_locks.json
  lock_rc=$?
  # soundness self-validation: every edge the instrumented run OBSERVED
  # must be in graftflow's STATIC may-edge graph
  python -m scripts.graftflow --no-rules --cross-check /tmp/_t1_locks.json
  flow_rc=$?
else
  echo "lock-order: no sanitizer dump produced (smoke run rc=$san_rc)"
fi

# ---- verdict ---------------------------------------------------------------
[ "$analysis_rc" -ne 0 ] && echo "GATE FAILED: static analysis (rc=$analysis_rc: 1=graftlint 2=graftcheck 4=graftflow bitmask)"
[ "$rc" -ne 0 ] && echo "GATE FAILED: tier-1 pytest (rc=$rc)"
[ "$san_rc" -ne 0 ] && echo "GATE FAILED: sanitizer smoke subset (rc=$san_rc)"
[ "$lock_rc" -ne 0 ] && echo "GATE FAILED: lock-order cross-check (rc=$lock_rc)"
[ "$flow_rc" -ne 0 ] && echo "GATE FAILED: graftflow observed-vs-static cross-check (rc=$flow_rc)"
# pytest's exit code still wins for compatibility with the driver recount
if [ "$rc" -ne 0 ]; then exit "$rc"; fi
if [ "$analysis_rc" -ne 0 ] || [ "$san_rc" -ne 0 ] || [ "$lock_rc" -ne 0 ] || [ "$flow_rc" -ne 0 ]; then exit 1; fi
exit 0
