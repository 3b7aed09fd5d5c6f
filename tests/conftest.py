"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding code paths
compile and execute without TPU hardware. Must be set before jax import.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture()
def ds():
    """Fresh in-memory datastore, closed when the test ends: a graph prewarm
    the test armed is cancelled or waited out there, and does not go on
    compiling (and logging its shapes) under the tests that follow."""
    from surrealdb_tpu.kvs.ds import Datastore

    store = Datastore("memory")
    yield store
    store.close()


def pytest_configure(config):
    """Prime the graftflow flow_audit report file once per run when it is
    absent (a bare pytest invocation — the tier1.sh analysis gate writes
    it before the suite otherwise). Without the prime, the FIRST
    debug-bundle call of the process runs the ~5s in-process analysis,
    and when that first call is a federated-bundle RPC handler the stall
    can exceed the cluster RPC timeout and mark a healthy node
    unreachable. ~5s once, then free for every later run on the host."""
    try:
        from surrealdb_tpu import cnf

        if cnf.FLOW_AUDIT_REPORT and not os.path.exists(cnf.FLOW_AUDIT_REPORT):
            from scripts.graftflow.report import generate, write_report

            write_report(generate(), cnf.FLOW_AUDIT_REPORT)
    except Exception:  # noqa: BLE001 — priming is best-effort; the bundle
        pass  # fallback (surrealdb_tpu/bundle.py) still degrades cleanly


def pytest_sessionfinish(session, exitstatus):
    """Flight-recorder CI hook: a failing suite dumps its own diagnostics
    (task registry, compile log, slow/error rings, traces) from INSIDE the
    dying process — scripts/tier1.sh points SURREAL_T1_BUNDLE at
    /tmp/_t1_bundle.json so failed runs carry their own bundle.

    Under SURREAL_SANITIZE=1 with SURREAL_SANITIZE_OUT set, the lock
    sanitizer's observed acquisition graph is dumped too (success or
    failure) — scripts/tier1.sh feeds it to the graftlint lock-order
    cross-check."""
    sanitize_out = os.environ.get("SURREAL_SANITIZE_OUT")
    if sanitize_out:
        try:
            from surrealdb_tpu.utils import locks

            locks.dump(sanitize_out)
        except Exception:  # noqa: BLE001
            pass
    path = os.environ.get("SURREAL_T1_BUNDLE")
    if not path or exitstatus in (0, 5):  # 5 = no tests collected
        return
    try:
        from surrealdb_tpu.bundle import write_bundle

        write_bundle(path)
    except Exception:  # noqa: BLE001 — diagnostics must never mask the failure
        pass
