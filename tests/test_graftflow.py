"""graftflow: every GF rule fires on its seeded fixture and stays silent
on the clean twin; the call graph discovers every named engine lock; the
repo analyzes clean against the committed baseline; and the cross-check
closes the static/runtime loop — a real sanitized run's observed lock
edges are a subset of the static may-edge graph, end to end."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftflow")
sys.path.insert(0, REPO)

from scripts.graftflow import callgraph, crosscheck  # noqa: E402
from scripts.graftflow import report as report_mod  # noqa: E402
from scripts.graftflow import rules as rules_mod  # noqa: E402

# the gf004 pair needs its helper module in the same analysis scope
_EXTRA = {
    "gf004_bad.py": ["gf004_helper.py"],
    "gf004_clean.py": ["gf004_helper_clean.py"],
}


def analyze(*names, rules=None):
    paths = [os.path.join(FIXTURES, n) for n in names]
    g = callgraph.build(paths)
    return g, rules_mod.run_rules(g, rules=rules)


def fire(rule: str, fixture: str):
    _g, findings = analyze(fixture, *_EXTRA.get(fixture, []), rules=[rule])
    return findings


# ------------------------------------------------------------------ per rule
@pytest.mark.parametrize("rule", ["GF001", "GF002", "GF003", "GF004"])
def test_rule_fires_on_bad_fixture_and_not_on_clean(rule):
    bad = fire(rule, f"{rule.lower()}_bad.py")
    assert any(f.rule == rule for f in bad), f"{rule} failed to fire"
    clean = fire(rule, f"{rule.lower()}_clean.py")
    assert clean == [], (
        f"{rule} false-positive on clean twin: {[f.render() for f in clean]}"
    )


def test_gf001_catches_never_executed_abba_statically():
    """The acceptance seed: an ABBA split across four functions that no
    test ever executes. The static proof must name both the hierarchy
    inversion and the Tarjan cycle."""
    findings = fire("GF001", "gf001_bad.py")
    keys = {f.key for f in findings}
    assert "GF001:inversion:kvs.mem->kvs.commit" in keys, keys
    assert any(k.startswith("GF001:cycle:") for k in keys), keys
    cyc = next(f for f in findings if f.key.startswith("GF001:cycle:"))
    assert "kvs.commit" in cyc.message and "kvs.mem" in cyc.message


def test_gf002_flags_deep_reader_and_names_the_body():
    keys = {f.key for f in fire("GF002", "gf002_bad.py")}
    # the reader one call below the spawned body is still caught
    assert any(k.endswith(":deep_body") for k in keys), keys
    assert any(k.endswith(":span_body") for k in keys), keys


def test_gf004_findings_live_in_the_helper_module_with_chain():
    findings = fire("GF004", "gf004_bad.py")
    assert all(f.path.endswith("gf004_helper.py") for f in findings)
    details = {f.key.rsplit(":", 1)[-1] for f in findings}
    assert "time.sleep" in details, details
    assert "np.asarray" in details, details
    assert any("kvs.commit" in f.key for f in findings), [f.key for f in findings]
    # the message carries the reachability chain back to the entry
    assert any("entry" in f.message for f in findings)


# ------------------------------------------------------------------ call graph
def test_static_lock_graph_discovers_every_declared_engine_lock():
    """Acceptance criterion: the analyzer finds every named lock site the
    runtime sanitizer knows — all 24+ names in locks.HIERARCHY have a
    discovered creation site, exactly."""
    from surrealdb_tpu.utils.locks import HIERARCHY

    g = callgraph.build([os.path.join(REPO, "surrealdb_tpu")])
    assert len(HIERARCHY) >= 24
    assert g.lock_names == set(HIERARCHY), (
        f"missing: {set(HIERARCHY) - g.lock_names}, "
        f"undeclared: {g.lock_names - set(HIERARCHY)}"
    )
    assert len(g.lock_sites) >= len(HIERARCHY)


def test_method_dispatch_via_class_attribution(tmp_path):
    """`self.x = Worker(); ...; self.x.go()` resolves to Worker.go — the
    attribution layer file-local rules don't have."""
    f = tmp_path / "attrib_fixture.py"
    f.write_text(textwrap.dedent("""
        from surrealdb_tpu.utils import locks

        class Worker:
            def __init__(self):
                self._lk = locks.Lock("kvs.mem")
            def go(self):
                with self._lk:
                    pass

        class Owner:
            def __init__(self):
                self.w = Worker()
                self.outer = locks.Lock("kvs.commit")
            def run_both(self):
                with self.outer:
                    self.w.go()
    """))
    g = callgraph.build([str(f)], root=str(tmp_path))
    edges = set(rules_mod.lock_edges(g))
    assert ("kvs.commit", "kvs.mem") in edges


def test_spawn_boundary_does_not_propagate_held_locks(tmp_path):
    """A body spawned while a lock is held runs on ANOTHER thread: its
    acquisitions must not become edges from the spawner's held set."""
    f = tmp_path / "boundary_fixture.py"
    f.write_text(textwrap.dedent("""
        from surrealdb_tpu import bg
        from surrealdb_tpu.utils import locks

        A = locks.Lock("kvs.commit")
        B = locks.Lock("kvs.mem")

        def body():
            with B:
                pass

        def arm():
            with A:
                bg.spawn("fixture", "t", body)
    """))
    g = callgraph.build([str(f)], root=str(tmp_path))
    edges = set(rules_mod.lock_edges(g))
    assert ("kvs.commit", "kvs.mem") not in edges
    # the spawned body's own acquisitions are still analyzed (it is a
    # root of its thread), and the spawn site is recorded
    fn = next(fi for fi in g.functions.values() if fi.name == "arm")
    assert fn.spawn_sites and fn.spawn_sites[0][3] == "bg.spawn"


def test_suppression_comment_silences_a_finding(tmp_path):
    src = textwrap.dedent("""
        from surrealdb_tpu import bg, telemetry

        def body():
            with telemetry.span("fixture_span"):
                pass

        def arm():
            bg.spawn("fixture", "t", body){}
    """)
    f = tmp_path / "supp_fixture.py"
    f.write_text(src.format("  # graftflow: disable=GF002"))
    g = callgraph.build([str(f)], root=str(tmp_path))
    assert rules_mod.run_rules(g, rules=["GF002"]) == []
    f.write_text(src.format(""))
    g = callgraph.build([str(f)], root=str(tmp_path))
    assert len(rules_mod.run_rules(g, rules=["GF002"])) == 1


# ------------------------------------------------------------------ the repo
def test_repo_analyzes_clean_with_committed_baseline():
    from scripts.baselines import apply_baseline, load_baseline

    g = callgraph.build([os.path.join(REPO, "surrealdb_tpu")])
    findings = rules_mod.run_rules(g)
    baseline = load_baseline(report_mod.default_baseline_path())
    assert len(baseline) <= 16, "graftflow baseline grew past the cap"
    new, _stale = apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_report_carries_nonempty_callgraph_stats():
    rep = report_mod.generate()
    assert rep["schema"] == "surrealdb-tpu-flow-audit/1"
    cg = rep["callgraph"]
    assert cg["nodes"] > 1000 and cg["edges"] > 1000
    assert cg["lock_sites"] >= 24
    assert len(cg["lock_names"]) >= 24
    assert set(rep["rules"]) == {"GF001", "GF002", "GF003", "GF004"}
    assert rep["lock_graph"]["edges"], "static lock graph is empty"
    assert rep["summary"]["new"] == 0


def test_bundle_embeds_flow_audit_section():
    from surrealdb_tpu import bundle

    b = bundle.debug_bundle()
    assert b["schema"] == "surrealdb-tpu-bundle/11"
    fa = b["flow_audit"]
    assert fa["available"] is True
    assert fa["callgraph"]["nodes"] > 0
    assert fa["callgraph"]["lock_sites"] > 0


# ------------------------------------------------------------------ cross-check
def _dump(tmp_path, edges, enabled=True):
    p = tmp_path / "locks.json"
    p.write_text(json.dumps({
        "enabled": enabled,
        "edges": [{"from": a, "to": b, "count": 1} for a, b in edges],
        "cycles": [], "violations": [],
    }))
    return str(p)


def test_crosscheck_subset_passes_and_gap_fails(tmp_path):
    static = {("kvs.commit", "kvs.mem"), ("kvs.commit", "idx.store")}
    known = {"kvs.commit", "kvs.mem", "idx.store"}
    ok = _dump(tmp_path, [("kvs.commit", "kvs.mem")])
    errors, warnings, gaps = crosscheck.check_dump(ok, static, known)
    assert errors == [] and warnings == []
    assert gaps == ["kvs.commit -> idx.store"]  # coverage gap, not failure
    # an observed edge the static graph misses is a SOUNDNESS error
    bad = _dump(tmp_path, [("kvs.mem", "idx.store")])
    errors, _w, _g = crosscheck.check_dump(bad, static, known)
    assert len(errors) == 1 and "SOUNDNESS GAP" in errors[0]


def test_crosscheck_test_local_locks_warn_not_fail(tmp_path):
    static = {("kvs.commit", "kvs.mem")}
    known = {"kvs.commit", "kvs.mem"}
    d = _dump(tmp_path, [("test.only", "kvs.mem")])
    errors, warnings, _g = crosscheck.check_dump(d, static, known)
    assert errors == [] and len(warnings) == 1
    assert "test-local" in warnings[0]


def test_crosscheck_over_sanitized_suite_slice(tmp_path):
    """The acceptance wire, end to end: run a tier-1 suite SLICE under
    SURREAL_SANITIZE=1 (the conftest sessionfinish hook writes the
    SURREAL_SANITIZE_OUT dump, exactly as tier1.sh gate 2 does for the
    full smoke subset), then assert every runtime-observed lock edge
    appears in graftflow's static may-edge graph."""
    dump = tmp_path / "slice_locks.json"
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu", "SURREAL_SANITIZE": "1",
        "SURREAL_SANITIZE_OUT": str(dump),
    }
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "tests/test_kvs.py", "-q",
            "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
        ],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(dump.read_text())
    assert doc["enabled"] and doc["edges"], "sanitized slice observed no edges"
    g = callgraph.build([os.path.join(REPO, "surrealdb_tpu")])
    errors, _warnings, _gaps = crosscheck.check_dump(
        str(dump), set(rules_mod.lock_edges(g)), set(g.lock_names)
    )
    assert errors == [], "\n".join(errors)


def test_crosscheck_end_to_end_over_sanitized_workload(tmp_path):
    """Same contract over a denser workload (commit + column-mirror +
    scan paths) driven directly, so the dump carries cross-layer edges a
    single test file's slice may not reach."""
    dump = tmp_path / "observed.json"
    workload = textwrap.dedent(f"""
        from surrealdb_tpu.kvs.ds import Datastore
        from surrealdb_tpu.utils import locks

        ds = Datastore("memory")
        ds.execute("USE NS n DB d")
        for i in range(40):
            ds.execute(f"CREATE t:{{i}} SET a = {{i}}, b = 'x' + <string> {{i}}")
        ds.execute("SELECT * FROM t WHERE a > 3")
        ds.execute("UPDATE t:1 SET a = 99")
        ds.close()
        assert locks.dump({str(dump)!r}) is not None
    """)
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu", "SURREAL_SANITIZE": "1",
        "SURREAL_COLUMN_MIRROR_MIN_ROWS": "1",
    }
    proc = subprocess.run(
        [sys.executable, "-c", workload], cwd=REPO,
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(dump.read_text())
    assert doc["enabled"] and doc["edges"], "sanitizer observed no edges"

    g = callgraph.build([os.path.join(REPO, "surrealdb_tpu")])
    static = set(rules_mod.lock_edges(g))
    errors, _warnings, gaps = crosscheck.check_dump(
        str(dump), static, set(g.lock_names)
    )
    assert errors == [], "\n".join(errors)
    # the static graph checks orderings this run never exercised — that
    # surplus is exactly what the static layer adds over the sanitizer
    assert gaps, "static graph adds no coverage beyond this run?"


# ------------------------------------------------------------------ CLI
def test_cli_exit_codes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ok = subprocess.run(
        [sys.executable, "-m", "scripts.graftflow"],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "lock site(s)" in ok.stdout
    bad = subprocess.run(
        [
            sys.executable, "-m", "scripts.graftflow",
            os.path.join(FIXTURES, "gf001_bad.py"),
            os.path.join(FIXTURES, "gf002_bad.py"),
        ],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "GF001" in bad.stdout and "GF002" in bad.stdout
    guard = subprocess.run(
        [
            sys.executable, "-m", "scripts.graftflow",
            "--rules", "GF001", "--update-baseline",
        ],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    assert guard.returncode == 2
    assert "full scope" in guard.stderr


def test_unified_analysis_entry_point():
    """`python -m scripts.analysis` runs the layers with a bitmask exit
    code and one summary line (graftcheck skipped here: the kernel audit
    has its own tier-1 gate and test file)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ok = subprocess.run(
        [sys.executable, "-m", "scripts.analysis", "--skip", "graftcheck"],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=600,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    summary = ok.stdout.strip().splitlines()[-1]
    assert summary.startswith("analysis: ")
    assert "graftlint=OK" in summary
    assert "graftcheck=SKIPPED" in summary
    assert "graftflow=OK" in summary
    bad = subprocess.run(
        [sys.executable, "-m", "scripts.analysis", "--skip", "nonsense"],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=60,
    )
    # usage errors live OUTSIDE the 1/2/4 layer bitmask — a typo'd --skip
    # must never decode as "graftcheck failed"
    assert bad.returncode == 64


def test_every_rule_registered_with_doc():
    assert set(rules_mod.RULES) == {"GF001", "GF002", "GF003", "GF004"}
    for rid, (fn, doc) in rules_mod.RULES.items():
        assert callable(fn) and doc
