"""graftlint: every rule fires on its fixture, stays silent on the clean
twin, and the repo itself lints clean against the committed baseline."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")
sys.path.insert(0, REPO)

from scripts.graftlint import engine  # noqa: E402
from scripts.graftlint import rules as rules_mod  # noqa: E402


def lint(*names, rules=None):
    paths = [os.path.join(FIXTURES, n) for n in names]
    return engine.lint_paths(paths, rules=rules)


def rule_ids(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------------ per rule
@pytest.mark.parametrize("rule", ["GL001", "GL002", "GL003", "GL004", "GL005", "GL006", "GL007", "GL008", "GL009", "GL010", "GL011", "GL012", "GL013", "GL015", "GL016"])
def test_rule_fires_on_bad_fixture_and_not_on_clean(rule):
    bad = lint(f"{rule.lower()}_bad.py", rules=[rule])
    assert rule in rule_ids(bad), f"{rule} failed to fire on its fixture"
    clean = lint(f"{rule.lower()}_clean.py", rules=[rule])
    assert rule not in rule_ids(clean), (
        f"{rule} false-positive on clean twin: {[f.render() for f in clean]}"
    )


def test_gl001_flags_thread_and_timer():
    keys = {f.key for f in lint("gl001_bad.py", rules=["GL001"])}
    assert any(k.endswith(":Thread") for k in keys)
    assert any(k.endswith(":Timer") for k in keys)


def test_gl003_key_carries_env_var_name():
    keys = {f.key for f in lint("gl003_bad.py", rules=["GL003"])}
    assert any("SURREAL_FIXTURE_FLAG" in k for k in keys)


def test_gl004_escapes_are_not_flagged():
    findings = lint("gl004_clean.py", rules=["GL004"])
    assert findings == []


def test_gl006_distinguishes_dynamic_name_and_labelset():
    msgs = [f.message for f in lint("gl006_bad.py", rules=["GL006"])]
    assert any("DYNAMIC metric name" in m for m in msgs)
    assert any("inconsistent label sets" in m for m in msgs)
    assert any("'sql'" in m for m in msgs)


def test_gl007_matching_name_and_span_only_functions_pass():
    keys = {f.key for f in lint("gl007_bad.py", rules=["GL007"])}
    assert any(k.endswith(":fixture_probe_span") for k in keys)
    assert any(k.endswith(":fixture_other") for k in keys)
    assert lint("gl007_clean.py", rules=["GL007"]) == []


def test_gl008_flags_unpaced_retry_and_swallow_separately():
    keys = {f.key for f in lint("gl008_bad.py", rules=["GL008"])}
    assert any(k.endswith(":retry") for k in keys), keys
    assert any(k.endswith(":swallow") for k in keys), keys
    # backoff'd / bounded retries and narrow evidence-keeping handlers pass
    assert lint("gl008_clean.py", rules=["GL008"]) == []


def test_gl010_bare_except_counts_as_base_exception():
    keys = {f.key for f in lint("gl010_bad.py", rules=["GL010"])}
    assert any("bare_except_is_base_exception" in k for k in keys), keys
    assert len(keys) == 3  # named, tuple and bare forms all flagged


def test_suppression_comment_silences_a_finding(tmp_path):
    f = tmp_path / "suppressed.py"
    f.write_text(
        "import threading\n"
        "t = threading.Thread(target=print)  # graftlint: disable=GL001\n"
    )
    assert engine.lint_paths([str(f)], rules=["GL001"]) == []
    f.write_text("import threading\nt = threading.Thread(target=print)\n")
    assert len(engine.lint_paths([str(f)], rules=["GL001"])) == 1


def test_baseline_grandfathers_then_catches_new(tmp_path):
    findings = lint("gl003_bad.py", rules=["GL003"])
    assert findings
    bpath = tmp_path / "baseline.json"
    engine.write_baseline(findings, str(bpath))
    baseline = engine.load_baseline(str(bpath))
    new, stale = engine.apply_baseline(findings, baseline)
    assert new == [] and stale == []
    # a fresh violation in another file is NOT covered
    extra = lint("gl003_bad.py", "gl001_bad.py", rules=["GL003", "GL001"])
    new, _ = engine.apply_baseline(extra, baseline)
    assert {f.rule for f in new} == {"GL001"}


# ------------------------------------------------------------------ the repo
def test_repo_lints_clean_with_committed_baseline():
    """The acceptance criterion: surrealdb_tpu/ has no findings beyond the
    committed baseline, and the baseline stays bounded — 2 historical GL006
    label entries and 4 of the original 6 GL010 BaseException-converter
    sites (the dispatch propagate-to-waiters sites remain deliberate).
    ISSUE 14 burned the last 3 GL008 swallow sites down to ZERO: the bg
    spawn firewall counts `bg_spawn_body_errors`, a failed boot bootstrap
    counts `bootstrap_errors`, and a crashing WS pool task counts
    `ws_pool_task_errors`. Shrink it; never grow it without review."""
    findings = engine.lint_paths([os.path.join(REPO, "surrealdb_tpu")])
    baseline = engine.load_baseline()
    assert len(baseline) <= 6, "baseline grew past the acceptance cap"
    assert sum(1 for e in baseline.values() if e["rule"] == "GL008") == 0
    assert sum(1 for e in baseline.values() if e["rule"] == "GL010") <= 4
    assert sum(1 for e in baseline.values() if e["rule"] not in ("GL008", "GL010")) <= 2
    new, _stale = engine.apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_cli_exit_codes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ok = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint"],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # introducing any fixture violation must flip the exit code
    bad = subprocess.run(
        [
            sys.executable, "-m", "scripts.graftlint",
            os.path.join(REPO, "surrealdb_tpu"),
            os.path.join(FIXTURES, "gl001_bad.py"),
            os.path.join(FIXTURES, "gl002_bad.py"),
            os.path.join(FIXTURES, "gl003_bad.py"),
            os.path.join(FIXTURES, "gl004_bad.py"),
            os.path.join(FIXTURES, "gl005_bad.py"),
            os.path.join(FIXTURES, "gl006_bad.py"),
            os.path.join(FIXTURES, "gl007_bad.py"),
            os.path.join(FIXTURES, "gl008_bad.py"),
            os.path.join(FIXTURES, "gl009_bad.py"),
            os.path.join(FIXTURES, "gl011_bad.py"),
            os.path.join(FIXTURES, "gl012_bad.py"),
            os.path.join(FIXTURES, "gl013_bad.py"),
            os.path.join(FIXTURES, "gl016_bad.py"),
        ],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    for rule in ("GL001", "GL002", "GL003", "GL004", "GL005", "GL006", "GL007", "GL008", "GL009", "GL011", "GL012", "GL013", "GL016"):
        assert rule in bad.stdout, f"{rule} missing from CLI output"
    # --update-baseline refuses a restricted scope (it would silently drop
    # every grandfathered entry the restricted run can't see)
    guard = subprocess.run(
        [
            sys.executable, "-m", "scripts.graftlint",
            "--rules", "GL001", "--update-baseline",
        ],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    assert guard.returncode == 2
    assert "full scope" in guard.stderr


def test_gl009_flags_dynamic_kind_unregistered_kind_and_ring_access():
    keys = {f.key for f in lint("gl009_bad.py", rules=["GL009"])}
    assert any(k.endswith(":dynamic-kind") for k in keys), keys
    assert any(":kind:fixture.made_up_kind" in k for k in keys), keys
    assert any(k.endswith(":ring") for k in keys), keys
    assert any(k.endswith(":import:_ring") for k in keys), keys
    # the direct-import alias (`from ... events import emit as _emit`)
    # does not dodge the dynamic-kind check
    assert any(":note_aliased:dynamic-kind" in k for k in keys), keys
    # registered kinds (including conditional expressions over registered
    # constants, the flap-site idiom) pass clean
    assert lint("gl009_clean.py", rules=["GL009"]) == []


def test_gl011_flags_undeclared_and_dynamic_names():
    keys = {f.key for f in lint("gl011_bad.py", rules=["GL011"])}
    assert any(":name:fixture.not_in_hierarchy" in k for k in keys), keys
    assert any(":name:fixture.also_missing" in k for k in keys), keys
    assert any(k.endswith(":dynamic-name") for k in keys), keys
    # declared names (either import alias) pass clean
    assert lint("gl011_clean.py", rules=["GL011"]) == []


def test_gl012_flags_private_access_under_any_alias():
    keys = {f.key for f in lint("gl012_bad.py", rules=["GL012"])}
    # all three import spellings (`import surrealdb_tpu.stats as st`,
    # `from surrealdb_tpu import stats` and the plain
    # `import surrealdb_tpu.stats` dotted path) are caught, per member
    assert any(":sneak_dotted:_store" in k for k in keys), keys
    assert any(k.endswith(":_store") for k in keys), keys
    assert any(k.endswith(":_lock") for k in keys), keys
    assert any(k.endswith(":_active_by_thread") for k in keys), keys
    assert any(k.endswith(":_Entry") for k in keys), keys
    assert any(k.endswith(":_evicted") for k in keys), keys
    assert any(k.endswith(":_note_evictions") for k in keys), keys
    # the public doors — fingerprint/activate/record/statements — stay clean
    assert lint("gl012_clean.py", rules=["GL012"]) == []


def test_gl013_flags_private_access_under_any_alias():
    keys = {f.key for f in lint("gl013_bad.py", rules=["GL013"])}
    # all three import spellings (`import surrealdb_tpu.accounting as acct`,
    # `from surrealdb_tpu import accounting` and the plain
    # `import surrealdb_tpu.accounting` dotted path) are caught, per member
    assert any(":sneak_dotted:_store" in k for k in keys), keys
    assert any(k.endswith(":_store") for k in keys), keys
    assert any(k.endswith(":_lock") for k in keys), keys
    assert any(k.endswith(":_global") for k in keys), keys
    assert any(k.endswith(":_Entry") for k in keys), keys
    assert any(k.endswith(":_active_by_thread") for k in keys), keys
    assert any(k.endswith(":_tally_by_thread") for k in keys), keys
    assert any(k.endswith(":_budget_cache") for k in keys), keys
    assert any(k.endswith(":_evicted") for k in keys), keys
    # the public doors — charge/activate/tally/top/snapshot — stay clean
    assert lint("gl013_clean.py", rules=["GL013"]) == []


def test_gl016_flags_blocking_sockets_and_sleep_only_when_marked(tmp_path):
    keys = {f.key for f in lint("gl016_bad.py", rules=["GL016"])}
    assert any(k.endswith(":drain:recv") for k in keys), keys
    assert any(k.endswith(":drain:sendall") for k in keys), keys
    assert any(k.endswith(":take_one:accept") for k in keys), keys
    assert any(k.endswith(":Pump.tick:recv_into") for k in keys), keys
    # both sleep spellings (time.sleep and the direct import) are caught
    assert sum(1 for k in keys if k.endswith(":sleep")) >= 1, keys
    # _nb_ wrappers + Event.wait pacing pass clean
    assert lint("gl016_clean.py", rules=["GL016"]) == []
    # an UNMARKED module with identical blocking calls is out of scope —
    # the rule is about loop threads, not sockets in general
    f = tmp_path / "unmarked.py"
    f.write_text(
        "import time\n"
        "def drain(sock):\n"
        "    time.sleep(1)\n"
        "    return sock.recv(4096)\n"
    )
    assert engine.lint_paths([str(f)], rules=["GL016"]) == []


def test_gl016_loop_module_is_marked_and_clean():
    # the real event-loop ingress carries the marker and holds itself to
    # the rule it anchors
    import ast as _ast

    path = os.path.join(REPO, "surrealdb_tpu", "net", "loop.py")
    with open(path) as fh:
        src = fh.read()
    tree = _ast.parse(src)
    assert any(
        isinstance(n, _ast.Assign)
        and any(getattr(t, "id", "") == "EVENT_LOOP_MODULE" for t in n.targets)
        for n in tree.body
    )
    assert engine.lint_paths([path], rules=["GL016"]) == []


def test_gl011_hierarchy_matches_runtime():
    # the rule checks against the REAL declared hierarchy, so the static
    # and runtime halves can never drift
    from surrealdb_tpu.utils.locks import HIERARCHY

    assert rules_mod._gl011_hierarchy() == set(HIERARCHY)


def test_gl009_registry_matches_runtime():
    # the rule checks against the REAL registry, so the static and runtime
    # halves can never drift
    from surrealdb_tpu.events import KINDS

    assert rules_mod._gl009_registry() == set(KINDS)


def test_every_rule_has_doc_and_registration():
    assert set(rules_mod.RULES) == {
        "GL001", "GL002", "GL003", "GL004", "GL005", "GL006", "GL007",
        "GL008", "GL009", "GL010", "GL011", "GL012", "GL013", "GL015",
        "GL016",
    }
    for rid, (fn, doc) in rules_mod.RULES.items():
        assert callable(fn) and doc
