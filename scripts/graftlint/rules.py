"""graftlint rules GL001–GL010 (see package docstring for the catalog).

Each rule is `fn(modules: List[Module]) -> List[Finding]`. Rules are
deliberately HEURISTIC — they encode this codebase's conventions, not a
soundness proof — and every rule supports `# graftlint: disable=GL00X`
for the rare intentional exception (the suppression is visible in review,
which is the point).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .engine import Finding, Module

# rule id -> (fn, one-line doc); populated by @_rule below
RULES: Dict[str, Tuple] = {}


def _rule(rule_id: str, doc: str):
    def deco(fn):
        RULES[rule_id] = (fn, doc)
        return fn

    return deco


def _call_name(node: ast.Call) -> Tuple[Optional[str], str]:
    """(receiver, attr) for `recv.attr(...)`, (None, name) for `name(...)`."""
    f = node.func
    if isinstance(f, ast.Attribute):
        recv = f.value.id if isinstance(f.value, ast.Name) else None
        return recv, f.attr
    if isinstance(f, ast.Name):
        return None, f.id
    return None, ""


def _imports_of(m: Module) -> Set[str]:
    """Every module path this file imports (absolute, dotted)."""
    out: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


def _from_imports(m: Module, module: str) -> Set[str]:
    """Names imported via `from <module> import ...` in this file."""
    out: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.ImportFrom) and node.module == module:
            out.update(a.asname or a.name for a in node.names)
    return out


# ------------------------------------------------------------------ GL001
# Threads the flight recorder cannot see: bg.py owns ALL thread/timer
# creation (spawn/spawn_service/start_thread/timer) so every thread has a
# registry entry, a deterministic name, and watchdog coverage.
GL001_ALLOWED_FILES = frozenset({"surrealdb_tpu/bg.py"})


@_rule("GL001", "raw threading.Thread/Timer outside bg.py")
def gl001(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL001_ALLOWED_FILES:
            continue
        direct = _from_imports(m, "threading")
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            hit = (
                attr in ("Thread", "Timer")
                and (
                    (recv is not None and "threading" in recv)
                    or (recv is None and attr in direct)
                )
            )
            if hit:
                out.append(
                    Finding(
                        "GL001", m.rel, node.lineno, node.col_offset,
                        f"raw threading.{attr} — spawn via surrealdb_tpu.bg "
                        "(spawn/spawn_service/start_thread/timer) so the "
                        "flight recorder sees it",
                        f"GL001:{m.rel}:{m.enclosing_def(node)}:{attr}",
                    )
                )
    return out


# ------------------------------------------------------------------ GL002
# Kernel-definition-only modules: their jitted functions are invoked (and
# compile_log-wrapped) by callers, never launched here.
GL002_KERNEL_DEF_MODULES = frozenset(
    {
        "surrealdb_tpu/ops/bm25.py",
        "surrealdb_tpu/ops/column_agg.py",
        "surrealdb_tpu/ops/distances.py",
        "surrealdb_tpu/parallel/mesh.py",
    }
)


@_rule("GL002", "jax.jit site in a module that never touches compile_log")
def gl002(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL002_KERNEL_DEF_MODULES:
            continue
        if "compile_log" in m.source and (
            "surrealdb_tpu.compile_log" in _imports_of(m)
            or "compile_log" in _from_imports(m, "surrealdb_tpu")
        ):
            continue
        for node in ast.walk(m.tree):
            jit_site: Optional[ast.AST] = None
            if isinstance(node, ast.Call):
                recv, attr = _call_name(node)
                if attr == "jit" and recv == "jax":
                    jit_site = node
                # functools.partial(jax.jit, ...)
                elif attr == "partial" and node.args:
                    a0 = node.args[0]
                    if (
                        isinstance(a0, ast.Attribute)
                        and a0.attr == "jit"
                        and isinstance(a0.value, ast.Name)
                        and a0.value.id == "jax"
                    ):
                        jit_site = node
            elif isinstance(node, ast.Attribute) and node.attr == "jit":
                # bare @jax.jit decorator (no call parens)
                if isinstance(node.value, ast.Name) and node.value.id == "jax":
                    jit_site = node
            if jit_site is not None:
                out.append(
                    Finding(
                        "GL002", m.rel, node.lineno, node.col_offset,
                        "jax.jit in a module with no compile_log wiring — "
                        "first-call XLA compiles here are phantom "
                        "(unattributed) latency; wrap launch sites with "
                        "compile_log.tracked(...)",
                        f"GL002:{m.rel}:{m.enclosing_def(node)}",
                    )
                )
                break  # one finding per scope is enough; key is per-def
    # de-dup same-key findings (break above only stops the walk early)
    seen: Set[str] = set()
    uniq = []
    for f in out:
        if f.key not in seen:
            seen.add(f.key)
            uniq.append(f)
    return uniq


# ------------------------------------------------------------------ GL003
GL003_ALLOWED_FILES = frozenset({"surrealdb_tpu/cnf.py"})


@_rule("GL003", "os.environ/os.getenv outside cnf.py")
def gl003(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL003_ALLOWED_FILES:
            continue
        direct = _from_imports(m, "os")
        for node in ast.walk(m.tree):
            name = None
            if isinstance(node, ast.Attribute) and node.attr in (
                "environ", "getenv",
            ):
                if isinstance(node.value, ast.Name) and node.value.id in (
                    "os", "_os",
                ):
                    name = node.attr
            elif isinstance(node, ast.Name) and node.id in direct and node.id in (
                "environ", "getenv",
            ):
                name = node.id
            if name is None:
                continue
            env_var = _nearest_env_literal(m, node)
            detail = env_var or m.enclosing_def(node)
            out.append(
                Finding(
                    "GL003", m.rel, node.lineno, node.col_offset,
                    f"os.{name} outside cnf.py — route through a cnf knob "
                    "or cnf.env_* helper"
                    + (f" (variable {env_var})" if env_var else ""),
                    f"GL003:{m.rel}:{detail}",
                )
            )
    return out


def _nearest_env_literal(m: Module, node: ast.AST) -> Optional[str]:
    """The env-var string literal on the same source line, if any (stable
    baseline detail)."""
    try:
        line = m.lines[node.lineno - 1]
    except IndexError:
        return None
    import re as _re

    lits = _re.findall(r"[\"']([A-Z][A-Z0-9_]{2,})[\"']", line)
    return lits[0] if lits else None


# ------------------------------------------------------------------ GL004
@_rule("GL004", "transaction handle without commit/cancel on any path")
def gl004(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        for fn in ast.walk(m.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            out.extend(_gl004_check_fn(m, fn))
    return out


def _gl004_check_fn(m: Module, fn: ast.AST) -> List[Finding]:
    # local names assigned from `<expr>.transaction(...)`
    tx_names: Dict[str, ast.AST] = {}
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr == "transaction"
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            tx_names[node.targets[0].id] = node
    if not tx_names:
        return []
    finished: Set[str] = set()
    escaped: Set[str] = set()
    for node in ast.walk(fn):
        # txn.commit() / txn.cancel() finishes it
        if isinstance(node, ast.Attribute) and node.attr in ("commit", "cancel"):
            if isinstance(node.value, ast.Name) and node.value.id in tx_names:
                finished.add(node.value.id)
        # escapes: returned / yielded / passed to a call / stored on an
        # object / re-assigned to something else — ownership moved, the
        # callee or holder is responsible
        elif isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
            v = node.value
            if v is not None:
                for n in ast.walk(v):
                    if isinstance(n, ast.Name) and n.id in tx_names:
                        escaped.add(n.id)
        elif isinstance(node, ast.Call):
            for n in list(node.args) + [kw.value for kw in node.keywords]:
                for nn in ast.walk(n):
                    if isinstance(nn, ast.Name) and nn.id in tx_names:
                        escaped.add(nn.id)
        elif isinstance(node, ast.Assign):
            for n in ast.walk(node.value):
                if isinstance(n, ast.Name) and n.id in tx_names:
                    if not (
                        isinstance(node.value, ast.Call)
                        and isinstance(node.value.func, ast.Attribute)
                        and node.value.func.attr == "transaction"
                    ):
                        escaped.add(n.id)
    out: List[Finding] = []
    for name, site in tx_names.items():
        if name in finished or name in escaped:
            continue
        out.append(
            Finding(
                "GL004", m.rel, site.lineno, site.col_offset,
                f"transaction `{name}` has no commit()/cancel() in "
                f"{m.enclosing_def(site)} and never escapes — leaks its "
                "snapshot until GC (the runtime detector fires after the "
                "fact; fix the path)",
                f"GL004:{m.rel}:{m.enclosing_def(site)}:{name}",
            )
        )
    return out


# ------------------------------------------------------------------ GL005
# Files whose functions are dispatch hot path: a blocking host sync here
# serializes the whole coalescing pipeline. Other files opt in with a
# `# graftlint: hot-path` comment anywhere in the file.
GL005_HOT_FILES = frozenset({"surrealdb_tpu/dbs/dispatch.py"})
GL005_BLOCKING_ATTRS = frozenset({"block_until_ready", "device_get", "tolist"})
GL005_NP_SYNC = frozenset({"asarray", "array"})
GL005_NP_NAMES = frozenset({"np", "numpy", "onp", "jnp"})


@_rule("GL005", "blocking host sync inside dispatch hot-path files")
def gl005(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        hot = m.rel in GL005_HOT_FILES or any(
            "graftlint: hot-path" in ln for ln in m.lines[:50]
        )
        if not hot:
            continue
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            hit = attr in GL005_BLOCKING_ATTRS or (
                attr in GL005_NP_SYNC and recv in GL005_NP_NAMES
            )
            if hit:
                out.append(
                    Finding(
                        "GL005", m.rel, node.lineno, node.col_offset,
                        f"blocking host sync `.{attr}(...)` on the dispatch "
                        "hot path — this serializes every rider of the "
                        "coalesced batch; move it to a collect phase / the "
                        "runner closure",
                        f"GL005:{m.rel}:{m.enclosing_def(node)}:{attr}",
                    )
                )
    return out


# ------------------------------------------------------------------ GL006
GL006_WRITERS = frozenset(
    {"inc", "observe", "observe_hist", "gauge_set", "gauge_add", "span"}
)
# positional/config kwargs that are NOT metric labels
GL006_NON_LABEL_KWARGS = frozenset({"by", "buckets"})
GL006_FORBIDDEN_LABELS = frozenset({"id", "trace_id", "sql", "query", "path"})
GL006_NAME_RE = r"^[a-z][a-z0-9_]*$"


@_rule("GL006", "telemetry metric-name / label-cardinality hygiene")
def gl006(modules: List[Module]) -> List[Finding]:
    import re as _re

    out: List[Finding] = []
    # metric -> {frozenset(label keys) -> [(module, node), ...] all sites}
    label_sets: Dict[str, Dict[frozenset, List[Tuple[Module, ast.Call]]]] = {}
    for m in modules:
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            if recv != "telemetry" or attr not in GL006_WRITERS:
                continue
            if not node.args:
                continue
            name_node = node.args[0]
            if not (
                isinstance(name_node, ast.Constant)
                and isinstance(name_node.value, str)
            ):
                out.append(
                    Finding(
                        "GL006", m.rel, node.lineno, node.col_offset,
                        f"telemetry.{attr} with a DYNAMIC metric name — "
                        "unbounded series cardinality; use a static name "
                        "and put the variable part in a label",
                        f"GL006:{m.rel}:{m.enclosing_def(node)}:dynamic-name",
                    )
                )
                continue
            metric = name_node.value
            if not _re.match(GL006_NAME_RE, metric):
                out.append(
                    Finding(
                        "GL006", m.rel, node.lineno, node.col_offset,
                        f"metric name {metric!r} is not a valid Prometheus "
                        "base name ([a-z][a-z0-9_]*)",
                        f"GL006:{metric}:name",
                    )
                )
            keys = []
            for kw in node.keywords:
                if kw.arg is None:
                    out.append(
                        Finding(
                            "GL006", m.rel, node.lineno, node.col_offset,
                            f"telemetry.{attr}({metric!r}, **dynamic) — "
                            "label KEYS must be static keywords",
                            f"GL006:{metric}:dynamic-labels",
                        )
                    )
                    continue
                if kw.arg in GL006_NON_LABEL_KWARGS:
                    continue
                keys.append(kw.arg)
                if kw.arg in GL006_FORBIDDEN_LABELS:
                    out.append(
                        Finding(
                            "GL006", m.rel, node.lineno, node.col_offset,
                            f"label key {kw.arg!r} on {metric!r} is "
                            "high-cardinality by construction (per-request "
                            "values) — join via the slow/error rings or "
                            "traces instead",
                            f"GL006:{metric}:label:{kw.arg}",
                        )
                    )
            label_sets.setdefault(metric, {}).setdefault(
                frozenset(keys), []
            ).append((m, node))
    # cross-site consistency: one metric, one label-key set (Prometheus
    # aggregation breaks silently otherwise). Canonical = the set used at
    # the MOST call sites (an outlier new site must not out-vote five
    # established ones just by carrying more keys); ties break to the
    # larger set.
    for metric, sets in sorted(label_sets.items()):
        if len(sets) <= 1:
            continue
        majority = max(sets, key=lambda s: (len(sets[s]), len(s), sorted(s)))
        for keyset, sites in sorted(
            sets.items(), key=lambda kv: (kv[1][0][0].rel, kv[1][0][1].lineno)
        ):
            if keyset == majority:
                continue
            m, node = sites[0]
            out.append(
                Finding(
                    "GL006", m.rel, node.lineno, node.col_offset,
                    f"metric {metric!r} emitted with label keys "
                    f"{sorted(keyset) or '[]'} here ({len(sites)} site(s)) "
                    f"but {sorted(majority)} at {len(sets[majority])} "
                    "other site(s) — inconsistent label sets break "
                    "aggregation",
                    f"GL006:{metric}:labelset:{','.join(sorted(keyset))}",
                )
            )
    return out


# ------------------------------------------------------------------ GL007
# Manual span-record calls that hand-name their span: tracing.pop's 2nd
# positional arg, tracing.record_span_into's 2nd positional arg.
GL007_SPAN_RECORDERS = {"pop": 1, "record_span_into": 1}


@_rule("GL007", "manual span name drifting from its observe() metric family")
def gl007(modules: List[Module]) -> List[Finding]:
    """Sites that record a span by hand (tracing.pop / record_span_into)
    AND feed a duration histogram (telemetry.observe) in the same function
    must use ONE name for both — the span tree and the metric family are
    two views of the same instrument, and a drifted name breaks the
    trace<->metric join (`knn_search` spans with an `ivf_probe` histogram
    would never correlate). telemetry.span() is exempt: it feeds both from
    one name by construction."""
    out: List[Finding] = []
    for m in modules:
        for fn in ast.walk(m.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            observes: Set[str] = set()
            spans: List[Tuple[str, ast.Call]] = []
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                recv, attr = _call_name(node)
                if recv == "telemetry" and attr == "observe" and node.args:
                    a0 = node.args[0]
                    if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                        observes.add(a0.value)
                elif recv == "tracing" and attr in GL007_SPAN_RECORDERS:
                    idx = GL007_SPAN_RECORDERS[attr]
                    if len(node.args) > idx:
                        a = node.args[idx]
                        if isinstance(a, ast.Constant) and isinstance(a.value, str):
                            spans.append((a.value, node))
            if not observes or not spans:
                continue
            for name, node in spans:
                if name in observes:
                    continue
                out.append(
                    Finding(
                        "GL007", m.rel, node.lineno, node.col_offset,
                        f"manual span {name!r} recorded in a function whose "
                        f"observe() families are {sorted(observes)} — span "
                        "name and metric family must match for the "
                        "trace<->metric join; rename one (or move the span "
                        "to telemetry.span())",
                        f"GL007:{m.rel}:{m.enclosing_def(node)}:{name}",
                    )
                )
    return out


# ------------------------------------------------------------------ GL008
# Fault-handling hygiene (the failpoint engine's static companion): a retry
# loop with no backoff hammers whatever just failed, and a bare
# `except Exception: pass` erases the evidence every recovery path needs.
GL008_BROAD_TYPES = frozenset({"Exception", "BaseException"})
GL008_PACING_CALLS = frozenset({"sleep", "wait"})


def _gl008_is_const_true(test: ast.AST) -> bool:
    return isinstance(test, ast.Constant) and test.value is True


def _gl008_has_pacing(node: ast.AST) -> bool:
    """A sleep()/Event.wait()-class call anywhere in the loop body — the
    minimum evidence of backoff between retry attempts."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            _, attr = _call_name(sub)
            if attr in GL008_PACING_CALLS:
                return True
    return False


@_rule("GL009", "event emitted outside events.emit / with an unregistered kind")
def gl009(modules: List[Module]) -> List[Finding]:
    """The event timeline (surrealdb_tpu/events.py) is a CLOSED registry:
    every emission goes through `events.emit(kind, ...)` with a kind
    declared in events.KINDS — a dynamic or unregistered kind is a
    timeline entry nobody can filter, alert on, or document, and an
    ad-hoc append to the ring (`events._ring`) bypasses the trace link,
    the counter, and the runtime registry check."""
    kinds = _gl009_registry()
    out: List[Finding] = []
    for m in modules:
        if m.rel == "surrealdb_tpu/events.py":
            continue
        # direct-import aliases: `from surrealdb_tpu.events import emit`
        # (or `emit as e`) must not bypass the rule, and importing the
        # ring itself is flagged at the import site
        emit_names: Set[str] = set()
        for imp in ast.walk(m.tree):
            if not (
                isinstance(imp, ast.ImportFrom)
                and imp.module == "surrealdb_tpu.events"
            ):
                continue
            for a in imp.names:
                if a.name == "emit":
                    emit_names.add(a.asname or a.name)
                elif a.name == "_ring":
                    out.append(
                        Finding(
                            "GL009", m.rel, imp.lineno, imp.col_offset,
                            "importing events._ring — the timeline is "
                            "written only through events.emit(kind, ...) "
                            "(trace link + counter + registry check)",
                            f"GL009:{m.rel}:import:_ring",
                        )
                    )
        for node in ast.walk(m.tree):
            # (a) ad-hoc ring access: events._ring.<anything> outside the
            # module that owns it
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "_ring"
                and isinstance(node.value, ast.Name)
                and node.value.id in ("events", "_events")
            ):
                out.append(
                    Finding(
                        "GL009", m.rel, node.lineno, node.col_offset,
                        "direct events._ring access — the timeline is "
                        "written only through events.emit(kind, ...) "
                        "(trace link + counter + registry check)",
                        f"GL009:{m.rel}:{m.enclosing_def(node)}:ring",
                    )
                )
                continue
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            is_emit = (attr == "emit" and recv in ("events", "_events")) or (
                recv is None and attr in emit_names
            )
            if not is_emit:
                continue
            if not node.args:
                continue
            names = _gl009_kind_strings(node.args[0])
            if names is None:
                out.append(
                    Finding(
                        "GL009", m.rel, node.lineno, node.col_offset,
                        "events.emit with a DYNAMIC kind — kinds are a "
                        "closed registry (events.KINDS); use a static "
                        "registered string and put the variable part in "
                        "a field",
                        f"GL009:{m.rel}:{m.enclosing_def(node)}:dynamic-kind",
                    )
                )
                continue
            for name in names:
                if kinds is not None and name not in kinds:
                    out.append(
                        Finding(
                            "GL009", m.rel, node.lineno, node.col_offset,
                            f"events.emit kind {name!r} is not in the "
                            "events.KINDS registry — register it (with a "
                            "description) before emitting",
                            f"GL009:{m.rel}:kind:{name}",
                        )
                    )
    return out


def _gl009_kind_strings(a0: ast.AST) -> Optional[List[str]]:
    """Static kind candidates of an emit's first arg: a string constant,
    or a conditional expression whose branches both resolve statically
    (`"a.up" if up else "a.down"` names two registered kinds). None means
    the kind is dynamic."""
    if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
        return [a0.value]
    if isinstance(a0, ast.IfExp):
        body = _gl009_kind_strings(a0.body)
        orelse = _gl009_kind_strings(a0.orelse)
        if body is not None and orelse is not None:
            return body + orelse
    return None


def _gl009_registry() -> Optional[Set[str]]:
    """The declared kind registry. Imported from the real module (linting
    runs from the repo root) so the rule and the runtime check can never
    drift; None (skip the kind check) if the engine is unimportable."""
    try:
        from surrealdb_tpu.events import KINDS

        return set(KINDS)
    except Exception:  # noqa: BLE001 — lint must not require a working engine
        return None


# ------------------------------------------------------------------ GL010
# BaseException catches KeyboardInterrupt/SystemExit and the sanitizer's
# own control exceptions: outside the supervisor sites that deliberately
# firewall service loops (bg.py) and the fault-injection engine
# (faults.py), a handler may only catch BaseException to CLEAN UP AND
# RE-RAISE. A handler that terminates the exception converts a process
# shutdown into a half-alive engine.
GL010_ALLOWED_FILES = frozenset(
    {"surrealdb_tpu/bg.py", "surrealdb_tpu/faults.py"}
)


@_rule("GL010", "except BaseException without re-raise outside bg.py/faults.py")
def gl010(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL010_ALLOWED_FILES:
            continue
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            t = node.type
            names = []
            if isinstance(t, ast.Name):
                names = [t.id]
            elif isinstance(t, ast.Tuple):
                names = [e.id for e in t.elts if isinstance(e, ast.Name)]
            # a bare `except:` IS `except BaseException:` — same hazard
            if t is not None and "BaseException" not in names:
                continue
            # cleanup-then-propagate is the sanctioned shape: any raise
            # inside the handler body keeps the exception alive
            if any(isinstance(n, ast.Raise) for n in ast.walk(node)):
                continue
            out.append(
                Finding(
                    "GL010", m.rel, node.lineno, node.col_offset,
                    "`except BaseException` that terminates the exception "
                    "— this swallows KeyboardInterrupt/SystemExit too; "
                    "narrow to Exception, or re-raise after cleanup "
                    "(supervisor firewalls live only in bg.py/faults.py)",
                    f"GL010:{m.rel}:{m.enclosing_def(node)}",
                )
            )
    return out


# ------------------------------------------------------------------ GL011
# Every named engine lock must be declared in utils/locks.HIERARCHY: the
# runtime sanitizer can only prove an order for levels it knows, and the
# graftflow static lock-order proof (GF001) skips undeclared names
# entirely — an undeclared lock is a lock with NO deadlock coverage.
# Today an unhierarchied name is only caught when a sanitized test run
# happens to nest it; this rule fails it at lint time, before any test.
GL011_ALLOWED_FILES = frozenset({"surrealdb_tpu/utils/locks.py"})
GL011_LOCK_RECEIVERS = frozenset({"locks", "_locks"})
GL011_LOCKS_MODULE = "surrealdb_tpu.utils.locks"


def _gl011_lock_aliases(m: Module) -> Set[str]:
    """Every local alias the locks module is importable under in this
    file — `import surrealdb_tpu.utils.locks as lk` must not dodge the
    rule just by not being named 'locks'/'_locks'."""
    out = set(GL011_LOCK_RECEIVERS)
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == GL011_LOCKS_MODULE and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                full = f"{node.module}.{a.name}"
                if full == GL011_LOCKS_MODULE or (
                    a.name == "locks" and node.module.endswith("utils")
                ):
                    out.add(a.asname or a.name)
    return out


def _gl011_hierarchy():
    """Imported from the REAL module (linting runs from the repo root) so
    the rule and the runtime check can never drift; None skips the check."""
    try:
        from surrealdb_tpu.utils.locks import HIERARCHY

        return set(HIERARCHY)
    except Exception:  # noqa: BLE001 — lint must not require a working engine
        return None


@_rule("GL011", "locks.Lock/RLock name missing from the declared HIERARCHY")
def gl011(modules: List[Module]) -> List[Finding]:
    declared = _gl011_hierarchy()
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL011_ALLOWED_FILES:
            continue
        aliases = _gl011_lock_aliases(m)
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            if attr not in ("Lock", "RLock") or recv not in aliases:
                continue
            a0 = node.args[0] if node.args else None
            if a0 is None:  # locks.Lock(name="...") is legal too
                a0 = next(
                    (kw.value for kw in node.keywords if kw.arg == "name"), None
                )
            if not (isinstance(a0, ast.Constant) and isinstance(a0.value, str)):
                out.append(
                    Finding(
                        "GL011", m.rel, node.lineno, node.col_offset,
                        f"locks.{attr} with a DYNAMIC (or missing) name — "
                        "lock names are the unit of the declared order; use "
                        "a static string registered in locks.HIERARCHY",
                        f"GL011:{m.rel}:{m.enclosing_def(node)}:dynamic-name",
                    )
                )
                continue
            name = a0.value
            if declared is not None and name not in declared:
                out.append(
                    Finding(
                        "GL011", m.rel, node.lineno, node.col_offset,
                        f"lock name {name!r} is not declared in "
                        "utils/locks.HIERARCHY — it has no level, so neither "
                        "the runtime sanitizer nor graftflow GF001 can prove "
                        "any ordering against it; declare it (with a level) "
                        "before acquiring it",
                        f"GL011:{m.rel}:name:{name}",
                    )
                )
    return out


@_rule("GL008", "retry loop without backoff/attempt cap; bare except-swallow")
def gl008(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        for node in ast.walk(m.tree):
            # (a) swallow: a broad handler whose whole body is `pass` —
            # the failure is erased, not handled (narrow the type, log it,
            # or record it somewhere a human can find)
            if isinstance(node, ast.ExceptHandler):
                t = node.type
                broad = t is None or (
                    isinstance(t, ast.Name) and t.id in GL008_BROAD_TYPES
                )
                if broad and all(isinstance(b, ast.Pass) for b in node.body):
                    out.append(
                        Finding(
                            "GL008", m.rel, node.lineno, node.col_offset,
                            "bare `except Exception: pass` swallows the "
                            "failure with no trace — narrow the type, or "
                            "record it (telemetry/bg record/log) before "
                            "continuing",
                            f"GL008:{m.rel}:{m.enclosing_def(node)}:swallow",
                        )
                    )
            # (b) unbounded retry loop with no pacing: `while True` whose
            # exception handler `continue`s straight back into the attempt
            # with no sleep/wait anywhere in the loop — a tight hammer on
            # whatever just failed
            elif isinstance(node, ast.While) and _gl008_is_const_true(node.test):
                retries = any(
                    isinstance(sub, ast.Try)
                    and any(
                        any(isinstance(x, ast.Continue) for x in ast.walk(h))
                        for h in sub.handlers
                    )
                    for sub in ast.walk(node)
                )
                if retries and not _gl008_has_pacing(node):
                    out.append(
                        Finding(
                            "GL008", m.rel, node.lineno, node.col_offset,
                            "`while True` retry loop with no backoff — a "
                            "failing dependency gets hammered at CPU speed; "
                            "add exponential backoff (and an attempt cap) "
                            "or bound the loop",
                            f"GL008:{m.rel}:{m.enclosing_def(node)}:retry",
                        )
                    )
    return out


# ------------------------------------------------------------------ GL012
# The statement-statistics store (surrealdb_tpu/stats.py) has ONE write
# door: stats.record(). It owns the lock discipline (mutate under
# stats.store, emit events/counters only after release) and the plan-flip
# detection; an ad-hoc writer reaching into the private store, the
# activation table, or the entry class would bypass both. Outside
# stats.py, touching any private member of the stats module is a finding.
GL012_ALLOWED_FILES = frozenset({"surrealdb_tpu/stats.py"})
GL012_STATS_MODULE = "surrealdb_tpu.stats"
GL012_PRIVATE = frozenset(
    {"_store", "_lock", "_active_by_thread", "_Entry", "_evicted",
     "_note_evictions"}
)


def _gl012_stats_aliases(m: Module) -> Set[str]:
    """Every local NAME the stats module is bound to in this file
    (`from surrealdb_tpu import stats [as _stats]`,
    `import surrealdb_tpu.stats as x`). A plain
    `import surrealdb_tpu.stats` binds only `surrealdb_tpu` — that access
    path is matched as the dotted chain in gl012(), not as an alias."""
    out: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == GL012_STATS_MODULE and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                if (
                    f"{node.module}.{a.name}" == GL012_STATS_MODULE
                    or (a.name == "stats" and node.module == "surrealdb_tpu")
                ):
                    out.add(a.asname or a.name)
    return out


def _gl012_dotted(node) -> Optional[str]:
    """`a.b.c` rendered as a dotted name, None when the chain's root is
    not a plain Name (a call/subscript can't be the module)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@_rule("GL012", "ad-hoc access to the statement-stats store outside stats.record()")
def gl012(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL012_ALLOWED_FILES:
            continue
        aliases = _gl012_stats_aliases(m)
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Attribute):
                continue
            if node.attr not in GL012_PRIVATE:
                continue
            via_alias = (
                isinstance(node.value, ast.Name) and node.value.id in aliases
            )
            # the dotted form a plain `import surrealdb_tpu.stats` enables
            via_dotted = _gl012_dotted(node.value) == GL012_STATS_MODULE
            if not (via_alias or via_dotted):
                continue
            out.append(
                Finding(
                    "GL012", m.rel, node.lineno, node.col_offset,
                    f"stats.{node.attr} accessed outside stats.py — "
                    "statement-stats recording must go through "
                    "stats.record() (the one door that keeps the lock "
                    "discipline and the plan-flip detection honest)",
                    f"GL012:{m.rel}:{m.enclosing_def(node)}:{node.attr}",
                )
            )
    return out


# ------------------------------------------------------------------ GL013
# The tenant cost-attribution store (surrealdb_tpu/accounting.py) has ONE
# write door: accounting.charge(). It owns the lock discipline (mutate
# under accounting.store, emit breach events/counters only after release),
# the budget crossing detection and the store/fp-cap eviction; an ad-hoc
# writer reaching into the private store, the activation/tally tables, or
# the entry class would bypass all three — and break the conservation
# property tests/test_accounting.py holds. Outside accounting.py, touching
# any private member of the accounting module is a finding.
GL013_ALLOWED_FILES = frozenset({"surrealdb_tpu/accounting.py"})
GL013_ACCT_MODULE = "surrealdb_tpu.accounting"
GL013_PRIVATE = frozenset(
    {"_store", "_lock", "_global", "_evicted", "_Entry",
     "_active_by_thread", "_tally_by_thread", "_tenant_ctx",
     "_budget_cache"}
)


def _gl013_acct_aliases(m: Module) -> Set[str]:
    """Every local NAME the accounting module is bound to in this file
    (mirrors _gl012_stats_aliases; a plain `import surrealdb_tpu.accounting`
    is matched as the dotted chain in gl013())."""
    out: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == GL013_ACCT_MODULE and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                if (
                    f"{node.module}.{a.name}" == GL013_ACCT_MODULE
                    or (a.name == "accounting" and node.module == "surrealdb_tpu")
                ):
                    out.add(a.asname or a.name)
    return out


@_rule("GL013", "ad-hoc access to the tenant-accounting store outside accounting.charge()")
def gl013(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL013_ALLOWED_FILES:
            continue
        aliases = _gl013_acct_aliases(m)
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Attribute):
                continue
            if node.attr not in GL013_PRIVATE:
                continue
            via_alias = (
                isinstance(node.value, ast.Name) and node.value.id in aliases
            )
            via_dotted = _gl012_dotted(node.value) == GL013_ACCT_MODULE
            if not (via_alias or via_dotted):
                continue
            out.append(
                Finding(
                    "GL013", m.rel, node.lineno, node.col_offset,
                    f"accounting.{node.attr} accessed outside accounting.py "
                    "— tenant-meter mutation must go through "
                    "accounting.charge() (the one door that keeps the lock "
                    "discipline, budget detection and conservation honest)",
                    f"GL013:{m.rel}:{m.enclosing_def(node)}:{node.attr}",
                )
            )
    return out


# ------------------------------------------------------------------ GL015
# The plan/pipeline cache (surrealdb_tpu/dbs/plan_cache.py) has ONE write
# door: the PlanCache methods themselves (fetch/observe/install_*/
# bump_generation/ddl_begin/ddl_end/on_plan_flip/note_epoch/clear). They
# own the lock discipline (mutate under plan_cache.store, emit eviction
# events/counters only after release) and the validation-on-serve
# contract — generation/epoch/scope stamps checked on every serve. An
# ad-hoc writer reaching into the private tables (`_entries`, `_gen`,
# route maps) would bypass both and could serve a
# stale plan, the one failure mode the cache is built to make impossible.
# Outside plan_cache.py, touching any private member of the module OR of
# a PlanCache INSTANCE (any attribute chain ending in `.plan_cache`, the
# datastore's handle) is a finding.
GL015_ALLOWED_FILES = frozenset({"surrealdb_tpu/dbs/plan_cache.py"})
GL015_PC_MODULE = "surrealdb_tpu.dbs.plan_cache"
GL015_PRIVATE = frozenset(
    {"_entries", "_warm", "_by_stmt", "_index_defs", "_gen", "_inflight",
     "_epoch", "_hits", "_misses", "_invalidations", "_verifies",
     "_evlog", "_lock", "_caches", "_serve_digest", "_serve_lexed",
     "_route_for", "_emit_evict"}
)


def _gl015_pc_aliases(m: Module) -> Set[str]:
    """Every local NAME the plan_cache module is bound to in this file
    (mirrors _gl012_stats_aliases)."""
    out: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == GL015_PC_MODULE and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                if (
                    f"{node.module}.{a.name}" == GL015_PC_MODULE
                    or (a.name == "plan_cache"
                        and node.module == "surrealdb_tpu.dbs")
                ):
                    out.add(a.asname or a.name)
    return out


@_rule("GL015", "plan-cache state mutated outside the cache's write door")
def gl015(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if m.rel in GL015_ALLOWED_FILES:
            continue
        aliases = _gl015_pc_aliases(m)
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Attribute):
                continue
            if node.attr not in GL015_PRIVATE:
                continue
            # module-level access: plan_cache._caches via alias or the
            # dotted form a plain `import surrealdb_tpu.dbs.plan_cache`
            # enables
            via_alias = (
                isinstance(node.value, ast.Name) and node.value.id in aliases
            )
            via_dotted = _gl012_dotted(node.value) == GL015_PC_MODULE
            # instance access: any chain ENDING in `.plan_cache` is the
            # datastore's cache handle (ds.plan_cache._entries,
            # self.ds.plan_cache._lock, ctx.executor.ds.plan_cache._gen…)
            via_instance = (
                isinstance(node.value, ast.Attribute)
                and node.value.attr == "plan_cache"
            )
            if not (via_alias or via_dotted or via_instance):
                continue
            out.append(
                Finding(
                    "GL015", m.rel, node.lineno, node.col_offset,
                    f"plan_cache.{node.attr} accessed outside "
                    "dbs/plan_cache.py — plan-cache state must go through "
                    "the PlanCache write door (the methods that keep the "
                    "lock discipline and the validation-on-serve stamps "
                    "honest; a bypass can serve a stale plan)",
                    f"GL015:{m.rel}:{m.enclosing_def(node)}:{node.attr}",
                )
            )
    return out


# ------------------------------------------------------------------ GL016
# Event-loop-marked modules (module-level `EVENT_LOOP_MODULE = True`, e.g.
# surrealdb_tpu/net/loop.py) multiplex 100k+ sockets on a handful of
# threads: ONE blocking call stalls every connection the thread owns. Two
# classes of finding inside a marked module:
#   - blocking socket calls — `.recv()`, `.sendall()`, `.accept()` (and
#     recv variants) anywhere except inside a `_nb_`-prefixed nonblocking
#     wrapper function, which is where EAGAIN is actually handled;
#   - `time.sleep` ANYWHERE — loop pacing belongs to selector timeouts
#     and `Event.wait`, which a shutdown can interrupt; a sleep can't be.
GL016_MARKER = "EVENT_LOOP_MODULE"
GL016_BLOCKING = frozenset({"recv", "recv_into", "recvfrom", "sendall", "accept"})


def _gl016_marked(m: Module) -> bool:
    """True for modules declaring `EVENT_LOOP_MODULE = True` at top level."""
    for node in m.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (
                    isinstance(t, ast.Name)
                    and t.id == GL016_MARKER
                    and isinstance(node.value, ast.Constant)
                    and bool(node.value.value)
                ):
                    return True
    return False


@_rule("GL016", "blocking socket call / time.sleep in an event-loop module")
def gl016(modules: List[Module]) -> List[Finding]:
    out: List[Finding] = []
    for m in modules:
        if not _gl016_marked(m):
            continue
        sleep_direct = "sleep" in _from_imports(m, "time")
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = _call_name(node)
            fn = m.enclosing_def(node) or ""
            if attr in GL016_BLOCKING and not fn.split(".")[-1].startswith("_nb_"):
                out.append(
                    Finding(
                        "GL016", m.rel, node.lineno, node.col_offset,
                        f"blocking socket .{attr}() on an event-loop thread "
                        "— one blocked call stalls every connection this "
                        "loop owns; go through a `_nb_*` nonblocking "
                        "wrapper that handles EAGAIN",
                        f"GL016:{m.rel}:{fn}:{attr}",
                    )
                )
            is_sleep = attr == "sleep" and (
                (recv is not None and "time" in recv)
                or (recv is None and sleep_direct)
            )
            if is_sleep:
                out.append(
                    Finding(
                        "GL016", m.rel, node.lineno, node.col_offset,
                        "time.sleep in an event-loop module — pace with "
                        "selector timeouts or Event.wait (interruptible at "
                        "shutdown); a sleeping loop thread is a stalled "
                        "ingress",
                        f"GL016:{m.rel}:{fn}:sleep",
                    )
                )
    return out
